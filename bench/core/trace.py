"""Reduction of a JAX profiler trace to the per-layer device numbers.

The traced run wraps its window in a ``bench_window`` host annotation;
everything is clipped to that interval.  Per chip (device planes
``/device:TPU:<i>``, in order, the cell's first ``chips``), the op
intervals of the ``XLA Ops`` line give:

* busy time: the length of the union of the op intervals;
* all-to-all time, and its exposed part: the all-to-all intervals minus
  the union of every other op's intervals on that chip;
* each op's summed duration, for the breakdown's ``device_ops``;
* the idle gaps between busy intervals, each named by the host event
  that overlaps it most (host threads, annotations excluded), for the
  breakdown's ``idle_gaps``.

Chips are averaged.  Only JAX and numpy are used, so the reduction can be
checked on a trace recorded on the CPU.
"""
from __future__ import annotations

import glob
import os
from typing import Dict, Optional, Tuple

import numpy as np

WINDOW = "bench_window"
OPS_LINES = ("XLA Ops",)
ALL_TO_ALL = ("all-to-all", "all_to_all", "alltoall")


# ---------------------------------------------------------------------------
# interval arithmetic
# ---------------------------------------------------------------------------
def union(starts: np.ndarray, ends: np.ndarray) -> Tuple[np.ndarray, np.ndarray]:
    """Merged, sorted, disjoint intervals covering the inputs."""
    if len(starts) == 0:
        return np.empty(0, np.int64), np.empty(0, np.int64)
    order = np.argsort(starts, kind="stable")
    s, e = starts[order], ends[order]
    run_end = np.maximum.accumulate(e)
    new = np.ones(len(s), bool)
    new[1:] = s[1:] > run_end[:-1]
    idx = np.flatnonzero(new)
    return s[idx], np.maximum.reduceat(run_end, idx)


def length(starts: np.ndarray, ends: np.ndarray) -> int:
    s, e = union(starts, ends)
    return int((e - s).sum())


def clip(starts, ends, lo: int, hi: int):
    s, e = np.maximum(starts, lo), np.minimum(ends, hi)
    keep = e > s
    return s[keep], e[keep]


def minus(a_s, a_e, b_s, b_e) -> int:
    """Length of union(a) not covered by union(b)."""
    a_s, a_e = union(a_s, a_e)
    b_s, b_e = union(b_s, b_e)
    total = int((a_e - a_s).sum())
    if not len(b_s) or not total:
        return total
    # overlap of two disjoint sorted interval sets
    covered = 0
    j = 0
    for s, e in zip(a_s.tolist(), a_e.tolist()):
        while j < len(b_e) and b_e[j] <= s:
            j += 1
        k = j
        while k < len(b_s) and b_s[k] < e:
            covered += min(e, int(b_e[k])) - max(s, int(b_s[k]))
            k += 1
    return total - covered


def gaps(starts, ends, lo: int, hi: int):
    """Idle intervals of [lo, hi) outside union(starts, ends)."""
    s, e = union(*clip(starts, ends, lo, hi))
    g_s = np.concatenate([[lo], e])
    g_e = np.concatenate([s, [hi]])
    keep = g_e > g_s
    return g_s[keep], g_e[keep]


# ---------------------------------------------------------------------------
# reading the trace
# ---------------------------------------------------------------------------
def _events(line):
    names, s, d = [], [], []
    for ev in line.events:
        names.append(ev.name)
        s.append(ev.start_ns)
        d.append(ev.duration_ns)
    s = np.asarray(s, np.float64).astype(np.int64)
    return names, s, s + np.asarray(d, np.float64).astype(np.int64)


def load(trace_dir: str):
    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    from jax.profiler import ProfileData
    return ProfileData.from_file(paths[-1])


def _device_index(name: str) -> Optional[int]:
    pre = "/device:TPU:"
    if name.startswith(pre) and name[len(pre):].isdigit():
        return int(name[len(pre):])
    return None


def reduce(trace_dir: str, chips: int, top: int = 10) -> Dict:
    """Per-chip busy, all-to-all and gap figures of the traced window."""
    pd = load(trace_dir)
    window = None
    host = []                                  # (names, starts, ends)
    devices = {}
    for plane in pd.planes:
        idx = _device_index(plane.name)
        if idx is not None:
            for line in plane.lines:
                if line.name in OPS_LINES:
                    devices[idx] = _events(line)
            continue
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                names, s, e = _events(line)
                for n, a, b in zip(names, s, e):
                    if n == WINDOW:
                        window = (int(a), int(b))
                host.append((names, s, e))
    if window is None:
        raise ValueError(f"no {WINDOW!r} annotation in the trace")
    if len(devices) < chips:
        raise ValueError(f"trace holds ops of {len(devices)} chips, the "
                         f"cell has {chips}")
    lo, hi = window
    per_chip, op_time, all_gaps = [], {}, []
    for idx in sorted(devices)[:chips]:
        names, s, e = devices[idx]
        inside = (e > lo) & (s < hi)
        names = [n for n, k in zip(names, inside) if k]
        s, e = np.maximum(s[inside], lo), np.minimum(e[inside], hi)
        is_a2a = np.array([any(t in n.lower() for t in ALL_TO_ALL)
                           for n in names], bool)
        busy = length(s, e)
        a2a = length(s[is_a2a], e[is_a2a])
        exposed = minus(s[is_a2a], e[is_a2a], s[~is_a2a], e[~is_a2a])
        for n, d in zip(names, (e - s).tolist()):
            op_time[n] = op_time.get(n, 0) + d / chips
        g_s, g_e = gaps(s, e, lo, hi)
        all_gaps += list(zip(g_s.tolist(), g_e.tolist()))
        per_chip.append({"busy_s": busy * 1e-9, "all_to_all_s": a2a * 1e-9,
                         "exposed_all_to_all_s": exposed * 1e-9,
                         "ops": len(names), "all_to_all_ops": int(is_a2a.sum())})
    mean = lambda k: float(np.mean([c[k] for c in per_chip]))
    all_gaps.sort(key=lambda g: g[0] - g[1])
    return {
        "window_s": (hi - lo) * 1e-9,
        "busy_s": mean("busy_s"),
        "all_to_all_s": mean("all_to_all_s"),
        "exposed_all_to_all_s": mean("exposed_all_to_all_s"),
        "collective_ops": sum(c["all_to_all_ops"] for c in per_chip),
        "chips": per_chip,
        "device_ops": sorted(op_time.items(), key=lambda kv: -kv[1])[:top],
        "idle_gaps": [(_host_label(host, a, b), (b - a) * 1e-9)
                      for a, b in all_gaps[:top]],
    }


def _host_label(host, lo: int, hi: int) -> str:
    """The host event (not the window annotation) overlapping [lo, hi) the
    most, or 'no host event'."""
    best, best_ov = "no host event", 0
    for names, s, e in host:
        ov = np.minimum(e, hi) - np.maximum(s, lo)
        ok = ov > 0
        if not ok.any():
            continue
        for i in np.flatnonzero(ok):
            if names[i] != WINDOW and ov[i] > best_ov:
                best, best_ov = names[i], int(ov[i])
    return best


def breakdown(reduced: Dict) -> Dict:
    """The result line's ``breakdown``: top device ops and idle gaps."""
    return {"device_ops": [[n, s * 1e-9] for n, s in reduced["device_ops"]],
            "idle_gaps": [[n, s] for n, s in reduced["idle_gaps"]]}
