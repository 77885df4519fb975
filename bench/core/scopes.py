"""The traced window by the program's own names, beside ``core/trace.py``.

The program names its work twice over (``PERF.md`` section 3):

* the step loop's ``obs`` spans are ``jax.profiler`` annotations on the
  loop's thread -- ``epoch_fill``, ``batch_wait``, ``step`` (with
  ``step_sync`` inside) and ``epoch_end`` tile an epoch;
* the compiled step's ops carry ``jax.named_scope`` names in their
  ``op_name`` metadata -- ``feature_gather``, ``hec_lookup``,
  ``hec_store``, ``layer<k>_aggregate``, ``layer<k>_update``, ``loss``,
  ``optimizer``, ``aep_pack``, ``aep_exchange``, ``aep_consume``.  Backward
  ops keep their forward's scope inside ``transpose(jvp(...))``.

A device op event is named by its HLO instruction (``%fusion.8 = ...``);
the compiled step's HLO text (``jitted.lower(...).compile().as_text()``)
maps each instruction to its ``op_name`` and so to its scopes.  From the
trace and that text, clipped to the ``bench_window`` annotation:

* ``scope_s``: device seconds of the ops under each scope, mean over
  chips (an op counts for every scope on its path: ``aep_consume`` sits
  inside ``hec_store``), ``unattributed``, the share of op time under
  no scope, and ``unattributed_ops``, the longest of those ops;
* ``loop_s``: seconds of each loop span on the line that holds the window
  annotation, and ``loop_cover``, the share of the window that
  ``epoch_fill``, ``batch_wait``, ``step`` and ``epoch_end`` cover;
* ``idle_on_batch_s``: device idle time that falls inside the loop's
  ``batch_wait`` or ``epoch_fill``, mean over chips.

Only JAX and numpy are used, so the reduction can be checked on the CPU.
"""
from __future__ import annotations

import re
from typing import Dict, Tuple

import numpy as np

from core import trace

SCOPES = ("feature_gather", "hec_lookup", "hec_store", "loss", "optimizer",
          "aep_pack", "aep_exchange", "aep_consume")
LAYER_SCOPE = re.compile(r"layer\d+_(aggregate|update)$")
LOOP = ("epoch_fill", "batch_wait", "step", "epoch_end")
LOOP_SPANS = LOOP + ("step_sync",)
WAITS = ("epoch_fill", "batch_wait")

_COMPUTATION = re.compile(r"^\s*(?:ENTRY\s+)?%?([\w.\-]+)\s.*\{\s*$")
_INSTR = re.compile(r"^\s*(ROOT\s+)?%?([\w.\-]+)\s*=")
_OP_NAME = re.compile(r"metadata=\{[^}]*?op_name=\"([^\"]*)\"")
_CALLS = re.compile(r"calls=%?([\w.\-]+)")
_EVENT = re.compile(r"^%?([\w.\-]+)")


def is_scope(token: str) -> bool:
    return token in SCOPES or bool(LAYER_SCOPE.match(token))


def op_scopes(hlo_text: str) -> Dict[str, Tuple[str, ...]]:
    """HLO instruction name -> the scopes on its ``op_name``, outermost
    first (empty where the op is under none).  An instruction without
    metadata (a fusion may have none) takes its called computation's
    root's."""
    op_name, calls, roots = {}, {}, {}
    computation = None
    for line in hlo_text.splitlines():
        m = _INSTR.match(line)
        if m is None:
            c = _COMPUTATION.match(line)
            if c is not None:
                computation = c.group(1)
            continue
        name = m.group(2)
        if m.group(1):
            roots[computation] = name
        o = _OP_NAME.search(line)
        if o is not None:
            op_name[name] = o.group(1)
        k = _CALLS.search(line)
        if k is not None:
            calls[name] = k.group(1)
    out = {}
    for name in set(op_name) | set(calls):
        path = op_name.get(name)
        if path is None:
            path = op_name.get(roots.get(calls[name]), "")
        out[name] = tuple(t for t in re.findall(r"\w+", path)
                          if is_scope(t))
    return out


def instruction(event_name: str) -> str:
    """The HLO instruction an op event is named by."""
    m = _EVENT.match(event_name)
    return m.group(1) if m else event_name


def scope_seconds(names, starts, ends, scopes_of) -> Tuple[Dict, Dict, int]:
    """Per scope, the summed duration (ns) of the ops under it; per
    instruction under no scope, its summed duration; and the total."""
    per, loose, total = {}, {}, 0
    for n, d in zip(names, (ends - starts).tolist()):
        name = instruction(n)
        found = set(scopes_of.get(name, ()))
        total += d
        if not found:
            loose[name] = loose.get(name, 0) + d
        for s in found:
            per[s] = per.get(s, 0) + d
    return per, loose, total


def _window_line(pd):
    """(window, {span: (starts, ends)}) from the host line that holds the
    window annotation."""
    for plane in pd.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            names, s, e = trace._events(line)
            if trace.WINDOW not in names:
                continue
            i = names.index(trace.WINDOW)
            lo, hi = int(s[i]), int(e[i])
            spans = {}
            for span in LOOP_SPANS:
                k = np.array([n == span for n in names], bool)
                spans[span] = trace.clip(s[k], e[k], lo, hi)
            return (lo, hi), spans
    raise ValueError(f"no {trace.WINDOW!r} annotation in the trace")


def reduce(trace_dir: str, chips: int, hlo_text: str, top: int = 10) -> Dict:
    """Scope, loop-span and idle-on-wait seconds of the traced window."""
    pd = trace.load(trace_dir)
    (lo, hi), spans = _window_line(pd)
    devices = {}
    for plane in pd.planes:
        idx = trace._device_index(plane.name)
        if idx is None:
            continue
        for line in plane.lines:
            if line.name in trace.OPS_LINES:
                devices[idx] = trace._events(line)
    if len(devices) < chips:
        raise ValueError(f"trace holds ops of {len(devices)} chips, the "
                         f"cell has {chips}")
    scopes_of = op_scopes(hlo_text)
    w_s = np.concatenate([spans[w][0] for w in WAITS])
    w_e = np.concatenate([spans[w][1] for w in WAITS])
    per_scope, unattributed, total, idle_on = {}, {}, 0, 0
    for idx in sorted(devices)[:chips]:
        names, s, e = devices[idx]
        inside = (e > lo) & (s < hi)
        names = [n for n, k in zip(names, inside) if k]
        s, e = np.maximum(s[inside], lo), np.minimum(e[inside], hi)
        per, loose, t = scope_seconds(names, s, e, scopes_of)
        for k, v in per.items():
            per_scope[k] = per_scope.get(k, 0) + v
        for k, v in loose.items():
            unattributed[k] = unattributed.get(k, 0) + v
        total += t
        g_s, g_e = trace.gaps(s, e, lo, hi)
        idle_on += trace.length(g_s, g_e) - trace.minus(g_s, g_e, w_s, w_e)
    loop = [spans[p] for p in LOOP]
    covered = trace.length(np.concatenate([s for s, _ in loop]),
                           np.concatenate([e for _, e in loop]))
    return {
        "window_s": (hi - lo) * 1e-9,
        "scope_s": {k: v * 1e-9 / chips
                    for k, v in sorted(per_scope.items())},
        "unattributed": (sum(unattributed.values()) / total
                         if total else None),
        "unattributed_ops": [
            (k, v * 1e-9 / chips) for k, v in
            sorted(unattributed.items(), key=lambda kv: -kv[1])[:top]],
        "loop_s": {p: int((e - s).sum()) * 1e-9
                   for p, (s, e) in spans.items()},
        "loop_calls": {p: len(s) for p, (s, _) in spans.items()},
        "loop_cover": covered / (hi - lo),
        "idle_on_batch_s": idle_on * 1e-9 / chips,
    }
