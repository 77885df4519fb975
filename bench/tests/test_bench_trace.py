"""The reduction from a profiler trace to device numbers: the interval
arithmetic against a nanosecond-by-nanosecond count, and the whole
reduction on a trace recorded on the chip and kept in ``data/``."""
from __future__ import annotations

import glob
import os
import sys

import numpy as np
import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH)

from core import trace  # noqa: E402

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")


def _random_intervals(rng, n, span=1000):
    s = rng.integers(0, span, n)
    return s, s + rng.integers(1, 60, n)


def _covered(starts, ends, span=1100):
    mask = np.zeros(span, bool)
    for a, b in zip(starts, ends):
        mask[a:b] = True
    return mask


@pytest.mark.parametrize("seed", range(6))
def test_busy_union_and_exposed_time(seed):
    rng = np.random.default_rng(seed)
    s, e = _random_intervals(rng, 40)
    c_s, c_e = _random_intervals(rng, 8)
    busy = _covered(np.r_[s, c_s], np.r_[e, c_e])
    assert trace.length(np.r_[s, c_s], np.r_[e, c_e]) == busy.sum()
    exposed = _covered(c_s, c_e) & ~_covered(s, e)
    assert trace.minus(c_s, c_e, s, e) == exposed.sum()
    g_s, g_e = trace.gaps(s, e, 100, 900)
    idle = ~_covered(s, e)
    idle[:100] = idle[900:] = False
    assert (g_e - g_s).sum() == idle.sum()


def test_no_collective_means_nothing_exposed():
    s = np.array([0, 10, 20])
    assert trace.minus(np.empty(0, int), np.empty(0, int), s, s + 5) == 0


RECORDED = sorted(glob.glob(os.path.join(DATA, "*_chips")))


@pytest.mark.parametrize("trace_dir", RECORDED)
def test_reduce_a_recorded_chip_trace(trace_dir):
    """A short traced window of the trainer on v5e chips (recorded with
    ``bench/tests/record_trace.py``): busy time per chip against the raw
    events, and the idle gaps and all-to-all time within bounds."""
    chips = int(os.path.basename(trace_dir).split("_")[0])
    red = trace.reduce(trace_dir, chips)
    assert 0 < red["busy_s"] <= red["window_s"]
    assert 0 <= red["exposed_all_to_all_s"] <= red["all_to_all_s"]
    if chips > 1:
        assert red["collective_ops"] > 0 and red["all_to_all_s"] > 0
    assert len(red["device_ops"]) == 10 and len(red["idle_gaps"]) <= 10
    idle = sum(s for _, s in red["idle_gaps"])
    assert idle <= chips * (red["window_s"] - red["busy_s"]) + 1e-9
    pd = trace.load(trace_dir)
    lo = hi = None
    for plane in pd.planes:
        for line in plane.lines:
            for ev in line.events:
                if ev.name == trace.WINDOW:
                    lo, hi = ev.start_ns, ev.start_ns + ev.duration_ns
    busy = []
    for plane in pd.planes:
        if trace._device_index(plane.name) is None:
            continue
        for line in plane.lines:
            if line.name not in trace.OPS_LINES:
                continue
            mask = np.zeros(int(hi - lo) // 1000 + 1, bool)     # 1 us bins
            for ev in line.events:
                a = max(ev.start_ns, lo)
                b = min(ev.start_ns + ev.duration_ns, hi)
                if b > a:
                    mask[int(a - lo) // 1000:int(b - lo) // 1000] = True
            busy.append(mask.sum() * 1e-6)
    assert red["busy_s"] == pytest.approx(np.mean(busy[:chips]), rel=0.02)
