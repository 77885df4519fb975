"""The reduction of a traced window by the program's own names
(``core/scopes.py``): HLO op names to scopes, and the whole reduction on
synthetic traces against a nanosecond-by-nanosecond count, and on a small
trace recorded on the chip and kept in ``data/``."""
from __future__ import annotations

import glob
import gzip
import os
import re
import sys

import numpy as np
import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH)

from core import scopes, trace  # noqa: E402

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")

HLO = """HloModule jit_stepf, entry_computation_layout={(f32[4]{0})->f32[4]{0}}

%fused_computation.3 (param_0: f32[4]) -> f32[4] {
  %param_0 = f32[4]{0} parameter(0)
  ROOT %gather.2 = f32[4]{0} gather(%param_0), metadata={op_name="jit(stepf)/shard_map/hec_lookup/gather" source_file="x.py" source_line=3}
}

ENTRY %main.9 (p: f32[4]) -> f32[4] {
  %p = f32[4]{0} parameter(0)
  %fusion.8 = f32[4]{0} fusion(%p), kind=kLoop, calls=%fused_computation.3
  %fusion.9 = f32[4]{0} fusion(%fusion.8), kind=kLoop, calls=%fused_computation.3, metadata={op_name="jit(stepf)/shard_map/transpose(jvp(layer1_aggregate))/scatter-add"}
  %add.1 = f32[4]{0} add(%fusion.9, %p), metadata={op_name="jit(stepf)/shard_map/hec_store/aep_consume/add"}
  %copy.4 = f32[4]{0} copy(%add.1)
  ROOT %mul.5 = f32[4]{0} multiply(%copy.4, %p), metadata={op_name="jit(stepf)/shard_map/psum/mul"}
}
"""


def test_op_names_map_to_the_program_scopes():
    got = scopes.op_scopes(HLO)
    assert got["fusion.8"] == ("hec_lookup",)        # via its fusion's root
    assert got["fusion.9"] == ("layer1_aggregate",)  # backward keeps scope
    assert got["add.1"] == ("hec_store", "aep_consume")
    assert got["mul.5"] == ()
    assert "copy.4" not in got                       # no metadata at all
    assert scopes.instruction("%fusion.8 = f32[4]{0} fusion(%p)") \
        == "fusion.8"
    assert scopes.instruction("fusion.8") == "fusion.8"


OPS = {"fusion.8": "hec_lookup", "fusion.9": "layer1_aggregate",
       "add.1": "hec_store", "copy.4": None, "mul.5": None}


def _plane(pid, name, lines):
    """Text proto of one XPlane; ``lines`` is [(line name, [(event name,
    start ns, end ns)])]."""
    names = sorted({n for _, evs in lines for n, _, _ in evs})
    ids = {n: i + 1 for i, n in enumerate(names)}
    out = [f'planes {{ id: {pid} name: "{name}"']
    for k, (lname, evs) in enumerate(lines):
        out.append(f'  lines {{ id: {k + 1} name: "{lname}" timestamp_ns: 0')
        for n, a, b in evs:
            out.append(f"    events {{ metadata_id: {ids[n]} offset_ps: "
                       f"{a * 1000} duration_ps: {(b - a) * 1000} }}")
        out.append("  }")
    for n, i in ids.items():
        out.append(f'  event_metadata {{ key: {i} value {{ id: {i} '
                   f'name: "{n}" }} }}')
    out.append("}")
    return "\n".join(out)


def _write_trace(path, host_lines, chips_ops):
    from jax.profiler import ProfileData
    planes = [_plane(1, "/host:CPU", host_lines)]
    for c, ops in enumerate(chips_ops):
        planes.append(_plane(2 + c, f"/device:TPU:{c}", [("XLA Ops", ops)]))
    blob = ProfileData.text_proto_to_serialized_xspace("\n".join(planes))
    d = os.path.join(path, "plugins", "profile", "t")
    os.makedirs(d)
    with open(os.path.join(d, "t.xplane.pb"), "wb") as f:
        f.write(blob)


def _mask(intervals, lo, hi):
    m = np.zeros(hi - lo, bool)
    for a, b in intervals:
        m[max(a, lo) - lo:max(min(b, hi) - lo, 0)] = True
    return m


@pytest.mark.parametrize("seed", range(4))
def test_reduce_synthetic_trace(tmp_path, seed):
    """Two chips of sequential ops; the loop's spans on the window's line
    and a worker's spans on another line that must not count."""
    rng = np.random.default_rng(seed)
    lo, hi = 100, 1900
    loop, t = [], 0
    while t < 2000:                          # spans tile [0, 2000) with gaps
        name = rng.choice(scopes.LOOP)
        d = int(rng.integers(5, 80))
        loop.append((str(name), t, t + d))
        if name == "step" and d > 10:
            loop.append(("step_sync", t + 5, t + d))
        t += d + int(rng.integers(0, 4))
    worker = [("batch_wait", 0, 2000), ("sample", 10, 900)]
    chips_ops = []
    for _ in range(2):
        ops, t = [], 0
        while t < 2000:
            t += int(rng.integers(0, 40))
            d = int(rng.integers(1, 30))
            name = str(rng.choice(sorted(OPS)))
            ops.append((f"%{name} = f32[4]{{0}} op()", t, t + d))
            t += d
        chips_ops.append(ops)
    _write_trace(str(tmp_path), [("python3", [("bench_window", lo, hi)]
                                  + loop), ("python3", worker)], chips_ops)
    red = scopes.reduce(str(tmp_path), 2, HLO)

    assert red["window_s"] == pytest.approx((hi - lo) * 1e-9)
    tiles = _mask([(a, b) for n, a, b in loop if n in scopes.LOOP], lo, hi)
    assert red["loop_cover"] == pytest.approx(tiles.mean())
    for span in scopes.LOOP_SPANS:
        want = _mask([(a, b) for n, a, b in loop if n == span], lo, hi)
        assert red["loop_s"][span] == pytest.approx(want.sum() * 1e-9)
    waits = _mask([(a, b) for n, a, b in loop if n in scopes.WAITS], lo, hi)
    per_scope, idle, attributed, total = {}, 0, 0, 0
    for ops in chips_ops:
        busy = _mask([(a, b) for _, a, b in ops], lo, hi)
        idle += (~busy & waits).sum()
        for n, a, b in ops:
            d = _mask([(a, b)], lo, hi).sum()
            s = OPS[scopes.instruction(n)]
            total += d
            if s:
                attributed += d
                per_scope[s] = per_scope.get(s, 0) + d
    assert red["idle_on_batch_s"] == pytest.approx(idle / 2 * 1e-9)
    assert red["unattributed"] == pytest.approx(1 - attributed / total)
    assert {n for n, _ in red["unattributed_ops"]} \
        == {n for n, s in OPS.items() if s is None}
    assert sum(v for _, v in red["unattributed_ops"]) * 2 \
        == pytest.approx((total - attributed) * 1e-9)
    assert set(red["scope_s"]) | {"aep_consume"} \
        == set(per_scope) | {"aep_consume"}
    for s, v in per_scope.items():
        assert red["scope_s"][s] == pytest.approx(v / 2 * 1e-9)
    assert red["scope_s"]["aep_consume"] == red["scope_s"]["hec_store"]


RECORDED = sorted(glob.glob(os.path.join(DATA, "*_scopes")))


@pytest.mark.parametrize("trace_dir", RECORDED)
def test_reduce_a_recorded_chip_trace(trace_dir):
    """A short traced window of the test-size trainer on one v5e chip with
    its compiled step's HLO text (recorded with
    ``bench/tests/record_scopes.py``): the loop's spans tile the window,
    every scope of the step is found, the ops left unattributed carry no
    scope, and the idle time under the loop's waits is device idle time."""
    with gzip.open(os.path.join(trace_dir, "step.hlo.txt.gz"), "rt") as f:
        hlo = f.read()
    red = scopes.reduce(trace_dir, 1, hlo)
    assert red["loop_cover"] >= 0.9
    assert red["loop_calls"]["epoch_fill"] >= 1
    assert red["loop_calls"]["step"] == red["loop_calls"]["step_sync"] > 0
    for s in ("feature_gather", "hec_lookup", "hec_store", "aep_consume",
              "aep_pack", "layer0_aggregate", "layer1_aggregate",
              "layer0_update", "loss", "optimizer"):
        assert red["scope_s"][s] > 0, s
    assert 0 < red["unattributed"] < 1
    lines = dict(re.findall(r"^\s*(?:ROOT\s+)?%([\w.\-]+) = (.*)$", hlo,
                            re.M))
    for name, _ in red["unattributed_ops"]:
        for path in re.findall(r'op_name="([^"]*)"', lines[name]):
            assert not any(scopes.is_scope(t)
                           for t in re.findall(r"\w+", path)), name
    whole = trace.reduce(trace_dir, 1)
    assert max(red["scope_s"].values()) <= whole["busy_s"]
    idle = whole["window_s"] - whole["busy_s"]
    assert 0 < red["idle_on_batch_s"] <= idle + 1e-9
