"""The benchmark harness on the CPU: BENCHMARK.json against its schema,
discovery of cells, configurations and metrics by name (a new cell added
by files alone), the metric readers, the configuration as run, and the
refusal to run without a TPU.  Importing this file touches no JAX."""
from __future__ import annotations

import json
import os
import re
import shutil
import subprocess
import sys
import types

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, BENCH)
sys.path.insert(1, os.path.join(ROOT, "src"))

import run as harness  # noqa: E402

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


def _bench():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


CELLS = [w["name"] for w in _bench()["workloads"]]
METRICS = sorted(f[:-3] for f in os.listdir(os.path.join(BENCH, "metrics"))
                 if f.endswith(".py"))


def test_benchmark_json_keeps_to_its_schema():
    b = _bench()
    assert set(b) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    assert b["paths"] == ["bench"] and b["command"][1] == "bench/run.py"
    assert 1 <= b["run_seconds"] <= 51
    cells = b["workloads"]
    assert 1 <= len(cells) <= 24
    assert sum(w["chips"] == 4 for w in cells) <= max(1, len(cells) // 2)
    names = [x["name"] for x in b["configs"] + cells + b["end_to_end"]
             + b["per_layer"]]
    assert all(NAME.match(n) for n in names)
    for group in (b["configs"], cells, b["end_to_end"] + b["per_layer"]):
        assert len({x["name"] for x in group}) == len(group)
    used = {w["config"] for w in cells}
    for c in b["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert c["name"] in used and c["file"].startswith("bench/")
        with open(os.path.join(ROOT, c["file"])) as f:
            assert json.load(f)["reduced"] == c["reduced"]
    pairs = [(w["config"], w["traffic"]) for w in cells]
    assert len(set(pairs)) == len(pairs)
    for w in cells:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["chips"] in (1, 4) and len(w["why"]) <= 200
    assert any(m["name"] == "setup_s" for m in b["end_to_end"])
    for m in b["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    for m in b["per_layer"]:
        assert m["source"] in SOURCES and "bound" not in m
        assert m["moves"] in {e["name"] for e in b["end_to_end"]}
        assert set(m.get("workloads", [])) <= set(CELLS)
    for m in b["end_to_end"] + b["per_layer"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
        assert os.path.exists(os.path.join(BENCH, "metrics",
                                           m["name"] + ".py"))
    # a full check fits the budget with 24 cells at this run length
    assert (2 + 14 * 24) * (b["run_seconds"] + 60) + 24 * 180 + 1200 \
        <= 43200


@pytest.mark.parametrize("cell", CELLS)
def test_every_cell_resolves_by_name(cell):
    c = harness.resolve_cell(cell)
    assert c["chips"] in (1, 4) and c["limits"]
    names = [m["name"] for m in c["end_to_end"]]
    assert "setup_s" in names and len(names) >= 2 and c["per_layer"]
    assert c["traffic"]["feat_dim"] == c["config"]["config"]["feat_dim"]
    assert c["traffic"]["num_classes"] == \
        c["config"]["config"]["num_classes"]


def test_a_new_cell_needs_only_new_files(tmp_path):
    """A configuration, traffic mix, cell and per-layer metric added as
    files (and entries) are found by name; no existing file changes."""
    root = tmp_path / "checkout"
    shutil.copytree(BENCH, root / "bench",
                    ignore=shutil.ignore_patterns(".cache", "__pycache__"))
    b = _bench()
    cfg = json.load(open(os.path.join(BENCH, "configs",
                                      "graphsage_papers100m.json")))
    cfg["name"] = "sage_small_hec"
    cfg["overrides"] = {"hec": {"cache_size": 65536}}
    cfg["config"]["hec"]["cache_size"] = 65536
    cfg["reduced"] = ["hec"]
    (root / "bench/configs/sage_small_hec.json").write_text(json.dumps(cfg))
    traffic = json.load(open(os.path.join(
        BENCH, "traffic", "papers100m_share_1part.json")))
    traffic["num_vertices"] = 50_000
    (root / "bench/traffic/tiny_share.json").write_text(json.dumps(traffic))
    (root / "bench/workloads/sage-tiny.json").write_text(
        json.dumps({"limits": {"loss_gap": 1.0}}))
    (root / "bench/metrics/steps_per_epoch.py").write_text(
        "def read(record):\n"
        "    run = record['run']\n"
        "    return run.steps / run.epochs if run.epochs else None\n")
    b["configs"].append({"name": "sage_small_hec", "source": "x",
                         "file": "bench/configs/sage_small_hec.json",
                         "reduced": ["hec"], "why": "x"})
    b["workloads"].append({"name": "sage-tiny", "config": "sage_small_hec",
                           "traffic": "tiny_share", "chips": 1, "why": "x"})
    b["per_layer"].append({"name": "steps_per_epoch", "unit": "steps",
                           "better": "lower", "source": "host_clock",
                           "layer": "trainer step", "moves": "epoch_s",
                           "workloads": ["sage-tiny"]})
    (root / "BENCHMARK.json").write_text(json.dumps(b))

    cell = harness.resolve_cell("sage-tiny", root=str(root))
    assert cell["traffic"]["num_vertices"] == 50_000
    assert [m["name"] for m in cell["per_layer"]][-1] == "steps_per_epoch"
    assert "steps_per_epoch" not in [
        m["name"] for m in harness.resolve_cell("sage-1chip",
                                                root=str(root))["per_layer"]]
    from core import session
    assert session.build_config(cell["config"]).hec.cache_size == 65536
    record = {"run": types.SimpleNamespace(steps=38, epochs=2)}
    got = harness.read_metrics(cell["per_layer"][-1:], record, cell["bench"])
    assert got == {"steps_per_epoch": {"value": 19.0, "unit": "steps"}}


def _record(trace=True):
    from core.session import Run
    run = Run(setup_s=20.5, compile_s=0.3, window_s=31.0, epochs=8,
              steps=152, phase_s={"sample": 20.0, "host_prep": 3.0,
                                  "stage": 1.4, "step": 27.0})
    tr = {"window_s": 31.0, "busy_s": 6.2, "exposed_all_to_all_s": 0.0152,
          "collective_ops": 152} if trace else None
    return {"run": run, "chips": 1, "trace": tr,
            "peaks": {"bf16_flops": 197e12}, "flops_per_step": 82.3e9}


EXPECTED = {"epoch_s": 31.0 / 8, "setup_s": 20.5, "compile_s": 0.3,
            "host_prep_ms": 1e3 * 24.4 / 152,
            "step_mfu": 100 * 82.3e9 * 152 / 31.0 / 197e12,
            "device_idle_share": 80.0, "aep_exposed_ms": 0.1}


@pytest.mark.parametrize("name", METRICS)
def test_metric_reader(name):
    mod = harness.load_metric(name, BENCH)
    assert mod.read(_record()) == pytest.approx(EXPECTED[name])


@pytest.mark.parametrize("name", ["device_idle_share", "aep_exposed_ms"])
def test_trace_metrics_read_nothing_without_a_trace(name):
    assert harness.load_metric(name, BENCH).read(_record(False)) is None


@pytest.mark.parametrize("config", ["graphsage_papers100m", "gat_papers100m"])
def test_configuration_file_is_what_runs(config):
    from core import session
    with open(os.path.join(BENCH, "configs", config + ".json")) as f:
        c = json.load(f)
    cfg = session.build_config(c)
    assert cfg.hec.cache_size == c["config"]["hec"]["cache_size"]
    c["config"]["hidden_size"] = 128
    with pytest.raises(ValueError):
        session.build_config(c)


def test_unknown_device_kind_is_an_error():
    from core.device import peaks_for
    path = os.path.join(BENCH, "peaks.json")
    assert peaks_for("TPU v5 lite", path)["bf16_flops"] == 197e12
    with pytest.raises(KeyError):
        peaks_for("TPU v9 imaginary", path)


def test_run_refuses_the_cpu():
    """No TPU: a non-zero exit and no result line."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, os.path.join(BENCH, "run.py"),
                        "--workload", CELLS[0], "--seed", "2147483711",
                        "--seconds", "1", "--trace", "0"],
                       capture_output=True, text=True, env=env, timeout=120)
    assert p.returncode != 0
    assert "TPU" in p.stderr
    assert not any(line.startswith("{") for line in p.stdout.splitlines())


def test_judge_needs_every_number_under_its_limit():
    from core.check import judge
    assert judge({"a": 1e-3, "b": 0.0}, {"a": 2e-3, "b": 0})[0]
    assert not judge({"a": 3e-3, "b": 0.0}, {"a": 2e-3, "b": 0})[0]
    assert not judge({"a": 1e-3}, {"a": 2e-3, "b": 0})[0]      # b missing
    assert not judge({"a": 1e-3, "b": 0.0}, {"a": None, "b": 0})[0]
    assert not judge({"a": float("nan")}, {"a": 1.0})[0]


def test_worst_leaf_gap_scales_by_the_larger_of_leaf_and_median():
    from core.check import worst_leaf_gap
    ref = {"w1": 1.0, "w2": 2.0, "b": 1e-6}
    prog = {"w1": 1.01, "w2": 2.0, "b": 2e-6}
    gap, leaf = worst_leaf_gap(prog, ref)
    assert leaf == "w1" and gap == pytest.approx(0.01)
    gap, leaf = worst_leaf_gap(prog, ref, keep=["w2", "b"])
    assert leaf == "b" and gap == pytest.approx(1e-6)
