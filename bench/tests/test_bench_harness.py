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


TOY_MODEL = '''"""A toy: GraphSAGE with a sum aggregator, the self term weighted by
(1 + eps), and tanh between layers."""
import jax
import jax.numpy as jnp

OPTIONS = ("eps",)


def _dims(m):
    return [m.feat_dim] + [m.hidden] * (m.num_layers - 1) + [m.num_classes]


def init_params(key, m):
    dims = _dims(m)
    layers = []
    for l in range(m.num_layers):
        k1, k2, key = jax.random.split(key, 3)
        shape = (dims[l], dims[l + 1])
        layers.append({"wn": jax.random.normal(k1, shape) / dims[l],
                       "ws": jax.random.normal(k2, shape) / dims[l],
                       "b": jnp.zeros((dims[l + 1],))})
    return {"layers": layers}


def block(p, h, valid, nbr, self_rows, m):
    eps = dict(m.options)["eps"]
    idx = jnp.maximum(nbr, 0)
    mask = ((nbr >= 0) & valid[idx]).astype(h.dtype)[..., None]
    return ((h[idx] * mask).sum(1) @ p["wn"]
            + (1 + eps) * (h[self_rows] @ p["ws"]) + p["b"])


def between(h, m):
    return jnp.tanh(h)


def cache_dims(m):
    return _dims(m)[:-1]


def forward_flops(m, caps):
    dims = _dims(m)
    L = m.num_layers
    return {"projection": sum(2 * caps[k + 1] * dims[k] * dims[k + 1]
                              for k in range(L)),
            "aggregation": sum(caps[k + 1] * m.fanouts[k] * dims[k]
                               for k in range(L))}
'''


def _load(path, name, monkeypatch):
    import importlib.util
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    monkeypatch.setitem(sys.modules, name, mod)
    spec.loader.exec_module(mod)
    return mod


def test_a_new_model_needs_only_new_files(tmp_path, monkeypatch):
    """A model added as one file, ``bench/models/<model>.py``, is found by
    the name in its configuration: the reference's steps follow the
    toy's formula, written out here, and the FLOP count is the toy's own.
    No existing file changes."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    root = tmp_path / "checkout"
    shutil.copytree(BENCH, root / "bench",
                    ignore=shutil.ignore_patterns(".cache", "__pycache__"))
    (root / "bench/models/sage_sum.py").write_text(TOY_MODEL)
    ref = _load(root / "bench/core/reference.py", "copy_reference",
                monkeypatch)
    flops = _load(root / "bench/core/flops.py", "copy_flops", monkeypatch)
    c = dict(model="sage_sum", feat_dim=6, hidden_size=5, num_classes=3,
             num_hidden_layers=1, num_heads=1, fanouts=[2, 3], batch_size=4,
             dropout=0.0, lr=0.05, eps=0.5)
    m = ref.Model.from_config(c)
    assert m.options == (("eps", 0.5),) and m.cache_dims() == [6, 5]
    assert not os.path.exists(os.path.join(BENCH, "models", "sage_sum.py"))

    assert flops.capacities(4, [2, 3]) == [48, 16, 4]
    proj = 2 * (16 * 6 * 5 + 4 * 5 * 3)
    agg = 16 * 2 * 6 + 4 * 3 * 5
    assert flops.step_flops(m) == {"projection": 6.0 * proj,
                                   "aggregation": 6.0 * agg,
                                   "total": 6.0 * (proj + agg)}

    rng = np.random.default_rng(3)
    V, n = 40, (30, 12, 4)
    features = rng.standard_normal((V, 6)).astype(np.float32)
    steps = []
    for t in range(3):
        order = rng.permutation(V)
        nbr = [rng.integers(0, n[k], (n[k + 1], f)).astype(np.int32)
               for k, f in enumerate((2, 3))]
        nbr[0][0] = -1                                    # no neighbour
        steps.append([ref.RankBatch(
            vids=[order[:k] for k in n], solid=[np.ones(k, bool) for k in n],
            nbr_idx=nbr, labels=rng.integers(0, 3, 4).astype(np.int32),
            seed_mask=np.array([True, True, True, False]), seed=t)])
    with jax.default_matmul_precision("highest"):
        res = ref.run_reference(m, features, steps, [None], 7, nc=2,
                                delay=1, life_span=2)

    def mean_nll(params, b):
        h = jnp.asarray(features[b.vids[0]])
        for k, p in enumerate(params["layers"]):
            nbr = b.nbr_idx[k]
            summed = jnp.where((nbr >= 0)[..., None], h[nbr], 0).sum(1)
            h = (summed @ p["wn"] + 1.5 * (h[:len(nbr)] @ p["ws"])
                 + p["b"])
            if k == 0:
                h = jnp.tanh(h)
        logp = jax.nn.log_softmax(h, -1)
        nll = -logp[jnp.arange(4), b.labels]
        return (nll * b.seed_mask).sum() / b.seed_mask.sum()

    params = res.params0
    mu = jax.tree_util.tree_map(jnp.zeros_like, params)
    nu = mu
    for t, (b,) in enumerate(steps, start=1):
        with jax.default_matmul_precision("highest"):
            loss, g = jax.value_and_grad(mean_nll)(params, b)
        norm = jnp.sqrt(sum((x * x).sum()
                            for x in jax.tree_util.tree_leaves(g)))
        g = jax.tree_util.tree_map(lambda x: x * min(1.0, 1 / (norm + 1e-9)),
                                   g)
        assert res.losses[t - 1] == pytest.approx(float(loss), rel=1e-5)
        for a, r in zip(jax.tree_util.tree_leaves(g),
                        jax.tree_util.tree_leaves(res.grads[t - 1])):
            np.testing.assert_allclose(r, a, rtol=1e-4, atol=1e-6)
        mu = jax.tree_util.tree_map(lambda a, x: 0.9 * a + 0.1 * x, mu, g)
        nu = jax.tree_util.tree_map(lambda a, x: 0.999 * a + 0.001 * x * x,
                                    nu, g)
        params = jax.tree_util.tree_map(
            lambda p, a, v: p - 0.05 * (a / (1 - 0.9 ** t))
            / (jnp.sqrt(v / (1 - 0.999 ** t)) + 1e-8), params, mu, nu)
    for a, r in zip(jax.tree_util.tree_leaves(params),
                    jax.tree_util.tree_leaves(res.params)):
        np.testing.assert_allclose(r, a, rtol=1e-4, atol=1e-6)


def test_calibrate_prints_each_reading_as_it_exists(monkeypatch, capsys):
    """A stand-in that never returns, as in a call cut by its time limit,
    leaves the seed's program line and each earlier stand-in's line
    printed."""
    import calibrate
    from core import check, session
    from core import device as dev

    class Cut(Exception):
        pass

    class Readings:
        def __init__(self, *args):
            self.res = types.SimpleNamespace(losses=[2.0])

        def program(self):
            return {"loss_gap": 1e-4}

        def control(self):
            return {"grad_err": 0.05}

        def half_batch(self):
            raise Cut

    run = types.SimpleNamespace(setup_s=1.0, memory_peak_bytes=7)
    monkeypatch.setattr(dev, "require_chips", lambda chips: {})
    monkeypatch.setattr(dev, "set_compile_cache", lambda path: None)
    monkeypatch.setattr(dev, "CompileClock", lambda: None)
    for name in ("load_graph", "load_partition", "build_config"):
        monkeypatch.setattr(session, name, lambda *a: None)
    monkeypatch.setattr(session, "run_cell", lambda *a: (
        run, types.SimpleNamespace(losses=[2.0])))
    monkeypatch.setattr(check, "Check", Readings)
    with pytest.raises(Cut):
        calibrate.main(["--workload", CELLS[0], "--seeds", "5,6",
                        "--stand-ins", "1"])
    lines = [json.loads(x) for x in capsys.readouterr().out.splitlines()]
    assert [x["seed"] for x in lines] == [5, 5]
    assert lines[0]["program"] == {"loss_gap": 1e-4}
    assert lines[1]["stand_in"] == "control"
    assert lines[1]["numbers"] == {"grad_err": 0.05}


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
