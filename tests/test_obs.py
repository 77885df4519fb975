"""Observability subsystem tests: registry semantics, span nesting +
Chrome trace schema, hit-rate derivation, the epoch breakdown, and the
bit-identity contract — training steps and serve rounds compute the same
bits with observability off, on, or tracing (spans only *read* timings
and host counters; they never feed back into the numerics)."""
import glob
import json
import os
import re
import subprocess
import sys
import threading
import time

import jax
import numpy as np
import pytest

from repro import obs
from repro.configs.gnn import small_gnn_config
from repro.graph import partition_graph, synthetic_graph
from repro.serve.gnn import (GNNServeConfig, GNNServeScheduler,
                             ServeCacheConfig)
from repro.serve.gnn.scheduler import LatencyStats
from repro.train.gnn_trainer import (DistTrainer, _epoch_mean,
                                     build_dist_data, init_model_params)


@pytest.fixture(autouse=True)
def fresh_obs():
    """Every test starts from (and leaves behind) the default runtime."""
    obs.configure()
    yield
    obs.configure()


# -- registry ----------------------------------------------------------------
def test_counter_gauge_histogram_semantics():
    reg = obs.MetricsRegistry()
    reg.counter("c").inc()
    reg.counter("c").inc(2.5)
    reg.counter("c", layer=1).inc(7)        # distinct labeled instrument
    reg.gauge("g").set(3)
    reg.gauge("g").set(4)
    assert reg.value("c") == 3.5
    assert reg.value("c", layer=1) == 7.0
    assert reg.value("g") == 4.0
    assert reg.value("missing", default=-1.0) == -1.0
    rng = np.random.default_rng(0)
    xs = rng.normal(size=500)
    h = reg.histogram("h")
    for x in xs:
        h.observe(x)
    # percentiles are EXACT over the window (np.percentile, no buckets)
    assert h.percentile(50) == float(np.percentile(xs, 50))
    assert h.percentile(99) == float(np.percentile(xs, 99))
    s = h.summary()
    assert s["count"] == 500 and s["max"] == xs.max()


def test_histogram_window_bounds_memory():
    h = obs.Histogram(window=16)
    for i in range(100):
        h.observe(float(i))
    assert h.count == 100 and len(h.samples) == 16
    assert min(h.samples) == 84.0           # only the newest 16 retained


def test_disabled_registry_hands_out_nulls():
    reg = obs.MetricsRegistry(enabled=False)
    reg.counter("c").inc(5)
    reg.gauge("g").set(5)
    reg.histogram("h").observe(5)
    reg.log_event("e", x=1)
    assert reg.value("c") == 0.0
    assert reg.snapshot() == {}
    assert reg.events == []


def test_latency_stats_is_the_obs_histogram():
    """Satellite (a): the schedulers' p50/p99 code is the obs histogram —
    identical class behavior and identical metrics values."""
    assert issubclass(LatencyStats, obs.Histogram)
    rng = np.random.default_rng(1)
    xs = rng.exponential(0.01, size=300)
    st = LatencyStats()
    for x in xs:
        st.observe(float(x))
    m = st.metrics()
    a = xs * 1e3
    assert m["latency_count"] == 300
    assert m["latency_p50_ms"] == float(np.percentile(a, 50))
    assert m["latency_p99_ms"] == float(np.percentile(a, 99))
    assert m["latency_mean_ms"] == float(a.mean())
    st.reset()
    assert st.metrics() == {"latency_count": 0, "latency_p50_ms": 0.0,
                            "latency_p99_ms": 0.0, "latency_mean_ms": 0.0}


def test_hit_rate_metrics_sum_ratio_and_hot():
    """Satellite (c): rates are summed-numerator over summed-denominator
    (not a mean of per-step ratios), and the hot tier gets its own rate."""
    reg = obs.MetricsRegistry()
    for hits, halos, hot in [(1, 10, 1), (9, 10, 3)]:
        reg.counter("hec_hits_l0").inc(hits)
        reg.counter("hec_halos_l0").inc(halos)
        reg.counter("hot_hits_l0").inc(hot)
    reg.counter("hec_hits_l1").inc(4)
    reg.counter("hec_halos_l1").inc(0)      # no halos -> no rate at all
    out = obs.hit_rate_metrics(reg)
    assert out["hec_hit_rate_l0"] == 0.5    # 10/20, NOT mean(0.1, 0.9)
    assert out["hot_hit_rate_l0"] == 0.2    # 4/20
    assert "hec_hit_rate_l1" not in out     # zero-denominator window
    assert "hot_hit_rate_l1" not in out     # tier never recorded there


def test_zero_denominator_rates_absent_not_nan():
    """Satellite: cold-start windows (zero denominator) must yield absent
    rates — never NaN and never ZeroDivisionError."""
    reg = obs.MetricsRegistry()
    # completely cold registry: no counters at all
    assert obs.hit_rate_metrics(reg) == {}
    assert reg.rate_or_none("hec_hits_l0", "hec_halos_l0") is None
    # denominator recorded but zero
    reg.counter("hec_halos_l0").inc(0)
    reg.counter("hec_hits_l0").inc(0)
    reg.counter("hot_hits_l0").inc(0)
    out = obs.hit_rate_metrics(reg)
    assert out == {}
    assert reg.rate_or_none("hec_hits_l0", "hec_halos_l0") is None
    # the plain rate() keeps its 0.0-on-zero contract for epoch means
    assert reg.rate("hec_hits_l0", "hec_halos_l0") == 0.0
    # detector-side guard: skew of an all-zero window is None, not NaN
    assert obs.skew_ratio(np.zeros(4)) is None
    assert obs.skew_ratio(np.array([])) is None
    # once halos flow, the rate appears
    reg.counter("hec_halos_l0").inc(10)
    reg.counter("hec_hits_l0").inc(5)
    out = obs.hit_rate_metrics(reg)
    assert out["hec_hit_rate_l0"] == 0.5
    assert out["hot_hit_rate_l0"] == 0.0


def test_prometheus_text_exposition():
    """Satellite: ``to_prom_text`` renders the registry in the Prometheus
    text format — TYPE lines, sanitized names, escaped label values,
    histogram quantile/sum/count series."""
    reg = obs.MetricsRegistry()
    reg.counter("halo_rows", rank=0).inc(5)
    reg.counter("halo_rows", rank=1).inc(7)
    reg.counter("bad-name.metric").inc(1)    # needs sanitizing
    reg.gauge("cluster_skew", metric="halo_rows").set(1.4)
    h = reg.histogram("serve_latency_s", subsystem="serve")
    for v in [1.0, 2.0, 3.0, 4.0]:
        h.observe(v)
    text = reg.to_prom_text()
    lines = text.splitlines()
    assert text.endswith("\n")
    assert "# TYPE halo_rows counter" in lines
    assert 'halo_rows{rank="0"} 5.0' in lines
    assert 'halo_rows{rank="1"} 7.0' in lines
    assert "# TYPE bad_name_metric counter" in lines
    assert "# TYPE cluster_skew gauge" in lines
    assert 'cluster_skew{metric="halo_rows"} 1.4' in lines
    assert "# TYPE serve_latency_s summary" in lines
    assert ('serve_latency_s{quantile="0.5",subsystem="serve"} 2.5'
            in lines)
    assert 'serve_latency_s_count{subsystem="serve"} 4' in lines
    assert 'serve_latency_s_sum{subsystem="serve"} 10.0' in lines
    # each TYPE is declared exactly once per metric family
    type_lines = [l for l in lines if l.startswith("# TYPE halo_rows ")]
    assert len(type_lines) == 1
    # every sample line parses as `name{labels} value` with a float value
    for l in lines:
        if not l or l.startswith("#"):
            continue
        float(l.rsplit(" ", 1)[1])
    # label values with quotes/backslashes/newlines are escaped
    reg2 = obs.MetricsRegistry()
    reg2.counter("c", path='a"b\\c\nd').inc(1)
    out = reg2.to_prom_text()
    assert 'path="a\\"b\\\\c\\nd"' in out
    # disabled registry exposes nothing
    assert obs.MetricsRegistry(enabled=False).to_prom_text() == ""


def test_epoch_mean_derives_hot_hit_rate():
    steps = [{"loss": 1.0, "acc": 0.5, "examples": 10.0,
              "hec_hits_l0": 1.0, "hec_halos_l0": 10.0, "hot_hits_l0": 2.0},
             {"loss": 3.0, "acc": 1.0, "examples": 30.0,
              "hec_hits_l0": 9.0, "hec_halos_l0": 10.0, "hot_hits_l0": 0.0}]
    out = _epoch_mean(steps)
    assert out["hec_hit_rate_l0"] == 0.5
    assert out["hot_hit_rate_l0"] == 0.1
    # example-weighted loss/acc unchanged by the registry-backed path
    assert out["loss"] == (1.0 * 10 + 3.0 * 30) / 40


def test_registry_jsonl_sink(tmp_path):
    reg = obs.MetricsRegistry()
    reg.counter("c", layer=2).inc(3)
    reg.histogram("h").observe(1.0)
    reg.log_event("row", suite="s", value=7)
    path = reg.write_jsonl(str(tmp_path / "metrics.jsonl"))
    lines = [json.loads(l) for l in open(path)]
    assert {"metric": "c{layer=2}", "kind": "counter", "value": 3.0} in lines
    assert any(l.get("event") == "row" and l["value"] == 7 for l in lines)


# -- tracing -----------------------------------------------------------------
def test_span_nesting_and_chrome_schema():
    obs.configure(obs.ObsConfig(trace=True))
    with obs.span("outer", epoch=0):
        with obs.span("inner"):
            pass
    tracer = obs.get().tracer
    trace = tracer.export()
    assert obs.validate_chrome_trace(trace) == 2
    by_name = {e["name"]: e for e in trace["traceEvents"] if e["ph"] == "X"}
    assert by_name["inner"]["args"] == {"depth": 1, "parent": "outer"}
    assert by_name["outer"]["args"] == {"epoch": 0, "depth": 0}
    # chrome containment: inner strictly inside outer on the same tid
    o, i = by_name["outer"], by_name["inner"]
    assert o["tid"] == i["tid"]
    assert o["ts"] <= i["ts"]
    assert i["ts"] + i["dur"] <= o["ts"] + o["dur"] + 1e-6
    # registry side of the span: phase counters accumulated
    assert obs.get().registry.value("phase_calls", phase="inner") == 1.0


def test_spans_from_worker_threads_get_own_tids():
    obs.configure(obs.ObsConfig(trace=True))

    def work():
        with obs.span("worker_phase"):
            pass

    with obs.span("main_phase"):
        t = threading.Thread(target=work, name="prefetch-0")
        t.start()
        t.join()
    trace = obs.get().tracer.export()
    obs.validate_chrome_trace(trace)
    xs = {e["name"]: e["tid"] for e in trace["traceEvents"]
          if e["ph"] == "X"}
    assert xs["main_phase"] != xs["worker_phase"]
    meta = {e["args"]["name"] for e in trace["traceEvents"]
            if e["ph"] == "M"}
    assert "prefetch-0" in meta


def test_disabled_obs_is_a_shared_noop():
    obs.configure(obs.ObsConfig(enabled=False))
    s1 = obs.span("a")
    s2 = obs.span("b", x=1)
    assert s1 is s2                          # shared singleton, no allocs
    with s1:
        obs.count("c", 5)
        obs.observe("h", 1.0)
    assert obs.get().registry.snapshot() == {}
    assert obs.get().tracer.events == []


def _profiled_spans(trace_dir):
    """{name: [(line id, start_ns, end_ns)]} of the host planes' events."""
    from jax.profiler import ProfileData
    path, = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    out = {}
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for i, line in enumerate(plane.lines):
            for ev in line.events:
                out.setdefault(ev.name, []).append(
                    (i, ev.start_ns, ev.start_ns + ev.duration_ns))
    return out


def test_span_lands_on_the_profiler_host_plane(tmp_path):
    """Each span is a profiler annotation of the same name, on the thread
    that ran it, lasting no longer than the registry's perf_counter
    interval; a fully disabled runtime leaves no annotation."""
    jax.profiler.start_trace(str(tmp_path / "on"))
    with obs.span("outer_phase"):
        time.sleep(0.02)
        with obs.span("inner_phase"):
            time.sleep(0.01)
    jax.profiler.stop_trace()
    timed = obs.get().phase_seconds("outer_phase")
    obs.configure(obs.ObsConfig(enabled=False))
    jax.profiler.start_trace(str(tmp_path / "off"))
    with obs.span("silent_phase"):
        time.sleep(0.01)
    jax.profiler.stop_trace()

    got = _profiled_spans(str(tmp_path / "on"))
    (lo, o_s, o_e), = got["outer_phase"]
    (li, i_s, i_e), = got["inner_phase"]
    assert lo == li and o_s <= i_s and i_e <= o_e
    assert 0.03 <= (o_e - o_s) * 1e-9 <= timed
    assert "silent_phase" not in _profiled_spans(str(tmp_path / "off"))


# -- breakdown ---------------------------------------------------------------
def test_step_model_roofline_and_overlap():
    m = obs.StepModel.from_roofline(
        flops=2e12, bytes_accessed=1e9, push_bytes=5e8,
        peak_flops=1e12, hbm_bw=1e9, ici_bw=1e9)
    assert m.work_s == 2.0                   # compute-bound side of the max
    assert m.push_s == 0.5
    # bwd = 2/3 * 2.0 covers the whole 0.5s push -> fully hidden
    assert m.overlap_efficiency() == 1.0
    assert m.exposed_push_s == 0.0
    # exposed case: push exceeds the backward pass
    m2 = obs.StepModel(work_s=0.3, push_s=0.4)
    assert m2.overlap_efficiency() == pytest.approx(0.2 / 0.4)
    fwd, push, bwd = m2.split_step(1.0)
    assert fwd + push + bwd == pytest.approx(1.0)    # exact attribution
    assert obs.StepModel().overlap_efficiency() == 1.0


def test_breakdown_shares_sum_to_one():
    bd = obs.EpochBreakdown(obs.StepModel(work_s=1.0, push_s=0.8))
    bd.add_epoch(sample=0.2, host_prep=0.1, stage=0.05, step=1.0, wall=1.2)
    bd.add_epoch(sample=0.0, host_prep=0.0, stage=0.0, step=2.0)
    for row in bd.rows():
        total = sum(row[f"share_{p}"] for p in obs.REPORT_PHASES)
        assert total == pytest.approx(1.0)
        assert 0.0 <= row["overlap_efficiency"] <= 1.0
    assert bd.rows()[0]["pipeline_overlap"] == pytest.approx(
        (1.35 - 1.2) / 1.35)
    assert "epoch" in bd.table()


# -- bit-identity ------------------------------------------------------------
@pytest.fixture(scope="module")
def tiny_train():
    g = synthetic_graph(num_vertices=400, avg_degree=5, num_classes=4,
                        feat_dim=8, seed=0)
    ps = partition_graph(g, 1, seed=0)
    cfg = small_gnn_config("graphsage", batch_size=16, feat_dim=8,
                           num_classes=4, fanouts=(3, 3), hidden_size=16)
    mesh = jax.make_mesh((1,), ("data",))
    dd = build_dist_data(ps, cfg)
    tr = DistTrainer(cfg=cfg, mesh=mesh, num_ranks=1, mode="aep")
    return ps, dd, tr, tr.make_step(dd)


def test_train_step_bit_identical_under_tracing(tiny_train):
    """Tracing on / obs off / defaults: same training bits, and the traced
    run contains the trainer's phase spans."""
    ps, dd, tr, step_fn = tiny_train

    def run():
        state = tr.init_state(jax.random.key(0))
        _, hist = tr.train_epochs(ps, dd, state, 2, step_fn=step_fn)
        return hist

    obs.configure(obs.ObsConfig(enabled=False))
    h_off = run()
    obs.configure(obs.ObsConfig(trace=True))
    h_on = run()
    obs.configure()
    h_def = run()
    for a, b in zip(h_off, h_on):
        assert a["loss"] == b["loss"] and a["acc"] == b["acc"]
        assert a["grad_norm"] == b["grad_norm"]
    for a, b in zip(h_off, h_def):
        assert a["loss"] == b["loss"]
    # obs-off histories carry no timing keys; enabled ones do
    assert "t_step" not in h_off[0]
    assert h_def[0]["t_step"] > 0.0 and h_def[0]["t_wall"] > 0.0
    obs.configure(obs.ObsConfig(trace=True))
    _ = run()
    trace = obs.get().tracer.export()
    n = obs.validate_chrome_trace(trace)
    names = {e["name"] for e in trace["traceEvents"] if e["ph"] == "X"}
    assert {"sample", "host_prep", "stage", "step"} <= names
    assert n > 0


LOOP_SPANS = ("epoch_fill", "batch_wait", "step", "epoch_end")


def test_loop_spans_tile_train_epochs(tiny_train, monkeypatch):
    """The step loop's own spans cover the epochs' wall time, epoch_fill
    opens once an epoch, and the host reads each step back in one
    transfer (step_sync, nested in step)."""
    ps, dd, tr, step_fn = tiny_train
    state = tr.init_state(jax.random.key(0))
    tr.train_epochs(ps, dd, state, 1, step_fn=step_fn)     # compile
    obs.configure()
    gets = []
    real_get = jax.device_get
    monkeypatch.setattr(jax, "device_get",
                        lambda x: gets.append(1) or real_get(x))
    state = tr.init_state(jax.random.key(0))
    t0 = time.perf_counter()
    state, hist = tr.train_epochs(ps, dd, state, 6, step_fn=step_fn)
    wall = time.perf_counter() - t0
    reg = obs.get().registry
    steps = int(state["step"])
    assert steps >= 12 and len(hist) == 6
    covered = sum(reg.value("phase_seconds", phase=p) for p in LOOP_SPANS)
    assert 0.9 * wall <= covered <= wall
    assert reg.value("phase_calls", phase="epoch_fill") == 6
    assert reg.value("phase_calls", phase="epoch_end") == 6
    assert reg.value("phase_calls", phase="batch_wait") == steps
    assert reg.value("phase_calls", phase="step_sync") == steps
    assert reg.value("phase_seconds", phase="step_sync") \
        <= reg.value("phase_seconds", phase="step")
    assert len(gets) == steps


_SCOPES_SCRIPT = r"""
import os, sys
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
import json, re
import jax
import numpy as np
from repro.configs.gnn import small_gnn_config
from repro.graph import partition_graph, synthetic_graph
from repro.launch.mesh import make_gnn_mesh
from repro.pipeline.staging import MinibatchPipeline
from repro.train.gnn_trainer import DistTrainer, build_dist_data

g = synthetic_graph(num_vertices=400, avg_degree=5, num_classes=4,
                    feat_dim=8, seed=0)
ps = partition_graph(g, 2, seed=0)
cfg = small_gnn_config(sys.argv[1], batch_size=16, feat_dim=8, num_heads=2,
                       num_classes=4, fanouts=(3, 3), hidden_size=16)
mesh = make_gnn_mesh(2)
dd = build_dist_data(ps, cfg, mesh)
tr = DistTrainer(cfg=cfg, mesh=mesh, num_ranks=2, mode="aep")
state = tr.init_state(jax.random.key(0), dd)
mb = next(iter(MinibatchPipeline(ps, cfg, mesh=mesh).epoch_batches(0)))
text = tr.make_step(dd).lower(
    state["params"], state["opt_state"], state["hec"], state["hot"],
    state["inflight"], dd, mb, np.uint32(0)).compile().as_text()
print(json.dumps(sorted(set(re.findall(r'op_name="([^"]+)"', text)))))
"""

SCOPES = ("feature_gather", "hec_lookup", "hec_store", "layer0_aggregate",
          "layer1_aggregate", "layer0_update", "layer1_update", "loss",
          "optimizer", "aep_pack", "aep_exchange", "aep_consume")


@pytest.mark.parametrize("model", ["graphsage", "gat"])
def test_compiled_step_carries_the_named_scopes(model):
    """The AEP step on two ranks, compiled: every named scope is in the
    ops' op_name metadata, and backward ops inherit their layer's."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, "-c", _SCOPES_SCRIPT, model],
                       capture_output=True, text=True, env=env, timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    names = json.loads(p.stdout.strip().splitlines()[-1])
    tokens = {t for n in names for t in re.findall(r"\w+", n)}
    assert set(SCOPES) <= tokens
    assert any("transpose(jvp(layer1_aggregate))" in n for n in names)


def test_serve_round_bit_identical_under_tracing():
    g = synthetic_graph(num_vertices=500, avg_degree=2, num_classes=4,
                        feat_dim=8, seed=1)
    part = partition_graph(g, 1, seed=0).parts[0]
    cfg = small_gnn_config("graphsage", batch_size=8, feat_dim=8,
                           num_classes=4, fanouts=(4, 4), hidden_size=16)
    params = init_model_params(jax.random.key(0), cfg)
    scfg = GNNServeConfig(num_slots=8,
                          cache=ServeCacheConfig(cache_size=4096, ways=4))
    rng = np.random.default_rng(0)
    vids = rng.integers(0, part.num_solid, 24)

    obs.configure(obs.ObsConfig(enabled=False))
    out_off = GNNServeScheduler(cfg, params, part, scfg).serve(vids)
    obs.configure(obs.ObsConfig(trace=True))
    srv = GNNServeScheduler(cfg, params, part, scfg)
    out_on = srv.serve(vids)
    np.testing.assert_array_equal(out_off, out_on)
    trace = obs.get().tracer.export()
    obs.validate_chrome_trace(trace)
    names = {e["name"] for e in trace["traceEvents"] if e["ph"] == "X"}
    assert {"serve_round", "serve_sample"} <= names
    # the frontend mirrors its latency samples into the shared registry
    assert obs.get().registry.histogram(
        "serve_latency_s", subsystem="serve").count == len(vids)
