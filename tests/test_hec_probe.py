"""The trainer's HEC probe runs only where some rank holds halos.

``make_step`` reads ``has_halos`` once from the host: on one partition
the step compiles no key gather, no HEC or tier probe and no
substitution; with halos every layer's rows are probed by their VID_o
keys (``substitute_halos``), as before.
"""
import json
import os
import re
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import obs
from repro.cache import hec as hec_lib
from repro.cache import hot_tier as hot_lib
from repro.configs.gnn import small_gnn_config
from repro.graph import partition_graph, synthetic_graph
from repro.graph.sampling import layer_capacities
from repro.train.gnn_trainer import (DistTrainer, build_dist_data,
                                     has_halos, substitute_halos)


@pytest.fixture(autouse=True)
def fresh_obs():
    obs.configure()
    yield
    obs.configure()


# ---------------------------------------------------------------------------
# (a) the probe against a per-row reference
# ---------------------------------------------------------------------------
S, NH, ROWS, DIM, LS = 60, 40, 64, 8, 2      # solids, halos, layer rows
HOT_SLOTS = [s for s in range(0, 16, 2) if s != 4]   # slot 4 goes stale


def _layer(n_halo, seed):
    """One layer's unique rows (``n_halo`` of them halos, 6 padded), the
    rank's id tables, an HEC holding 45 of its vertices (solids too: a
    solid's hit must never be taken) and a hot tier holding some halos."""
    rng = np.random.default_rng(seed)
    vid_o = rng.permutation(10_000)[:S + NH].astype(np.int32)
    nodes = np.concatenate([rng.permutation(S)[:ROWS - 6 - n_halo],
                            S + rng.permutation(NH)[:n_halo]])
    nodes = np.concatenate([rng.permutation(nodes), np.full(6, -1)])
    hot_vids = np.sort(vid_o[S:S + 16])
    data = {"vid_o": jnp.asarray(np.concatenate([vid_o, [-1, -1]])),
            "hot_vids": jnp.asarray(hot_vids)}
    # the HEC also holds the tier's vertices: the tier's replica wins
    cached = np.concatenate([hot_vids, rng.permutation(
        np.setdiff1d(vid_o, hot_vids))[:29]])
    cached_embs = rng.normal(size=(45, DIM)).astype(np.float32)
    hec = hec_lib.hec_store(hec_lib.hec_init(1024, 4, DIM),
                            jnp.asarray(cached), jnp.asarray(cached_embs))
    hit, _ = hec_lib.hec_lookup(hec, jnp.asarray(cached))
    assert bool(hit.all())                    # nothing evicted
    tier_embs = rng.normal(size=(8, DIM)).astype(np.float32)
    hot = hot_lib.tier_store(hot_lib.tier_init(16, DIM),
                             jnp.arange(0, 16, 2), jnp.asarray(tier_embs))
    hot = hot_lib.HotTierState(values=hot.values,
                               age=hot.age.at[4].set(LS + 1))
    ref = {"vid_o": vid_o,
           "hec": dict(zip(cached.tolist(), cached_embs)),
           "tier": {int(hot_vids[s]): tier_embs[s // 2] for s in HOT_SLOTS}}
    nodes = jnp.asarray(nodes, jnp.int32)
    is_halo = nodes >= S
    h = jnp.asarray(rng.normal(size=(ROWS, DIM)), jnp.float32)
    return data, hec, hot, nodes, is_halo, h, ref


def _reference(nodes, is_halo, h, ref, with_hot):
    """Row by row: a halo row takes its fresh tier replica, else its HEC
    line; every other row keeps its value."""
    h = np.array(h)
    use, use_hot = np.zeros(ROWS, bool), np.zeros(ROWS, bool)
    for i, (n, halo) in enumerate(zip(np.asarray(nodes),
                                      np.asarray(is_halo))):
        if not halo:
            continue
        v = int(ref["vid_o"][n])
        if with_hot and v in ref["tier"]:
            h[i], use_hot[i] = ref["tier"][v], True
        elif v in ref["hec"]:
            h[i], use[i] = ref["hec"][v], True
    return h, use, use_hot


@pytest.mark.parametrize("with_hot", [False, True], ids=["hec", "hec+hot"])
@pytest.mark.parametrize("n_halo", [0, 12, 20])
def test_probe_matches_per_row_reference(n_halo, with_hot):
    data, hec, hot, nodes, is_halo, h, ref = _layer(n_halo, seed=n_halo)
    got = jax.jit(substitute_halos, static_argnums=6)(
        h, nodes, is_halo, data, hec, hot if with_hot else None, LS)
    want = _reference(nodes, is_halo, h, ref, with_hot)
    for a, b in zip(got, want):
        np.testing.assert_array_equal(np.asarray(a), b)
    _, use, use_hot = want
    if n_halo:
        assert use.sum() > 0 and (use_hot.sum() > 0 or not with_hot)
        if with_hot:    # a halo both hold comes from the tier
            assert any(int(ref["vid_o"][n]) in ref["hec"]
                       for n in np.asarray(nodes)[use_hot])
    else:
        # the HEC holds solids of this layer, yet nothing is substituted:
        # a layer without halos gains nothing from a probe
        assert any(int(ref["vid_o"][n]) in ref["hec"]
                   for n in np.asarray(nodes) if n >= 0)
        np.testing.assert_array_equal(np.asarray(got[0]), np.asarray(h))


# ---------------------------------------------------------------------------
# (b) one partition: the step holds no probe and no per-row key gather
# ---------------------------------------------------------------------------
BATCH, FANOUTS = 16, (3, 4)


@pytest.fixture(scope="module")
def graph():
    return synthetic_graph(num_vertices=400, avg_degree=5, num_classes=4,
                           feat_dim=8, seed=0)


@pytest.fixture(scope="module")
def one_part(graph):
    return partition_graph(graph, 1, seed=0)


def _cfg(model):
    return small_gnn_config(model, batch_size=BATCH, feat_dim=8,
                            num_classes=4, fanouts=FANOUTS, hidden_size=16)


@pytest.mark.parametrize("parts,want", [(None, True), (1, False),
                                        (4, True)])
def test_has_halos(graph, parts, want):
    dd = None if parts is None else build_dist_data(
        partition_graph(graph, parts, seed=0), _cfg("graphsage"))
    assert has_halos(dd) is want


def _probe_gauges(num_layers):
    reg = obs.get().registry
    return [reg.value("hec_probe_rows", -1.0, layer=k)
            for k in range(num_layers)]


@pytest.mark.parametrize("model", ["graphsage", "gat"])
def test_one_partition_step_has_no_hec_probe(one_part, model):
    """No op under the ``hec_lookup`` scope and no ``s32`` gather of the
    layer-0 rows' keys from ``vid_o``; the probe gauge reads 0 a layer."""
    cfg = _cfg(model)
    rows0 = layer_capacities(BATCH, FANOUTS)[0]
    P = one_part.parts[0].num_solid
    assert P != rows0
    mesh = jax.make_mesh((1,), ("data",))
    dd = build_dist_data(one_part, cfg, mesh)
    tr = DistTrainer(cfg=cfg, mesh=mesh, num_ranks=1, mode="aep")
    st = tr.init_state(jax.random.key(0), dd)
    mb = next(iter(tr._pipeline(one_part, 0).epoch_batches(0)))
    step = tr.make_step(dd)
    assert _probe_gauges(cfg.num_layers) == [0.0] * cfg.num_layers
    text = step.lower(st["params"], st["opt_state"], st["hec"], st["hot"],
                      st["inflight"], dd, mb, jnp.uint32(0)) \
        .as_text(dialect="hlo", debug_info=True)
    assert "hec_store" in text           # the scopes reach the HLO text
    assert "hec_lookup" not in text
    # vid_o's rank slice is s32[P]; a key per layer-0 row is s32[rows0]
    tables = set(re.findall(rf"(\S+) = s32\[{P}\]\{{0\}}", text))
    gathered = re.findall(rf"= s32\[{rows0}\]\{{0\}} gather\(([^,]+),", text)
    assert tables and not tables & set(gathered)


# ---------------------------------------------------------------------------
# (c) several ranks with halos (multi-device subprocess)
# ---------------------------------------------------------------------------
_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import json
import jax
from repro import obs
from repro.configs.gnn import HECConfig, small_gnn_config
from repro.graph import partition_graph, synthetic_graph
from repro.launch.mesh import make_gnn_mesh
from repro.train import gnn_trainer as gt

g = synthetic_graph(num_vertices=1500, avg_degree=8, num_classes=6,
                    feat_dim=24, seed=0)
ps = partition_graph(g, 4, seed=0)
mesh = make_gnn_mesh(4)
out = {}
for model, hot in [("graphsage", 0), ("graphsage", 48), ("gat", 0)]:
    hec = HECConfig(cache_size=4096, ways=4, life_span=2, push_limit=256,
                    delay=1, hot_size=hot, hot_budget=32 if hot else 0)
    cfg = small_gnn_config(model, batch_size=32, feat_dim=24,
                           num_classes=6, hec=hec)
    dd = gt.build_dist_data(ps, cfg)
    obs.configure()
    tr = gt.DistTrainer(cfg=cfg, mesh=mesh, num_ranks=4, mode="aep")
    st = tr.init_state(jax.random.key(0), dd)
    step = tr.make_step(dd)
    reg = obs.get().registry
    gauges = [reg.value("hec_probe_rows", -1.0, layer=k)
              for k in range(cfg.num_layers)]
    mb = next(iter(tr._pipeline(ps, 0).epoch_batches(0)))
    text = step.lower(st["params"], st["opt_state"], st["hec"], st["hot"],
                      st["inflight"], dd, mb, jax.numpy.uint32(0)) \
        .as_text(dialect="hlo", debug_info=True)
    st, hist = tr.train_epochs(ps, dd, st, 1, step_fn=step)
    L = cfg.num_layers
    out[f"{model}-{hot}"] = {
        "gauges": gauges,
        "rows": [int(mb["layer_nodes"][k].shape[1]) for k in range(L)],
        "probed": "hec_lookup" in text,
        "hec_hits": sum(hist[0].get(f"hec_hits_l{l}", 0.0) for l in range(L)),
        "hot_hits": sum(hist[0].get(f"hot_hits_l{l}", 0.0) for l in range(L))}
print("RESULT" + json.dumps(out))
"""


@pytest.fixture(scope="module")
def multi_rank():
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.abspath(
        os.path.join(os.path.dirname(__file__), "..", "src"))
    proc = subprocess.run([sys.executable, "-c", _SCRIPT], env=env,
                          capture_output=True, text=True, timeout=1200)
    assert proc.returncode == 0, proc.stderr[-3000:]
    line = [l for l in proc.stdout.splitlines() if l.startswith("RESULT")][-1]
    return json.loads(line[len("RESULT"):])


@pytest.mark.parametrize("variant", ["graphsage-0", "graphsage-48", "gat-0"])
def test_multi_rank_step_probes_every_row(multi_rank, variant):
    """With halos on every rank the step probes each layer's rows (the
    gauge reads the layer's rows) and an epoch takes HEC hits (and, with
    the hot tier, tier hits)."""
    r = multi_rank[variant]
    assert r["probed"]
    assert r["gauges"] == [float(n) for n in r["rows"]]
    assert r["hec_hits"] > 0
    assert (r["hot_hits"] > 0) == variant.endswith("-48")
