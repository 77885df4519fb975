"""Unified comm/cache subsystem tests (PR 4).

Pins the refactor's three contracts:

  (a) the unified HEC in ``repro.cache.hec`` bit-matches the pre-refactor
      ``core/hec.py`` state transitions on identical insert/lookup traces
      (a pure-numpy reference of the documented semantics: Fibonacci-hash
      set index, match > empty > oldest-OCF way choice, stable same-set
      batch de-conflict, last-write-wins) — and ``repro.core.hec`` is a
      true shim (same function objects),

  (b) trainer steps bit-match between overlap (push dispatched between
      forward and backward) and inline push schedules after a full epoch
      — params, HEC contents, and loss history (multi-device subprocess),

  (c) exchange plans round-trip the partition contract exactly on random
      partitions: push_mask == db_halo membership, sorted owner tables ==
      ``PartitionSet.route``, and one ``exchange_halos_host`` delivers
      every halo its owner's row, identically to the legacy per-call path.
"""
import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.cache import hec as H
from repro.cache import hot_tier as T
from repro.comm.engine import HaloExchangeEngine
from repro.comm.plan import (_SENTINEL, build_exchange_plan,
                             partition_degrees)
from repro.graph import partition_graph, synthetic_graph


# ---------------------------------------------------------------------------
# (a) unified HEC bit-matches the pre-refactor state transitions
# ---------------------------------------------------------------------------
def _ref_set_index(vids, nsets):
    h = (vids.astype(np.uint32) * np.uint32(0x9E3779B1)) >> np.uint32(8)
    return (h % np.uint32(nsets)).astype(np.int64)


class RefHEC:
    """Pure-numpy reference of the pre-refactor core/hec.py semantics."""

    def __init__(self, cache_size, ways, dim):
        nsets = cache_size // ways
        self.tags = np.full((nsets, ways), -1, np.int32)
        self.age = np.zeros((nsets, ways), np.int32)
        self.values = np.zeros((nsets, ways, dim), np.float32)

    def tick(self, life_span):
        age = self.age + 1
        expired = age > life_span
        self.tags = np.where(expired, -1, self.tags)
        self.age = np.where(expired, 0, age).astype(np.int32)

    def store(self, vids, embs):
        vids = np.asarray(vids, np.int32)
        n = len(vids)
        nsets, ways = self.tags.shape
        valid = vids >= 0
        s = _ref_set_index(vids, nsets)
        # way choice from the PRE-batch state for every entry at once
        way = np.empty(n, np.int64)
        for i in range(n):
            row = self.tags[s[i]]
            match = row == vids[i]
            empty = row < 0
            if match.any():
                way[i] = np.argmax(match)
            elif empty.any():
                way[i] = np.argmax(empty)
            else:
                way[i] = np.argmax(self.age[s[i]])
        # stable same-set de-conflict: r-th same-set entry takes (way+r)%ways
        order = np.argsort(s, kind="stable")
        s_sorted = s[order]
        first = np.searchsorted(s_sorted, s_sorted, side="left")
        rank = np.empty(n, np.int64)
        rank[order] = np.arange(n) - first
        way = (way + rank) % ways
        # scatter in batch order: later entries win on (set, way) collisions
        for i in range(n):
            if valid[i]:
                self.tags[s[i], way[i]] = vids[i]
                self.age[s[i], way[i]] = 0
                self.values[s[i], way[i]] = embs[i]


@pytest.mark.parametrize("seed,ways", [(0, 2), (1, 4), (2, 8)])
def test_unified_hec_bitmatches_reference_trace(seed, ways):
    rng = np.random.default_rng(seed)
    cs, dim = 16 * ways, 4
    st = H.hec_init(cs, ways, dim)
    ref = RefHEC(cs, ways, dim)
    for step in range(20):
        n = int(rng.integers(1, 48))
        vids = rng.integers(-1, 5000, n).astype(np.int32)
        embs = rng.normal(size=(n, dim)).astype(np.float32)
        st = H.hec_store(st, jnp.asarray(vids), jnp.asarray(embs))
        ref.store(vids, embs)
        if step % 3 == 2:
            st = H.hec_tick(st, life_span=4)
            ref.tick(life_span=4)
        np.testing.assert_array_equal(np.asarray(st.tags), ref.tags)
        np.testing.assert_array_equal(np.asarray(st.age), ref.age)
        np.testing.assert_array_equal(np.asarray(st.values), ref.values)
        # lookups agree with the reference contents
        probe = rng.integers(0, 5000, 32).astype(np.int32)
        hit, emb = H.hec_lookup(st, jnp.asarray(probe))
        for i, v in enumerate(probe):
            srow = _ref_set_index(np.asarray([v], np.int32), cs // ways)[0]
            m = ref.tags[srow] == v
            assert bool(hit[i]) == bool(m.any())
            if m.any():
                np.testing.assert_array_equal(
                    np.asarray(emb[i]), ref.values[srow, np.argmax(m)])


def test_core_hec_is_a_pure_shim():
    """repro.core.hec re-exports the SAME objects as repro.cache.hec —
    there is exactly one HEC implementation."""
    from repro.core import hec as old
    for name in ["HECState", "hec_init", "hec_store", "hec_search",
                 "hec_load", "hec_lookup", "hec_tick", "hec_occupancy"]:
        assert getattr(old, name) is getattr(H, name), name


def test_serving_caches_are_policy_wrappers():
    from repro.cache.hec import EmbeddingCache
    from repro.serve.gnn.embedding_cache import ServingCache
    from repro.serve.gnn.distributed.sharded_cache import ShardedServingCache
    assert issubclass(ServingCache, EmbeddingCache)
    assert issubclass(ShardedServingCache, EmbeddingCache)
    # no overridden state transitions: store/reset logic comes from the base
    for cls in (ServingCache, ShardedServingCache):
        assert "warm" not in cls.__dict__
        assert "sync_host" not in cls.__dict__
        assert "on_model_update" not in cls.__dict__


def test_push_tag_bitcast_roundtrip():
    """AEP tags ride the fused all_to_all bitcast into a float lane —
    the pack/unpack must be bit-exact for every tag value incl. -1 and
    the sentinel."""
    tags = jnp.asarray(np.array([[-1, 0, 1, 2 ** 30 - 1, 12345]], np.int32))
    packed = jax.lax.bitcast_convert_type(tags, jnp.float32)
    unpacked = jax.lax.bitcast_convert_type(packed, jnp.int32)
    np.testing.assert_array_equal(np.asarray(unpacked), np.asarray(tags))


# ---------------------------------------------------------------------------
# (b) overlap-vs-inline trainer bit-match (multi-device subprocess)
# ---------------------------------------------------------------------------
_OVERLAP_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import json
import jax
import numpy as np
from repro.configs.gnn import HECConfig, small_gnn_config
from repro.graph import partition_graph, synthetic_graph
from repro.launch.mesh import make_gnn_mesh
from repro.train.gnn_trainer import DistTrainer, build_dist_data

g = synthetic_graph(num_vertices=1500, avg_degree=8, num_classes=6,
                    feat_dim=24, seed=0)
ps = partition_graph(g, 4, seed=0)
mesh = make_gnn_mesh(4)

def bit_equal(a, b):
    return bool(jax.tree_util.tree_all(jax.tree_util.tree_map(
        lambda x, y: bool((np.asarray(x) == np.asarray(y)).all()), a, b)))

out = {}
for hot in [0, 48]:
    hec = HECConfig(cache_size=4096, ways=4, life_span=2, push_limit=256,
                    delay=1, hot_size=hot, hot_budget=32 if hot else 0)
    cfg = small_gnn_config("graphsage", batch_size=32, feat_dim=24,
                           num_classes=6, hec=hec)
    dd = build_dist_data(ps, cfg)
    states, hists, hot_hits = {}, {}, 0.0
    for overlap in [True, False]:
        tr = DistTrainer(cfg=cfg, mesh=mesh, num_ranks=4, mode="aep",
                         overlap=overlap)
        st = tr.init_state(jax.random.key(0), dd)
        st, hist = tr.train_epochs(ps, dd, st, 2)
        states[overlap] = st
        hists[overlap] = [h["loss"] for h in hist]
        hot_hits += sum(sum(h.get(f"hot_hits_l{l}", 0.0)
                            for l in range(cfg.num_layers)) for h in hist)
    out["hot" if hot else "base"] = {
        "params_equal": bit_equal(states[True]["params"],
                                  states[False]["params"]),
        "hec_equal": bit_equal(states[True]["hec"], states[False]["hec"]),
        "hot_equal": bit_equal(states[True]["hot"], states[False]["hot"]),
        "inflight_equal": bit_equal(states[True]["inflight"],
                                    states[False]["inflight"]),
        "loss_equal": hists[True] == hists[False],
        "loss_first": hists[True][0], "loss_last": hists[True][-1],
        "hot_hits": hot_hits,
    }
print("RESULT" + json.dumps(out))
"""


@pytest.fixture(scope="module")
def overlap_results():
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.abspath(
        os.path.join(os.path.dirname(__file__), "..", "src"))
    proc = subprocess.run([sys.executable, "-c", _OVERLAP_SCRIPT], env=env,
                          capture_output=True, text=True, timeout=1200)
    assert proc.returncode == 0, proc.stderr[-3000:]
    line = [l for l in proc.stdout.splitlines() if l.startswith("RESULT")][-1]
    return json.loads(line[len("RESULT"):])


@pytest.mark.parametrize("variant", ["base", "hot"])
def test_overlap_bitmatches_inline_push(overlap_results, variant):
    """The paper's dispatch-then-wait overlap moves identical bits: model
    params, HEC contents, hot-tier replicas, in-flight queue, and loss
    history all bit-match the inline-push schedule after a full epoch —
    with AND without the hot-tier broadcast segment riding the fused
    collective."""
    r = overlap_results[variant]
    assert r["params_equal"]
    assert r["hec_equal"]
    assert r["hot_equal"]
    assert r["inflight_equal"]
    assert r["loss_equal"]


@pytest.mark.parametrize("variant", ["base", "hot"])
def test_overlap_training_converges(overlap_results, variant):
    r = overlap_results[variant]
    assert r["loss_last"] < r["loss_first"]


def test_hot_tier_training_serves_hub_halos(overlap_results):
    """With the tier on, hub halo rows are answered from the local
    replica (hot hits observed); with it off the counters don't exist."""
    assert overlap_results["hot"]["hot_hits"] > 0
    assert overlap_results["base"]["hot_hits"] == 0


# ---------------------------------------------------------------------------
# (c) exchange-plan round-trip identity on random partitions
# ---------------------------------------------------------------------------
@pytest.fixture(scope="module", params=[(0, 3), (1, 4)])
def plan_ps(request):
    seed, R = request.param
    g = synthetic_graph(num_vertices=800, avg_degree=6, num_classes=4,
                        feat_dim=8, seed=seed)
    ps = partition_graph(g, R, seed=seed)
    return ps, build_exchange_plan(ps)


def test_plan_matches_db_halo_contract(plan_ps):
    ps, plan = plan_ps
    R = ps.num_parts
    for i in range(R):
        for j in range(R):
            db = ps.db_halo(i, j)
            assert plan.pair_rows[i, j] == len(db)
            np.testing.assert_array_equal(plan.db_halo[i, j, :len(db)], db)
            assert (plan.db_halo[i, j, len(db):] == _SENTINEL).all()
            # push_mask[i, j, p] <=> solid p of rank i is a halo on rank j
            expect = np.zeros(plan.push_mask.shape[-1], bool)
            if i != j:
                expect[:ps.parts[i].num_solid] = np.isin(
                    ps.parts[i].solid_vids, db)
            np.testing.assert_array_equal(plan.push_mask[i, j], expect)


def test_plan_solid_tables_match_route(plan_ps):
    ps, plan = plan_ps
    for r, p in enumerate(ps.parts):
        S = p.num_solid
        vids = plan.solid_sorted_vids[r, :S]
        np.testing.assert_array_equal(vids, np.sort(p.solid_vids))
        assert (plan.solid_sorted_vids[r, S:] == _SENTINEL).all()
        owner, local = ps.route(vids)
        assert (owner == r).all()
        np.testing.assert_array_equal(plan.solid_sorted_idx[r, :S], local)


def test_exchange_roundtrip_identity(plan_ps):
    """One exchange delivers, for EVERY halo replica, exactly its owner's
    row — h_solid encodes (vid_o, owner) so the received rows are
    self-identifying."""
    ps, plan = plan_ps
    engine = HaloExchangeEngine(ps.num_parts, plan=plan)
    h_solid = [np.stack([p.solid_vids.astype(np.float32),
                         np.full(p.num_solid, r, np.float32)], 1)
               for r, p in enumerate(ps.parts)]
    rows, nbytes = engine.exchange_halos_host(h_solid)
    assert plan.halo_rows_total == sum(
        int(plan.pair_rows[i, j])
        for i in range(ps.num_parts) for j in range(ps.num_parts) if i != j)
    assert nbytes == plan.exchange_bytes(dim=2)
    assert nbytes == plan.halo_rows_total * (2 * 4 + 4)
    for j, p in enumerate(ps.parts):
        np.testing.assert_array_equal(rows[j][:, 0],
                                      p.halo_vids.astype(np.float32))
        np.testing.assert_array_equal(rows[j][:, 1],
                                      p.halo_owner.astype(np.float32))


def test_exchange_publishes_per_rank_series(plan_ps):
    """The offline exchange publishes receiver-side rank series that
    match the plan-time expectation exactly (exact exchange = zero
    drift by construction)."""
    from repro import obs
    ps, plan = plan_ps
    obs.configure()                           # fresh default registry
    try:
        engine = HaloExchangeEngine(ps.num_parts, plan=plan)
        h_solid = [np.zeros((p.num_solid, 3), np.float32)
                   for p in ps.parts]
        engine.exchange_halos_host(h_solid)
        reg = obs.get().registry
        got = obs.rank_series(reg, "rank_exchange_rows", ps.num_parts)
        np.testing.assert_array_equal(got, plan.expected_inbound_rows())
        by = obs.rank_series(reg, "rank_exchange_bytes", ps.num_parts)
        assert by.sum() == plan.exchange_bytes(dim=3)
        drift = obs.EdgeCutDriftDetector(plan.expected_inbound_rows())
        assert drift.update(0, got) == [] and drift.last_drift == 0.0
    finally:
        obs.configure()


def test_compat_exchange_matches_engine(plan_ps):
    from repro.serve.gnn.distributed import exchange_halos
    ps, plan = plan_ps
    rng = np.random.default_rng(7)
    h_solid = [rng.normal(size=(p.num_solid, 5)).astype(np.float32)
               for p in ps.parts]
    engine = HaloExchangeEngine(ps.num_parts, plan=plan)
    rows_a, nb_a = engine.exchange_halos_host(h_solid)
    rows_b, nb_b = exchange_halos(ps, h_solid)
    assert nb_a == nb_b
    for a, b in zip(rows_a, rows_b):
        np.testing.assert_array_equal(a, b)


# ---------------------------------------------------------------------------
# (d) shared set-index hash: kernel and cache can never drift
# ---------------------------------------------------------------------------
def test_set_index_shared():
    """kernels/hec_search.set_index IS repro.cache.hec.set_index (one
    function object), and both match the documented Fibonacci hash."""
    from repro.kernels import hec_search
    assert hec_search.set_index is H.set_index
    assert H._set_index is H.set_index          # internal alias too
    vids = np.array([-1, 0, 1, 7, 4096, 2 ** 30, 123456789], np.int32)
    for nsets in [16, 128, 4096]:
        got = np.asarray(H.set_index(jnp.asarray(vids), nsets))
        np.testing.assert_array_equal(got, _ref_set_index(vids, nsets))


# ---------------------------------------------------------------------------
# (e) hot-vertex tier: plan tables, staleness fallback, fused-push segment
# ---------------------------------------------------------------------------
@pytest.fixture(scope="module")
def hot_ps():
    g = synthetic_graph(num_vertices=900, avg_degree=8, num_classes=4,
                        feat_dim=8, seed=2, intra_prob=0.35)
    return partition_graph(g, 4, seed=0)


def test_plan_hot_tables_contract(hot_ps):
    """Hot set = top-K degree among halo'd vertices, sorted by vid; hot
    vids leave the pairwise push contract, db_halo stays untouched, and
    hot_size=0 is byte-identical to the pre-tier plan."""
    ps = hot_ps
    K = 64
    plan0 = build_exchange_plan(ps)
    plan = build_exchange_plan(ps, hot_size=K)
    assert plan.hot_size == K
    assert (np.diff(plan.hot_vids) > 0).all()          # sorted, unique
    deg = partition_degrees(ps)
    halo_d = np.unique(np.concatenate([p.halo_vids for p in ps.parts]))
    assert np.isin(plan.hot_vids, halo_d).all()        # halos somewhere
    # every non-hot candidate has degree <= the lowest hot degree
    cold = np.setdiff1d(halo_d, plan.hot_vids)
    assert deg[cold].max() <= deg[plan.hot_vids].min() + 0  # ties by vid
    np.testing.assert_array_equal(plan.hot_owner,
                                  ps.owner[plan.hot_vids])
    reps = sum(int(np.isin(p.halo_vids, plan.hot_vids).sum())
               for p in ps.parts)
    assert int(plan.hot_replicas.sum()) == reps
    # db_halo (the partition contract) is NOT filtered...
    np.testing.assert_array_equal(plan.db_halo, plan0.db_halo)
    # ...but push_mask is: exactly the hot rows leave the contract
    for i in range(ps.num_parts):
        solid_hot = np.isin(ps.parts[i].solid_vids, plan.hot_vids)
        for j in range(ps.num_parts):
            expect = plan0.push_mask[i, j].copy()
            expect[:ps.parts[i].num_solid] &= ~solid_hot
            np.testing.assert_array_equal(plan.push_mask[i, j], expect)
    # hot_size=0 (the default) is byte-identical to the pre-tier plan
    np.testing.assert_array_equal(plan0.push_mask,
                                  build_exchange_plan(ps).push_mask)
    assert plan0.hot_size == 0
    m = plan.modeled_remote_rows(deg, rounds=16, refresh_every=16)
    assert m["hot_rows"] < m["baseline_rows"]


def test_tier_staleness_fallback():
    """A replica slot is readable for exactly ``life_span`` ticks after a
    refresh, then ``tier_lookup`` rejects it — the caller falls back to
    the normal fetch path (the paper's bounded-staleness semantics)."""
    hot_vids = jnp.asarray([3, 7, 20], jnp.int32)
    st = T.tier_init(3, 4)
    probe = jnp.asarray([3, 7, 20, 5], jnp.int32)
    hit, _ = T.tier_lookup(st, hot_vids, probe, life_span=2)
    assert not np.asarray(hit).any()                   # empty: all stale
    st = T.tier_store(st, jnp.asarray([0, 2], jnp.int32),
                      jnp.ones((2, 4)) * jnp.asarray([[1.0], [2.0]]))
    hit, emb = T.tier_lookup(st, hot_vids, probe, life_span=2)
    np.testing.assert_array_equal(np.asarray(hit),
                                  [True, False, True, False])
    np.testing.assert_array_equal(np.asarray(emb[0]), np.full(4, 1.0))
    np.testing.assert_array_equal(np.asarray(emb[2]), np.full(4, 2.0))
    for _ in range(2):                                 # ages 1, 2: fresh
        st = T.tier_tick(st)
        hit, _ = T.tier_lookup(st, hot_vids, probe, life_span=2)
        np.testing.assert_array_equal(np.asarray(hit),
                                      [True, False, True, False])
    st = T.tier_tick(st)                               # age 3 > ls: stale
    hit, _ = T.tier_lookup(st, hot_vids, probe, life_span=2)
    assert not np.asarray(hit).any()
    # serving semantics (life_span=None): fresh until dropped
    hit, _ = T.tier_lookup(st, hot_vids, probe)
    np.testing.assert_array_equal(np.asarray(hit),
                                  [True, False, True, False])


def test_push_hot_segment_roundtrip():
    """The hot broadcast segment rides the SAME fused all_to_all: pack +
    unpack are bit-exact for tags, payload, hot slot ids, and hot rows
    (single-device mesh, where the collective is the identity)."""
    from jax.sharding import PartitionSpec as P
    R, L, nc, hb, dmax = 1, 2, 3, 2, 5
    engine = HaloExchangeEngine(R, L, nc, hot_budget=hb)
    rng = np.random.default_rng(0)
    tags = jnp.asarray(rng.integers(-1, 2**31 - 1, (R, R, L, nc)),
                       jnp.int32)
    embs = jnp.asarray(rng.normal(size=(R, R, L, nc, dmax)), jnp.float32)
    h_tags = jnp.asarray([[0, -1], [1, 2**31 - 1]], jnp.int32)  # [L, hb]
    h_embs = jnp.asarray(rng.normal(size=(L, hb, dmax)), jnp.float32)

    mesh = jax.make_mesh((1,), ("data",))

    def run(t, e):
        rt, re, rht, rhe = engine.push(t[0], e[0],
                                       hot=(h_tags, h_embs))
        return rt[None], re[None], rht[None], rhe[None]

    shard = P("data")
    f = jax.jit(jax.shard_map(run, mesh=mesh, in_specs=(shard, shard),
                              out_specs=(shard,) * 4, check_vma=False))
    rt, re, rht, rhe = f(tags, embs)
    np.testing.assert_array_equal(np.asarray(rt), np.asarray(tags))
    np.testing.assert_array_equal(np.asarray(re), np.asarray(embs))
    np.testing.assert_array_equal(np.asarray(rht)[0, 0],
                                  np.asarray(h_tags))
    np.testing.assert_array_equal(np.asarray(rhe)[0, 0],
                                  np.asarray(h_embs))


def test_consume_push_feeds_tier():
    """The delay-expired hot segment lands in the replica (slot scatter)
    while the HEC consumes the pairwise segment, and ticking past the
    life-span invalidates the replica again."""
    L, dims = 2, [4, 4]
    engine = HaloExchangeEngine(num_ranks=2, num_layers=L, push_limit=2,
                                hot_budget=2)
    hec = [H.hec_init(16, 2, 4) for _ in range(L)]
    hot = [T.tier_init(5, 4) for _ in range(L)]
    inflight = {
        "tags": jnp.full((1, 2, L, 2), -1, jnp.int32),
        "embs": jnp.zeros((1, 2, L, 2, 4), jnp.float32),
        "hot_tags": jnp.asarray(
            [[[[0, -1], [2, -1]], [[1, -1], [-1, -1]]]], jnp.int32),
        "hot_embs": jnp.ones((1, 2, L, 2, 4), jnp.float32),
    }
    hec, hot = engine.consume_push(hec, inflight, dims, life_span=2,
                                   hot=hot)
    age0 = np.asarray(hot[0].age)
    assert age0[0] == 0 and age0[1] == 0          # slots 0 (src 0), 1 (src 1)
    assert age0[2] > 2 and age0[3] > 2            # untouched slots stay stale
    assert np.asarray(hot[1].age)[2] == 0         # layer 1 slot from src 0


def test_push_tags_travel_as_exact_normal_floats():
    """Tags in the fused payload are float32 values a float op cannot
    change: integers in [-2**15, 2**16), never denormals or NaNs (the TPU
    compiler may lower the pack through a float ``maximum``, which flushes
    denormals and rewrites NaN bits)."""
    from repro.comm.engine import _f32_to_tags, _tags_to_f32
    tags = jnp.asarray([-2**31, -1, 0, 1, 7, 2**16 - 1, 2**16, 123456789,
                        2**31 - 1], jnp.int32)
    halves = np.asarray(_tags_to_f32(tags))
    assert np.all(np.isfinite(halves))
    assert np.all(halves == np.round(halves))
    assert np.all((halves == 0) | (np.abs(halves) >= 1.0))
    assert halves.min() >= -2**15 and halves.max() < 2**16
    np.testing.assert_array_equal(
        np.asarray(_f32_to_tags(jnp.maximum(jnp.asarray(halves), -jnp.inf))),
        np.asarray(tags))
