"""Asynchronous minibatch pipeline tests (repro.pipeline).

Covers: vectorized-sampler parity with the reference ``sample_blocks``
contract (shapes, masks, dst-prefix, halo-leaf, edge-existence, fanout
bound, take-all rows) at three graph/fanout shapes, and statistics; the
host draw's pinned trace; prefetcher determinism for any worker count;
epoch schedules and empty-batch padding for rank imbalance; and
end-to-end bit-identical loss curves for inline vs prefetched sampling.
"""
import numpy as np
import pytest

from repro.configs.gnn import PipelineConfig, small_gnn_config
from repro.graph import partition_graph, synthetic_graph
from repro.graph.sampling import (epoch_minibatches, layer_capacities,
                                  sample_blocks)
from repro.pipeline import (MinibatchPipeline, SamplingPlan, prefetch,
                            sample_blocks_vectorized, stack_ranks)
from repro.pipeline.vectorized_sampler import _draw_neighbors, concat_blocks

FANOUTS = (4, 6)
BATCH = 32


@pytest.fixture(scope="module")
def ps():
    g = synthetic_graph(num_vertices=1500, avg_degree=6, num_classes=4,
                        feat_dim=8, seed=5)
    return partition_graph(g, 2, seed=0)


@pytest.fixture(scope="module")
def part(ps):
    return ps.parts[0]


@pytest.fixture(scope="module")
def ps4():
    """Four parts of a graph of average degree 20: most rows draw at the
    cells' fanouts, and halos are common.  The partitions' CSR holds both
    directions of each generated edge, so a generator degree of 8 gives
    rank 0 an average of 19.9 (and rows at or below 15 to take whole)."""
    g = synthetic_graph(num_vertices=2000, avg_degree=8, num_classes=4,
                        feat_dim=8, seed=6)
    return partition_graph(g, 4, seed=0)


# (partition set fixture, fanouts): the 2-part graph at the small fanouts
# and at the cells' 3-layer fanouts, and the 4-part degree-20 graph
_SHAPES = {"2part-f4,6": ("ps", FANOUTS),
           "2part-f5,10,15": ("ps", (5, 10, 15)),
           "4part-deg20-f5,10,15": ("ps4", (5, 10, 15))}


@pytest.fixture(scope="module", params=list(_SHAPES), ids=list(_SHAPES))
def vec_mb(request):
    """``(part, fanouts, blocks)``: one minibatch of rank 0's first seed
    batch, drawn by the host sampler at each of ``_SHAPES``."""
    ps_name, fanouts = _SHAPES[request.param]
    part = request.getfixturevalue(ps_name).parts[0]
    rng = np.random.default_rng(0)
    seeds = epoch_minibatches(part, BATCH, rng)[0]
    return part, fanouts, sample_blocks_vectorized(part, seeds, fanouts,
                                                   rng, BATCH)


def test_shapes_and_masks(vec_mb):
    _, fanouts, mb = vec_mb
    caps = layer_capacities(BATCH, fanouts)
    assert [len(n) for n in mb.layer_nodes] == caps
    for nodes, mask in zip(mb.layer_nodes, mb.node_mask):
        assert ((nodes >= 0) == mask).all()
    for k, f in enumerate(fanouts):
        assert mb.nbr_idx[k].shape == (caps[k + 1], f)


def test_dst_prefix_property(vec_mb):
    _, _, mb = vec_mb
    for k in range(len(mb.nbr_idx)):
        coarse, fine = mb.layer_nodes[k + 1], mb.layer_nodes[k]
        assert (fine[:len(coarse)] == coarse).all()


def test_fanout_bound(vec_mb):
    _, fanouts, mb = vec_mb
    for k, f in enumerate(fanouts):
        assert (mb.nbr_idx[k] >= 0).sum(1).max() <= f


def test_halos_never_expanded(vec_mb):
    part, _, mb = vec_mb
    for k in range(len(mb.nbr_idx)):
        dsts = mb.layer_nodes[k + 1]
        halo_dst = (dsts >= part.num_solid) & (dsts >= 0)
        assert (mb.nbr_idx[k][halo_dst] < 0).all()


def test_sampled_edges_exist_no_replacement(vec_mb):
    part, fanouts, mb = vec_mb
    for k, f in enumerate(fanouts):
        fine = mb.layer_nodes[k]
        dsts = mb.layer_nodes[k + 1]
        for r in range(len(dsts)):
            v = dsts[r]
            if v < 0 or v >= part.num_solid:
                continue
            row = part.indices[part.indptr[v]:part.indptr[v + 1]]
            got = mb.nbr_idx[k][r]
            got_vids = fine[got[got >= 0]].tolist()
            assert set(got_vids) <= set(row.tolist())
            assert len(set(got_vids)) == len(got_vids)   # w/o replacement
            if len(row) <= f:                            # take-all rows
                assert got_vids == row.tolist()


def test_statistics_match_reference(part):
    """Same sampling distribution => same expected layer occupancy."""
    rng = np.random.default_rng(1)
    seeds = epoch_minibatches(part, BATCH, rng)[0]
    r1, r2 = np.random.default_rng(2), np.random.default_rng(3)
    ref = np.mean([[m.sum() for m in sample_blocks(
        part, seeds, FANOUTS, r1, BATCH).node_mask] for _ in range(8)], 0)
    vec = np.mean([[m.sum() for m in sample_blocks_vectorized(
        part, seeds, FANOUTS, r2, BATCH).node_mask] for _ in range(8)], 0)
    np.testing.assert_allclose(vec, ref, rtol=0.05)


def test_prefetch_deterministic_any_worker_count():
    def make(step):
        rng = np.random.default_rng([7, step])
        return {"step": step, "draw": rng.random(16)}

    runs = {w: list(prefetch(make, 12, num_workers=w, depth=3))
            for w in (0, 1, 4)}
    for w in (1, 4):
        assert [b["step"] for b in runs[w]] == list(range(12))
        for a, b in zip(runs[0], runs[w]):
            np.testing.assert_array_equal(a["draw"], b["draw"])


def test_plan_sample_host_deterministic(ps):
    cfg = small_gnn_config("graphsage", batch_size=BATCH, feat_dim=8,
                           num_classes=4, fanouts=FANOUTS)
    plan = SamplingPlan(ps=ps, cfg=cfg, base_seed=9)
    sched = plan.epoch_schedule(0)
    a = plan.sample_host(0, 1, sched[1])
    b = plan.sample_host(0, 1, sched[1])
    np.testing.assert_array_equal(a["layer_nodes"][0], b["layer_nodes"][0])
    np.testing.assert_array_equal(a["nbr_idx"][0], b["nbr_idx"][0])
    # a different step draws differently
    c = plan.sample_host(0, 0, sched[1])
    assert not np.array_equal(a["nbr_idx"][0], c["nbr_idx"][0])


def test_epoch_schedule_pads_short_ranks():
    """Short ranks get empty padded batches; every seed trains exactly once.

    The partitioner balances train vertices, so force genuine imbalance by
    dropping half of rank 1's train seeds before building the plan.
    """
    g = synthetic_graph(num_vertices=1500, avg_degree=6, num_classes=4,
                        feat_dim=8, seed=5)
    ps2 = partition_graph(g, 2, seed=0)
    tr_idx = np.flatnonzero(ps2.parts[1].train_mask)
    ps2.parts[1].train_mask[tr_idx[len(tr_idx) // 2:]] = False
    cfg = small_gnn_config("graphsage", batch_size=17, feat_dim=8,
                           num_classes=4, fanouts=FANOUTS)
    plan = SamplingPlan(ps=ps2, cfg=cfg, base_seed=0)
    sched = plan.epoch_schedule(0)
    counts = [int(np.ceil(p.train_mask.sum() / 17)) for p in ps2.parts]
    assert counts[1] < counts[0]            # genuinely imbalanced
    assert len(sched) == counts[0]          # epoch runs the longest rank
    for r in range(2):
        got = np.sort(np.concatenate([row[r] for row in sched]))
        want = np.sort(np.flatnonzero(ps2.parts[r].train_mask))
        assert (got == want).all()          # each seed exactly once
    # the short rank's tail steps are empty padded batches
    for k in range(counts[1], counts[0]):
        assert len(sched[k][1]) == 0


@pytest.mark.parametrize("parts", [1, 2, 4])
def test_epoch_schedule_covers_train_once(parts):
    """Each rank's training vertices appear exactly once in an epoch of
    ``SamplingPlan.epoch_schedule``, one seed batch per rank a step; a
    rank past its own batches gets empty ones.  Beyond one part, the last
    rank keeps a third of its training vertices so that it is padded."""
    g = synthetic_graph(num_vertices=1200, avg_degree=6, num_classes=4,
                        feat_dim=8, seed=2)
    ps_n = partition_graph(g, parts, seed=0)
    if parts > 1:
        last = ps_n.parts[-1].train_mask
        last[np.flatnonzero(last)[len(np.flatnonzero(last)) // 3:]] = False
    bs = 8
    cfg = small_gnn_config("graphsage", batch_size=bs, feat_dim=8,
                           num_classes=4)
    plan = SamplingPlan(ps=ps_n, cfg=cfg, base_seed=0)
    counts = [int(np.ceil(p.train_mask.sum() / bs)) for p in ps_n.parts]
    for epoch in (0, 1):
        sched = plan.epoch_schedule(epoch)
        assert len(sched) == max(counts)
        assert all(len(row) == parts for row in sched)
        for r, part in enumerate(ps_n.parts):
            got = np.concatenate([row[r] for row in sched])
            assert len(got) == len(np.unique(got))
            np.testing.assert_array_equal(np.sort(got),
                                          np.flatnonzero(part.train_mask))
            assert all(len(sched[k][r]) > 0 for k in range(counts[r]))
            assert all(len(sched[k][r]) == 0
                       for k in range(counts[r], len(sched)))
    if parts > 1:
        assert counts[-1] < max(counts)         # the padding is exercised


def test_empty_padded_batch_step_is_finite():
    """A fully masked batch through the compiled step: zero examples, zero
    loss, finite params — the all-masked path the padding fix relies on."""
    import jax
    from repro.train.gnn_trainer import DistTrainer, build_dist_data

    g = synthetic_graph(num_vertices=800, avg_degree=6, num_classes=4,
                        feat_dim=8, seed=3)
    ps1 = partition_graph(g, 1, seed=0)
    cfg = small_gnn_config("graphsage", batch_size=16, feat_dim=8,
                           num_classes=4, fanouts=FANOUTS)
    dd = build_dist_data(ps1, cfg)
    tr = DistTrainer(cfg=cfg, mesh=jax.make_mesh((1,), ("data",)),
                     num_ranks=1, mode="aep")
    state = tr.init_state(jax.random.key(0))
    step_fn = tr.make_step(dd, donate=False)
    plan = SamplingPlan(ps=ps1, cfg=cfg, base_seed=0)
    mb = jax.device_put(plan.sample_host(0, 0, [np.empty(0, np.int64)]))
    params, _, _, _, _, _, metrics = step_fn(
        state["params"], state["opt_state"], state["hec"], state["hot"],
        state["inflight"], dd, mb, np.uint32(0))
    assert float(metrics["examples"]) == 0
    assert float(metrics["loss"]) == 0.0
    leaf = jax.tree_util.tree_leaves(params)[0]
    assert bool(jax.numpy.isfinite(leaf).all())


def test_stack_ranks_layout(ps):
    cfg = small_gnn_config("graphsage", batch_size=BATCH, feat_dim=8,
                           num_classes=4, fanouts=FANOUTS)
    plan = SamplingPlan(ps=ps, cfg=cfg, base_seed=0)
    mbh = plan.sample_host(0, 0, plan.epoch_schedule(0)[0])
    caps = layer_capacities(BATCH, FANOUTS)
    R = ps.num_parts
    assert mbh["seeds"].shape == (R, BATCH)
    assert mbh["seeds"].dtype == np.int32
    for k, cap in enumerate(caps):
        assert mbh["layer_nodes"][k].shape == (R, cap)
        assert mbh["node_mask"][k].dtype == np.bool_


@pytest.mark.parametrize("model", ["graphsage", "gat"])
def test_train_bit_identical_sync_vs_pipelined(model):
    """Prefetched epochs (1 and 4 workers) == inline sampling (0 workers),
    bit for bit, in training and evaluation."""
    import jax
    from repro.train.gnn_trainer import DistTrainer, build_dist_data

    g = synthetic_graph(num_vertices=1200, avg_degree=6, num_classes=4,
                        feat_dim=16, seed=7)
    ps1 = partition_graph(g, 1, seed=0)
    mesh = jax.make_mesh((1,), ("data",))

    def run(workers):
        cfg = small_gnn_config(
            model, batch_size=48, feat_dim=16, num_classes=4,
            pipeline=PipelineConfig(num_workers=workers, prefetch_depth=3))
        dd = build_dist_data(ps1, cfg)
        tr = DistTrainer(cfg=cfg, mesh=mesh, num_ranks=1, mode="aep")
        state = tr.init_state(jax.random.key(0))
        state, hist = tr.train_epochs(ps1, dd, state, 2)
        acc = tr.evaluate(ps1, dd, state, num_batches=2)
        return [h["loss"] for h in hist], acc

    loss_sync, acc_sync = run(0)
    loss_1w, acc_1w = run(1)
    loss_4w, acc_4w = run(4)
    assert loss_sync == loss_1w == loss_4w
    assert acc_sync == acc_1w == acc_4w
    assert loss_sync[-1] < loss_sync[0]       # actually learns


def test_concat_blocks_fused_forward_bitmatch(part):
    """Multi-round batching rests on ``concat_blocks``: the fused
    block-diagonal minibatch preserves the dst-prefix invariant at every
    layer and the fused forward computes, row for row, exactly what the
    separate forwards compute (both models)."""
    import jax
    import jax.numpy as jnp
    from repro.models.gnn import gat as gat_lib
    from repro.models.gnn import graphsage as sage_lib
    from repro.train.gnn_trainer import init_model_params

    rng = np.random.default_rng(0)
    B = 8
    mbs = [sample_blocks_vectorized(
        part, rng.integers(0, part.num_solid, B if i != 2 else 3),
        FANOUTS, np.random.default_rng(i), B) for i in range(4)]
    fused = concat_blocks(mbs)
    for k in range(fused.num_layers):           # dst-prefix invariant
        n_dst = len(fused.layer_nodes[k + 1])
        np.testing.assert_array_equal(fused.layer_nodes[k][:n_dst],
                                      fused.layer_nodes[k + 1])
    for model, lib in [("graphsage", sage_lib), ("gat", gat_lib)]:
        cfg = small_gnn_config(model, batch_size=B, feat_dim=8,
                               num_classes=4, fanouts=FANOUTS)
        params = init_model_params(jax.random.key(0), cfg)
        feats = jnp.asarray(part.features)

        def run(mb):
            mask0 = jnp.asarray(mb.node_mask[0])
            h0 = feats[np.clip(mb.layer_nodes[0], 0, part.num_solid - 1)] \
                * mask0[:, None]
            blocks = {"nbr_idx": [jnp.asarray(x.astype(np.int32))
                                  for x in mb.nbr_idx]}
            out, valid = lib.forward(params, h0, mask0, blocks)
            return np.asarray(out), np.asarray(valid)

        of, vf = run(fused)
        for i, m in enumerate(mbs):
            o, v = run(m)
            np.testing.assert_array_equal(of[i * B:(i + 1) * B], o)
            np.testing.assert_array_equal(vf[i * B:(i + 1) * B], v)


# ---------------------------------------------------------------------------
# host fanout draw (``_draw_neighbors``, Floyd's algorithm)
# ---------------------------------------------------------------------------
def _csr(degrees, num_extra=0):
    """CSR whose row ``v`` holds ``degrees[v]`` distinct neighbor VIDs, all
    above the rows (``num_extra`` more VIDs past them act as halos)."""
    indptr = np.zeros(len(degrees) + 1, np.int64)
    indptr[1:] = np.cumsum(degrees)
    base = len(degrees) + num_extra
    indices = np.concatenate([base + 1000 * v + np.arange(d)
                              for v, d in enumerate(degrees)])
    return indptr, indices.astype(np.int64)


def _row(indptr, indices, v):
    return indices[indptr[v]:indptr[v + 1]]


def test_host_draw_inclusion_share():
    """Each neighbor of a degree-40 row is drawn with share f/deg: 20,000
    independent draws of the row, f = 5, every share within 0.015 of
    0.125 (about 6 standard deviations)."""
    indptr, indices = _csr([40])
    n, f = 20000, 5
    out = _draw_neighbors(indptr, indices, np.zeros(n, np.int64), 1, f,
                          np.random.default_rng(11))
    row = _row(indptr, indices, 0)
    share = (out[:, :, None] == row).sum((0, 1)) / n
    np.testing.assert_allclose(share, f / len(row), atol=0.015)
    assert (np.sort(out, 1)[:, 1:] != np.sort(out, 1)[:, :-1]).all()


def test_host_draw_subsets_uniform():
    """Every 2-subset of a degree-5 row comes out equally often: 10 pairs,
    30,000 draws, each pair's share in [0.09, 0.11] (3,000 expected,
    about 5.8 standard deviations either side)."""
    indptr, indices = _csr([5])
    n = 30000
    out = _draw_neighbors(indptr, indices, np.zeros(n, np.int64), 1, 2,
                          np.random.default_rng(12))
    pairs, counts = np.unique(np.sort(out, 1), axis=0, return_counts=True)
    assert len(pairs) == 10
    assert set(pairs.ravel().tolist()) == set(_row(indptr, indices, 0))
    np.testing.assert_allclose(counts / n, 0.1, atol=0.01)


@pytest.mark.parametrize("f", [1, 3, 5])
def test_host_draw_hub_row_distinct(f):
    """A hub row of degree 1,000, beside rows of degree 2-8, draws f
    distinct neighbors of its own; the small rows are undisturbed."""
    degrees = [1000, 2, 4, 8, 5, 3]
    indptr, indices = _csr(degrees)
    cur = np.tile(np.arange(len(degrees)), 50)
    out = _draw_neighbors(indptr, indices, cur, len(degrees), f,
                          np.random.default_rng(f))
    for r, v in enumerate(cur):
        got = out[r][out[r] >= 0]
        row = _row(indptr, indices, v)
        assert len(got) == min(f, len(row))
        assert len(set(got.tolist())) == len(got)
        assert set(got.tolist()) <= set(row.tolist())
    hub = out[cur == 0]
    assert len(np.unique(hub)) > 10 * f      # draws spread over the hub


def test_host_draw_leaves_stay_empty():
    """Rows with ``allow=False``, halo rows and ``-1`` padding keep all
    ``-1``; the other rows draw as usual."""
    indptr, indices = _csr([12, 3, 9, 1], num_extra=4)
    S, f = 4, 4
    cur = np.array([0, -1, 5, 1, 2, 3, 7, -1, 0])       # 5, 7: halos
    allow = np.array([True, True, True, True, False, True, True, True,
                      False])
    out = _draw_neighbors(indptr, indices, cur, S, f,
                          np.random.default_rng(0), allow=allow)
    assert out.shape == (len(cur), f)
    empty = [1, 2, 4, 6, 7, 8]
    assert (out[empty] == -1).all()
    assert (out[0] >= 0).all()
    np.testing.assert_array_equal(out[3], list(_row(indptr, indices, 1))
                                  + [-1])
    np.testing.assert_array_equal(out[5], list(_row(indptr, indices, 3))
                                  + [-1] * 3)


def test_host_draw_pinned_trace():
    """The host draw's exact output for a fixed stream never drifts: any
    change to Floyd's draw order moves every minibatch of every run.
    Rows of degree 2, 3 (= f), 5 and 8, a halo, a degree-4 row with
    ``allow=False`` and ``-1`` padding."""
    indptr = np.array([0, 2, 5, 10, 18, 22])
    indices = np.array([7, 1,  0, 2, 9,  4, 8, 3, 6, 1,
                        2, 5, 9, 0, 7, 3, 6, 8,  1, 2, 3, 0])
    cur = np.array([3, 0, 1, 2, 6, 4, -1, 3, 2])        # 6: a halo
    allow = np.array([True] * 5 + [False] + [True] * 3)
    out = _draw_neighbors(indptr, indices, cur, 5, 3,
                          np.random.default_rng(2024), allow=allow)
    want = np.array([[5, 9, 8],            # row 3, degree 8
                     [7, 1, -1],           # row 0, degree 2: whole row
                     [0, 2, 9],            # row 1, degree 3: whole row
                     [3, 8, 1],            # row 2, degree 5
                     [-1, -1, -1],         # halo
                     [-1, -1, -1],         # row 4, allow=False
                     [-1, -1, -1],         # padding
                     [2, 6, 8],            # row 3 again, a fresh draw
                     [4, 6, 1]])           # row 2 again
    np.testing.assert_array_equal(out, want)


@pytest.mark.parametrize("f", [1, 2, 5, 10])
def test_host_draw_degree_at_and_past_fanout(f):
    """``deg == f`` takes the whole row in CSR order with no draw;
    ``deg == f + 1`` leaves out one neighbor, each about equally often."""
    indptr, indices = _csr([f, f + 1])
    n = 4000
    cur = np.repeat([0, 1], n)
    out = _draw_neighbors(indptr, indices, cur, 2, f,
                          np.random.default_rng(f))
    np.testing.assert_array_equal(out[:n], np.tile(_row(indptr, indices, 0),
                                                   (n, 1)))
    past = out[n:]
    row = _row(indptr, indices, 1)
    assert (past >= 0).all()
    assert (np.sort(past, 1)[:, 1:] != np.sort(past, 1)[:, :-1]).all()
    left_out = n - (past[:, :, None] == row).sum((0, 1))
    np.testing.assert_allclose(left_out / n, 1 / (f + 1), atol=0.04)


@pytest.mark.parametrize("workers", [0, 1, 4])
def test_plan_sample_host_same_any_worker_count(ps, workers):
    """Host minibatches of ``SamplingPlan.sample_host`` are identical for
    0 (inline), 1 and 4 prefetch workers: each step owns its stream."""
    cfg = small_gnn_config("graphsage", batch_size=BATCH, feat_dim=8,
                           num_classes=4, fanouts=FANOUTS)
    plan = SamplingPlan(ps=ps, cfg=cfg, base_seed=4)
    sched = plan.epoch_schedule(1)
    n = min(len(sched), 6)

    def make(step):
        return plan.sample_host(1, step, sched[step])

    want = [make(s) for s in range(n)]
    got = list(prefetch(make, n, num_workers=workers, depth=2))
    assert len(got) == n
    for a, b in zip(want, got):
        assert a.keys() == b.keys()
        for key in a:
            xs = a[key] if isinstance(a[key], list) else [a[key]]
            ys = b[key] if isinstance(b[key], list) else [b[key]]
            for x, y in zip(xs, ys, strict=True):
                np.testing.assert_array_equal(x, y)


def test_sample_rows_counter_adds_up(vec_mb):
    """``sample_rows{path}`` grows by the rows each layer expands:
    ``draw`` by those with ``deg > f``, ``all`` by the take-all rows."""
    from repro import obs
    part, fanouts, mb = vec_mb
    obs.configure()
    try:
        reg = obs.get().registry
        deg = part.indptr[1:] - part.indptr[:-1]
        seen = []
        for k, f in enumerate(fanouts):
            cur = mb.layer_nodes[k + 1]
            solid = (cur >= 0) & (cur < part.num_solid)
            d = np.where(solid, deg[np.where(solid, cur, 0)], 0)
            before = {p: reg.value("sample_rows", path=p)
                      for p in ("draw", "all")}
            _draw_neighbors(part.indptr, part.indices, cur, part.num_solid,
                            f, np.random.default_rng(k))
            drawn = reg.value("sample_rows", path="draw") - before["draw"]
            took = reg.value("sample_rows", path="all") - before["all"]
            assert drawn == (d > f).sum()
            assert took == ((d > 0) & (d <= f)).sum()
            assert drawn + took == (d > 0).sum()
            seen += [drawn, took]
        assert min(seen[0::2]) > 0 and max(seen[1::2]) > 0
    finally:
        obs.configure()
