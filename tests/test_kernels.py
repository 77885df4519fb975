"""Per-kernel shape/dtype sweeps against the pure-jnp oracles (on the CPU
backend the Pallas kernel bodies run in the interpreter)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref


@pytest.mark.parametrize("N,C,K", [(64, 32, 64), (300, 96, 130),
                                   (257, 128, 256), (16, 100, 47)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_fused_update_sweep(N, C, K, dtype):
    ks = jax.random.split(jax.random.key(N + K), 5)
    agg = jax.random.normal(ks[0], (N, C), dtype)
    sh = jax.random.normal(ks[1], (N, C), dtype)
    wn = jax.random.normal(ks[2], (C, K), dtype) * 0.1
    ws = jax.random.normal(ks[3], (C, K), dtype) * 0.1
    b = jax.random.normal(ks[4], (K,), dtype) * 0.1
    out = ops.fused_update(agg, sh, wn, ws, b, relu=True)
    exp = ref.fused_update_ref(agg, sh, wn, ws, b, relu=True)
    tol = 1e-5 if dtype == jnp.float32 else 5e-2
    np.testing.assert_allclose(out, exp, atol=tol, rtol=tol)


@pytest.mark.parametrize("drop", [0.1, 0.5, 0.9])
def test_fused_update_dropout_matches_ref(drop):
    N, C, K = 128, 64, 128
    ks = jax.random.split(jax.random.key(0), 5)
    args = (jax.random.normal(ks[0], (N, C)), jax.random.normal(ks[1], (N, C)),
            jax.random.normal(ks[2], (C, K)) * 0.1,
            jax.random.normal(ks[3], (C, K)) * 0.1,
            jax.random.normal(ks[4], (K,)) * 0.1)
    out = ops.fused_update(*args, relu=True, dropout=drop, seed=jnp.uint32(7))
    exp = ref.fused_update_ref(*args, relu=True, dropout=drop,
                               seed=jnp.uint32(7))
    np.testing.assert_allclose(out, exp, atol=1e-4, rtol=1e-4)
    # drop fraction plausible (relu already zeroes ~half)
    frac = float((out == 0).mean())
    assert frac >= drop * 0.8


def test_fused_update_no_relu():
    N, C, K = 64, 32, 32
    ks = jax.random.split(jax.random.key(1), 5)
    args = (jax.random.normal(ks[0], (N, C)), jax.random.normal(ks[1], (N, C)),
            jax.random.normal(ks[2], (C, K)), jax.random.normal(ks[3], (C, K)),
            jax.random.normal(ks[4], (K,)))
    out = ops.fused_update(*args, relu=False)
    exp = ref.fused_update_ref(*args, relu=False)
    np.testing.assert_allclose(out, exp, atol=1e-5, rtol=1e-5)


@pytest.mark.parametrize("N,M,f,D", [(100, 30, 5, 32), (333, 64, 9, 64),
                                     (50, 50, 1, 128)])
def test_sage_agg_sweep(N, M, f, D):
    ks = jax.random.split(jax.random.key(M + D), 3)
    h = jax.random.normal(ks[0], (N, D))
    nbr = jax.random.randint(ks[1], (M, f), -1, N)
    valid = jax.random.bernoulli(ks[2], 0.85, (N,))
    out = ops.sage_agg(h, nbr, valid)
    exp = ref.sage_agg_ref(h, nbr, valid)
    np.testing.assert_allclose(out, exp, atol=1e-5, rtol=1e-5)


def test_sage_agg_all_masked_row_is_zero():
    h = jnp.ones((10, 4))
    nbr = jnp.full((3, 2), -1, jnp.int32)
    out = ops.sage_agg(h, nbr, jnp.ones(10, bool))
    assert float(jnp.abs(out).max()) == 0.0


@pytest.mark.parametrize("N,M,f,H,dh", [(80, 20, 4, 2, 8), (200, 50, 7, 4, 16),
                                        (64, 64, 3, 8, 8)])
def test_gat_edge_sweep(N, M, f, H, dh):
    ks = jax.random.split(jax.random.key(N * H), 5)
    z = jax.random.normal(ks[0], (N, H, dh))
    eu = jax.random.normal(ks[1], (N, H))
    ev = jax.random.normal(ks[2], (N, H))
    nbr = jax.random.randint(ks[3], (M, f), -1, N)
    valid = jax.random.bernoulli(ks[4], 0.9, (N,))
    out = ops.gat_edge_aggregate(z, eu, ev, nbr, valid)
    exp = ref.gat_edge_ref(z, eu, ev, nbr, valid)
    np.testing.assert_allclose(out, exp, atol=1e-5, rtol=1e-5)


def test_gat_edge_softmax_normalized():
    """With all-valid neighbors and identical z rows, output == z row."""
    N, M, f, H, dh = 30, 10, 4, 2, 8
    z = jnp.ones((N, H, dh)) * 3.0
    eu = jax.random.normal(jax.random.key(0), (N, H))
    ev = jax.random.normal(jax.random.key(1), (N, H))
    nbr = jax.random.randint(jax.random.key(2), (M, f), 0, N)
    out = ops.gat_edge_aggregate(z, eu, ev, nbr, jnp.ones(N, bool))
    np.testing.assert_allclose(out, 3.0 * np.ones((M, H, dh)), rtol=1e-5)


@pytest.mark.parametrize("cs,ways,n", [(64, 4, 50), (256, 8, 200),
                                       (1024, 16, 333)])
def test_hec_search_kernel_matches_core(cs, ways, n):
    """Pallas HECSearch == repro.core.hec.hec_search on random caches."""
    from repro.core import hec as H
    from repro.kernels.hec_search import hec_search_kernel
    rng = np.random.default_rng(cs + n)
    s = H.hec_init(cs, ways, 4)
    stored = jnp.asarray(rng.integers(0, 10 * cs, cs // 2), jnp.int32)
    s = H.hec_store(s, stored, jnp.ones((len(stored), 4)))
    probe = jnp.concatenate([
        stored[: n // 2],
        jnp.asarray(rng.integers(10 * cs, 20 * cs, n - n // 2), jnp.int32)])
    hit_r, set_r, way_r = H.hec_search(s, probe)
    hit_k, set_k, way_k = hec_search_kernel(s.tags, probe)
    np.testing.assert_array_equal(np.asarray(hit_r), np.asarray(hit_k))
    np.testing.assert_array_equal(np.asarray(set_r), np.asarray(set_k))
    np.testing.assert_array_equal(
        np.asarray(jnp.where(hit_r, way_r, 0)),
        np.asarray(jnp.where(hit_k, way_k, 0)))


# ---------------------------------------------------------------------------
# PR 9: fused serve layer / batched HEC probe / device fanout draw
# ---------------------------------------------------------------------------
def _serve_inputs(M, f, D, K, N, seed=0):
    ks = jax.random.split(jax.random.key(seed), 5)
    h = jax.random.normal(ks[0], (N, D))
    nbr = jax.random.randint(ks[1], (M, f), -1, N)
    valid = jax.random.bernoulli(ks[2], 0.85, (N,))
    wn = jax.random.normal(ks[3], (D, K)) * 0.1
    ws = jax.random.normal(ks[4], (D, K)) * 0.1
    b = jnp.linspace(-1.0, 1.0, K, dtype=jnp.float32)
    return h, nbr, valid, wn, ws, b


@pytest.mark.parametrize("M,f,D,K,N", [(64, 8, 32, 32, 128),
                                       (200, 5, 48, 64, 333),
                                       (128, 1, 16, 16, 128)])
@pytest.mark.parametrize("relu", [True, False])
def test_fused_serve_layer_bitmatches_composed(M, f, D, K, N, relu):
    """The fused serve kernel is BIT-exact vs the composed jnp layer (the
    knob-on parity contract in ISSUE 9)."""
    h, nbr, valid, wn, ws, b = _serve_inputs(M, f, D, K, N, seed=M + K)
    out = ops.fused_serve_layer(h, nbr, valid, wn, ws, b, relu=relu)
    exp = ref.serve_layer_ref({"wn": wn, "ws": ws, "b": b}, h, nbr, valid,
                              relu=relu)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(exp))


def test_fused_serve_layer_masked_rows():
    """All -1 rows and rows whose every neighbor is invalid aggregate to
    zero (self-term + bias only), exactly like the composed path."""
    h, _, _, wn, ws, b = _serve_inputs(8, 4, 16, 16, 32, seed=5)
    nbr = jnp.full((8, 4), -1, jnp.int32)
    nbr = nbr.at[1].set(jnp.asarray([3, 7, 2, 9]))    # one live row
    valid = jnp.zeros(32, bool).at[jnp.asarray([3, 7])].set(True)
    out = ops.fused_serve_layer(h, nbr, valid, wn, ws, b, relu=False)
    exp = ref.serve_layer_ref({"wn": wn, "ws": ws, "b": b}, h, nbr, valid,
                              relu=False)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(exp))
    # all-masked row == pure self/bias row of the reference
    agg0 = jnp.zeros((8, 16))
    pure = agg0 @ wn + h[:8] @ ws + b
    np.testing.assert_allclose(np.asarray(out[0]), np.asarray(pure[0]),
                               atol=1e-6)


def test_serve_fused_forward_matches_graphsage():
    """L-layer fused forward == graphsage.forward (dropout off)."""
    from repro.kernels import serve_fused
    from repro.models.gnn import graphsage
    D, hid, f = 16, 24, 4
    params = {"layers": [
        {"wn": jax.random.normal(jax.random.key(1), (D, hid)) * 0.1,
         "ws": jax.random.normal(jax.random.key(2), (D, hid)) * 0.1,
         "b": jnp.zeros((hid,), jnp.float32)},
        {"wn": jax.random.normal(jax.random.key(3), (hid, 8)) * 0.1,
         "ws": jax.random.normal(jax.random.key(4), (hid, 8)) * 0.1,
         "b": jnp.zeros((8,), jnp.float32)}]}
    N1, N0 = 20, 60
    h0 = jax.random.normal(jax.random.key(5), (N0, D))
    valid0 = jax.random.bernoulli(jax.random.key(6), 0.9, (N0,))
    blocks = {"nbr_idx": [
        jax.random.randint(jax.random.key(7), (N1, f), -1, N0),
        jax.random.randint(jax.random.key(8), (8, f), -1, N1)]}
    out_f, val_f = serve_fused.forward(params, h0, valid0, blocks)
    out_c, val_c = graphsage.forward(params, h0, valid0, blocks,
                                     dropout=0.0, seed=jnp.uint32(0))
    np.testing.assert_array_equal(np.asarray(out_f), np.asarray(out_c))
    np.testing.assert_array_equal(np.asarray(val_f), np.asarray(val_c))


@pytest.mark.parametrize("cs,ways,B,n", [(64, 4, 3, 40), (256, 8, 1, 100),
                                         (512, 4, 6, 17)])
def test_hec_search_batched_matches_singles(cs, ways, B, n):
    """Each row of the batched probe == a single hec_search_kernel call."""
    from repro.cache import hec as H
    from repro.kernels.hec_search import hec_search_batched, hec_search_kernel
    rng = np.random.default_rng(cs + B)
    s = H.hec_init(cs, ways, 4)
    stored = jnp.asarray(rng.integers(0, 10 * cs, cs // 2), jnp.int32)
    s = H.hec_store(s, stored, jnp.ones((len(stored), 4)))
    vids = jnp.asarray(rng.integers(-1, 10 * cs, (B, n)), jnp.int32)
    hit_b, set_b, way_b = hec_search_batched(s.tags, vids)
    for i in range(B):
        hit_1, set_1, way_1 = hec_search_kernel(s.tags, vids[i])
        np.testing.assert_array_equal(np.asarray(hit_b[i]),
                                      np.asarray(hit_1))
        np.testing.assert_array_equal(np.asarray(set_b[i]),
                                      np.asarray(set_1))
        np.testing.assert_array_equal(np.asarray(way_b[i]),
                                      np.asarray(way_1))


def test_hec_probe_matches_hec_lookup():
    """hec_probe rows are bit-identical to hec_lookup on each round
    (the cache_fetch(rounds=N) contract of ISSUE 9)."""
    from repro.cache import hec as H
    from repro.kernels.hec_search import hec_probe
    rng = np.random.default_rng(11)
    s = H.hec_init(256, 4, 8)
    stored = jnp.asarray(rng.integers(0, 2000, 128), jnp.int32)
    s = H.hec_store(s, stored,
                    jnp.asarray(rng.normal(size=(128, 8)), jnp.float32))
    vids = jnp.asarray(rng.integers(-1, 2000, (5, 33)), jnp.int32)
    hit_p, emb_p = hec_probe(s, vids)
    for i in range(5):
        hit_l, emb_l = H.hec_lookup(s, vids[i])
        np.testing.assert_array_equal(np.asarray(hit_p[i]),
                                      np.asarray(hit_l))
        np.testing.assert_array_equal(np.asarray(emb_p[i]),
                                      np.asarray(emb_l))
