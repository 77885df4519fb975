"""Compile-only checks of the Pallas kernels for a TPU v5e chip.

No chip is needed: the TPU compiler compiles for a described ``v5e:2x2``
topology.  Each kernel is compiled at the shapes of its real call site at
``GRAPHSAGE_PAPERS100M`` widths (batch 1000, fanouts 5/10/15, hidden 256,
1M-entry HEC), and must come out as a Mosaic ``tpu_custom_call`` -- not as
the interpreter's XLA loop.  The kernel that Mosaic refuses must say so.

``repro.kernels.interpret_mode`` asks ``jax.default_backend()``, which here
is the CPU; the fixture below answers "tpu" for the duration of the module.
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs.gnn import GRAPHSAGE_PAPERS100M as CFG

B = CFG.batch_size                                   # 1000 seeds
N2 = B * (1 + CFG.fanouts[2])                        # 16000 last-layer sources
N1 = N2 * (1 + CFG.fanouts[1])                       # 176000 first-layer dsts
H = CFG.hidden_size
NSETS = CFG.hec.cache_size // CFG.hec.ways


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    """Kernels compiled for one described chip, with the backend seen as a
    TPU and the persistent compile cache off (a TPU entry written here could
    not be read back without a chip)."""
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    jax.clear_caches()
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(jax, "default_backend", lambda: "tpu")
        yield SingleDeviceSharding(topo.devices[0])
    jax.clear_caches()
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _spec(sharding, shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compile_mosaic(fn, *args):
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text, "kernel was not lowered by Mosaic"


@pytest.mark.parametrize("n,c,k,dropout,relu", [
    (N1, CFG.feat_dim, H, CFG.dropout, True),        # first layer
    (B, H, CFG.num_classes, 0.0, False),             # output layer
])
def test_fused_update_compiles(one_chip, n, c, k, dropout, relu):
    from repro.kernels.update_fused import fused_update
    s = lambda *shape: _spec(one_chip, shape)
    _compile_mosaic(
        lambda a, h, wn, ws, b, seed: fused_update(
            a, h, wn, ws, b, relu=relu, dropout=dropout, seed=seed),
        s(n, c), s(n, c), s(c, k), s(c, k), s(k),
        _spec(one_chip, (), jnp.uint32))


def test_sage_agg_compiles(one_chip):
    from repro.kernels.sage_agg import sage_agg
    f = CFG.fanouts[2]
    _compile_mosaic(sage_agg, _spec(one_chip, (N2, H)),
                    _spec(one_chip, (B, f), jnp.int32),
                    _spec(one_chip, (N2,), jnp.bool_))


def test_hec_probe_compiles(one_chip):
    """The serve-side batched probe: 4 requesters x 256 halo slots
    against one rank's 1M-entry layer cache."""
    from repro.cache.hec import HECState
    from repro.kernels.hec_search import hec_probe
    ways = CFG.hec.ways
    state = HECState(tags=_spec(one_chip, (NSETS, ways), jnp.int32),
                     age=_spec(one_chip, (NSETS, ways), jnp.int32),
                     values=_spec(one_chip, (NSETS, ways, H)))
    _compile_mosaic(hec_probe, state, _spec(one_chip, (4, 256), jnp.int32))


def test_gat_edge_compiles(one_chip):
    """GAT_PAPERS100M's last hidden layer: 4 heads of 256."""
    from repro.kernels.ops import gat_edge_aggregate
    heads, f = 4, CFG.fanouts[2]
    _compile_mosaic(gat_edge_aggregate,
                    _spec(one_chip, (N2, heads, H)),
                    _spec(one_chip, (N2, heads)),
                    _spec(one_chip, (N2, heads)),
                    _spec(one_chip, (B, f), jnp.int32),
                    _spec(one_chip, (N2,), jnp.bool_))


def test_fused_serve_layer_refuses_tpu(one_chip):
    """fused_kernel=True selects a kernel Mosaic refuses: it raises."""
    from repro.kernels import serve_fused
    with pytest.raises(NotImplementedError, match="fused_kernel=True"):
        serve_fused.require_interpreter()
    with pytest.raises(NotImplementedError, match="fused_kernel=True"):
        jax.jit(serve_fused.fused_serve_layer).lower(
            _spec(one_chip, (N2, H)), _spec(one_chip, (B, 15), jnp.int32),
            _spec(one_chip, (N2,), jnp.bool_), _spec(one_chip, (H, H)),
            _spec(one_chip, (H, H)), _spec(one_chip, (H,)))
