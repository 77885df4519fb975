"""HECSearch Pallas kernel (paper §3.2: "We have optimized these management
functions to perform lookup ... efficiently using OpenMP parallel regions").

The TPU-native HECSearch: tags live in HBM as [nsets, ways]; each probe
hashes its VID_o to a set, DMAs ONE set row via a scalar-prefetched
BlockSpec index_map, and compares all ways in VREGs.  Probes are batched
by the grid; the values gather (HECLoad) runs on the (set, way) pairs this
kernel returns.

Outputs per probe: hit flag and way index (set index is recomputed by the
caller from the same hash — ``set_index`` IS ``repro.cache.hec.set_index``,
one shared function object).
This kernel stays the lookup primitive of the unified cache subsystem
(``repro.cache``); the functional state transitions live there.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# THE set-index hash is defined once, in repro.cache.hec; this module
# re-exports the same function object so kernel and cache can never drift
# (parity pinned in tests/test_comm.py).
from repro.cache.hec import set_index
from repro.kernels import interpret_mode


def _probe(vid, tags_ref, hit_ref, way_ref):
    row = tags_ref[...]                       # [1, ways]
    match = row == vid
    any_hit = jnp.any(match) & (vid >= 0)
    # first matching way (argmax of the match row; 0 on a miss)
    lane = jax.lax.broadcasted_iota(jnp.int32, row.shape, 1)
    first = jnp.min(jnp.where(match, lane, row.shape[1]))
    hit_ref[...] = jnp.full(hit_ref.shape, any_hit, jnp.int32)
    way_ref[...] = jnp.full(way_ref.shape, jnp.where(any_hit, first, 0),
                            jnp.int32)


def _search_kernel(sets_ref, vids_ref, tags_ref, hit_ref, way_ref):
    _probe(vids_ref[pl.program_id(0)], tags_ref, hit_ref, way_ref)


def _search_batched_kernel(sets_ref, vids_ref, tags_ref, hit_ref, way_ref,
                           *, n):
    _probe(vids_ref[pl.program_id(0) * n + pl.program_id(1)], tags_ref,
           hit_ref, way_ref)


# One set row and one result per grid step travel as [1, ways] / [1, 1]
# tiles of [nsets, 1, ways] / [n, 1, 1] arrays: a block's last two dims
# then equal the array's, which Mosaic accepts.
def _set_row(ways, index_map):
    return pl.BlockSpec((None, 1, ways), index_map)


def _result(index_map):
    return pl.BlockSpec((None, 1, 1), index_map)


def _results(n):
    return [jax.ShapeDtypeStruct((n, 1, 1), jnp.int32)] * 2


@jax.jit
def hec_search_batched(tags: jnp.ndarray, vids: jnp.ndarray):
    """Probe N rounds' vids against one tag array in a single grid.

    tags [nsets, ways] int32; vids [B, n] int32 (B = fused exchange
    rounds) -> (hit [B, n], set [B, n], way [B, n]).  Per-probe math is
    ``_search_kernel`` verbatim over a (B, n) grid, so each row of the
    output bit-matches a ``hec_search_kernel`` call on that round — one
    dispatch instead of B.
    """
    nsets, ways = tags.shape
    bsz, n = vids.shape
    flat = vids.reshape(-1).astype(jnp.int32)
    sets = set_index(flat, nsets)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(bsz, n),
        in_specs=[_set_row(ways, lambda b, i, s, v: (s[b * n + i], 0, 0))],
        out_specs=[_result(lambda b, i, s, v: (b * n + i, 0, 0))] * 2,
    )
    hit, way = pl.pallas_call(
        functools.partial(_search_batched_kernel, n=n),
        grid_spec=grid_spec,
        out_shape=_results(bsz * n),
        interpret=interpret_mode(),
    )(sets, flat, tags.reshape(nsets, 1, ways))
    return (hit.reshape(bsz, n) > 0, sets.reshape(bsz, n),
            way.reshape(bsz, n))


@jax.jit
def hec_probe(state, vids: jnp.ndarray):
    """Batched HECSearch + HECLoad: vids [B, n] -> (hit [B, n], emb [B, n, d]).

    Row-for-row bit-identical to ``hec.hec_lookup(state, vids[b])``: same
    set hash, same argmax-way (0 on miss), same stop_gradient load, same
    zeroed miss rows — pinned in tests/test_kernels.py and consumed by
    ``HaloExchangeEngine.cache_fetch(rounds=N)``.
    """
    hit, sets, way = hec_search_batched(state.tags, vids)
    emb = jax.lax.stop_gradient(state.values[sets, way])
    return hit, jnp.where(hit[..., None], emb, 0.0)


@jax.jit
def hec_search_kernel(tags: jnp.ndarray, vids: jnp.ndarray):
    """tags [nsets, ways] int32; vids [n] int32 -> (hit [n], set [n], way [n])."""
    nsets, ways = tags.shape
    n = vids.shape[0]
    sets = set_index(vids, nsets)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(n,),
        in_specs=[_set_row(ways, lambda i, s, v: (s[i], 0, 0))],
        out_specs=[_result(lambda i, s, v: (i, 0, 0))] * 2,
    )
    hit, way = pl.pallas_call(
        _search_kernel,
        grid_spec=grid_spec,
        out_shape=_results(n),
        interpret=interpret_mode(),
    )(sets, vids.astype(jnp.int32), tags.reshape(nsets, 1, ways))
    return hit.reshape(n) > 0, sets, way.reshape(n)
