"""SAGE neighbor-aggregation Pallas kernel (paper's AGG primitive).

AGG is the memory-bound half of GNN training (paper §3: byte-to-op >> 1).
On CPU the paper leans on LIBXSMM gather/scatter primitives; the TPU-native
shape of the same computation is a *scalar-prefetch gather-accumulate*:

  * ``nbr_idx`` rides in SMEM (PrefetchScalarGridSpec) so the BlockSpec
    index_map can route each grid step's DMA to an arbitrary source row —
    the Pallas equivalent of an indexed gather from HBM,
  * grid = (N_dst, fanout); the output tile for dst row i is revisited
    fanout times and accumulated in VMEM, with the mean finalized by the
    (cheap) division outside,
  * SMEM holds 1 MiB, so wide layers run as a ``lax.map`` over chunks of
    dst rows, one kernel call per chunk.

Masked entries (idx < 0, or invalid source rows) contribute zero.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import interpret_mode


def _agg_kernel(idx_ref, h_ref, sum_ref, cnt_ref, *, f: int):
    j = pl.program_id(1)
    ok = (idx_ref[pl.program_id(0) * f + j] >= 0).astype(jnp.float32)

    @pl.when(j == 0)
    def _init():
        sum_ref[...] = jnp.zeros_like(sum_ref)
        cnt_ref[...] = jnp.zeros_like(cnt_ref)

    sum_ref[...] += h_ref[...].astype(jnp.float32) * ok
    cnt_ref[...] += ok


# neighbor slots per kernel call: the index table rides in SMEM (1 MiB on
# v5e), so a wide layer is aggregated in chunks of at most this many
_SMEM_SLOTS = 1 << 16


@jax.jit
def sage_agg(h_src, nbr_idx, src_valid):
    """h_src [N, D]; nbr_idx [M, f] (-1 pad); src_valid [N] bool -> [M, D]."""
    N, D = h_src.shape
    M, f = nbr_idx.shape
    # invalid sources fold into the index, so one table rides in SMEM
    idx = jnp.where(src_valid[jnp.maximum(nbr_idx, 0)], nbr_idx, -1)
    rows = max(1, min(M, _SMEM_SLOTS // f))      # dst rows per chunk
    pad = (-M) % rows
    idx = jnp.pad(idx, ((0, pad), (0, 0)), constant_values=-1)
    # rows travel as [1, D] tiles of [N, 1, D]: a block's last two dims
    # then equal the array's, which Mosaic accepts for any D
    row = lambda r: pl.BlockSpec((None, 1, D), r)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(rows, f),
        in_specs=[row(lambda i, j, ix: (jnp.maximum(ix[i * f + j], 0), 0, 0))],
        out_specs=[row(lambda i, j, ix: (i, 0, 0)),
                   pl.BlockSpec((None, 1, 1), lambda i, j, ix: (i, 0, 0))],
    )
    agg = pl.pallas_call(
        functools.partial(_agg_kernel, f=f),
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((rows, 1, D), jnp.float32),
                   jax.ShapeDtypeStruct((rows, 1, 1), jnp.float32)],
        interpret=interpret_mode(),
    )
    h3 = h_src.reshape(N, 1, D)
    s, c = jax.lax.map(lambda ix: agg(ix, h3),
                       idx.reshape(-1, rows * f).astype(jnp.int32))
    s, c = s.reshape(-1, D)[:M], c.reshape(-1, 1)[:M]
    return (s / jnp.maximum(c, 1.0)).astype(h_src.dtype)
