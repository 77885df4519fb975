"""Fused UPDATE Pallas kernel (paper §3.3 "UPDATE Optimizations").

The paper fuses GraphSAGE's UPDATE — two matmuls + bias + ReLU + Dropout —
with LIBXSMM TPPs, blocking in[N][C] -> in[nn][bn][nc][bc] so intermediate
tiles stay in L2.  The TPU translation of the same insight:

  * grid over (N/bn, K/bk) output tiles; both matmuls accumulate into ONE
    fp32 VMEM tile (the MXU-aligned analogue of the 4-D blocking),
  * bias + ReLU + Dropout are applied to that resident tile before the
    single store to HBM — the elementwise tail never round-trips memory,
  * dropout uses the same position-hash as the jnp reference, so kernel
    and reference agree bit-for-bit given the same seed.

Block sizes default to (bn, bk) = (256, 128): MXU wants multiples of 128
on the contracting/lane dims; remainder handling pads N (dims C,K of the
GNN are already 128-multiples in the paper's configs: 100..256).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import interpret_mode

_MIX1 = np.uint32(0x85EBCA6B)
_MIX2 = np.uint32(0xC2B2AE35)


def _update_kernel(agg_ref, self_ref, wn_ref, ws_ref, b_ref, seed_ref,
                   out_ref, *, relu: bool, dropout: float, bn: int, bk: int):
    i = pl.program_id(0)
    j = pl.program_id(1)
    acc = jnp.dot(agg_ref[...], wn_ref[...],
                  preferred_element_type=jnp.float32)
    acc += jnp.dot(self_ref[...], ws_ref[...],
                   preferred_element_type=jnp.float32)
    acc += b_ref[...].astype(jnp.float32)             # [1, bk] row
    if relu:
        acc = jnp.maximum(acc, 0.0)
    if dropout > 0.0:
        rows = (i * bn + jax.lax.broadcasted_iota(jnp.int32, (bn, bk), 0)
                ).astype(jnp.uint32)
        cols = (j * bk + jax.lax.broadcasted_iota(jnp.int32, (bn, bk), 1)
                ).astype(jnp.uint32)
        h = (rows * _MIX1) ^ (cols * _MIX2) ^ seed_ref[0]
        h = h ^ (h >> np.uint32(15))
        h = h * _MIX1
        h = h ^ (h >> np.uint32(13))
        # the top 24 bits fit int32 exactly; Mosaic has no u32 -> f32 cast
        u = (h >> np.uint32(8)).astype(jnp.int32).astype(jnp.float32) \
            / np.float32(1 << 24)
        acc = jnp.where(u >= jnp.float32(dropout),
                        acc / jnp.float32(1.0 - dropout), 0.0)
    out_ref[...] = acc.astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("relu", "dropout", "bn", "bk"))
def fused_update(agg, self_h, wn, ws, b, *, relu=True, dropout=0.0,
                 seed=jnp.uint32(0), bn=256, bk=128):
    """agg, self_h: [N, C]; wn, ws: [C, K]; b: [K] -> [N, K] float32."""
    N, C = agg.shape
    K = wn.shape[1]
    bn = min(bn, N)
    bk = min(bk, K)
    pad_n = (-N) % bn
    pad_k = (-K) % bk
    if pad_n:
        agg = jnp.pad(agg, ((0, pad_n), (0, 0)))
        self_h = jnp.pad(self_h, ((0, pad_n), (0, 0)))
    if pad_k:
        wn = jnp.pad(wn, ((0, 0), (0, pad_k)))
        ws = jnp.pad(ws, ((0, 0), (0, pad_k)))
        b = jnp.pad(b, (0, pad_k))
    Np, Kp = N + pad_n, K + pad_k
    seed_arr = jnp.asarray([seed], jnp.uint32)

    out = pl.pallas_call(
        functools.partial(_update_kernel, relu=relu, dropout=float(dropout),
                          bn=bn, bk=bk),
        grid=(Np // bn, Kp // bk),
        in_specs=[
            pl.BlockSpec((bn, C), lambda i, j: (i, 0)),
            pl.BlockSpec((bn, C), lambda i, j: (i, 0)),
            pl.BlockSpec((C, bk), lambda i, j: (0, j)),
            pl.BlockSpec((C, bk), lambda i, j: (0, j)),
            pl.BlockSpec((1, bk), lambda i, j: (0, j)),
            pl.BlockSpec(memory_space=pltpu.SMEM),
        ],
        out_specs=pl.BlockSpec((bn, bk), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((Np, Kp), jnp.float32),
        interpret=interpret_mode(),
    )(agg, self_h, wn, ws, b.reshape(1, Kp), seed_arr)
    return out[:N, :K]
