"""GAT edge-softmax broadcast-aggregation Pallas kernel (paper §3.3,
"Broadcast Support for AGG").

In GAT the per-head attention coefficient alpha[n, f, h] multiplies the
whole z[n, f, h, :] head vector — DGL's scalar loop broadcasts each alpha
head_dim times; the paper adds a LIBXSMM SIMD-broadcast primitive.  The
VPU-native version keeps the [bm, f, H] score tile resident in VMEM,
computes LeakyReLU + edge-softmax there, and applies the broadcast multiply
+ fanout reduction against the [bm, f, H*dh] neighbor tile in one pass —
the alpha tile never round-trips HBM.

Neighbor tensors arrive pre-gathered (XLA gather); the kernel fuses the
whole edge-softmax + weighted-sum epilogue.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import interpret_mode


def _gat_kernel(s_ref, z_ref, mask_ref, out_ref, *, heads: int):
    scores = s_ref[...].astype(jnp.float32)       # [bm, f, H]
    m = mask_ref[...] > 0                         # [bm, f, H]
    scores = jnp.where(scores >= 0, scores, 0.2 * scores)   # LeakyReLU(0.2)
    scores = jnp.where(m, scores, -1e30)
    smax = scores.max(axis=1, keepdims=True)
    p = jnp.exp(scores - smax)
    p = jnp.where(m, p, 0.0)
    alpha = p / jnp.maximum(p.sum(axis=1, keepdims=True), 1e-20)  # [bm,f,H]
    dh = z_ref.shape[-1] // heads
    for h in range(heads):      # per head: broadcast over dh, reduce f
        cols = slice(h * dh, (h + 1) * dh)
        zh = z_ref[:, :, cols].astype(jnp.float32)            # [bm, f, dh]
        out = (alpha[:, :, h:h + 1] * zh).sum(axis=1)         # [bm, dh]
        out_ref[:, cols] = out.astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("heads", "bm"))
def gat_edge(eu_nbr, ev, z_nbr, mask, *, heads: int, bm: int = 64):
    """eu_nbr [M,f,H]; ev [M,H]; z_nbr [M,f,H*dh]; mask [M,f] -> [M,H*dh]."""
    M, f, H = eu_nbr.shape
    HD = z_nbr.shape[-1]
    # the edge scores and the head-broadcast mask are formed here: Mosaic
    # cannot insert a unit dim into a vector inside the kernel
    scores = eu_nbr.astype(jnp.float32) + ev.astype(jnp.float32)[:, None, :]
    mask = jnp.broadcast_to(mask[..., None], (M, f, H)).astype(jnp.int32)
    bm = min(bm, M)
    pad = (-M) % bm
    if pad:
        scores = jnp.pad(scores, ((0, pad), (0, 0), (0, 0)))
        z_nbr = jnp.pad(z_nbr, ((0, pad), (0, 0), (0, 0)))
        mask = jnp.pad(mask, ((0, pad), (0, 0), (0, 0)))
    Mp = M + pad
    out = pl.pallas_call(
        functools.partial(_gat_kernel, heads=heads),
        grid=(Mp // bm,),
        in_specs=[
            pl.BlockSpec((bm, f, H), lambda i: (i, 0, 0)),
            pl.BlockSpec((bm, f, HD), lambda i: (i, 0, 0)),
            pl.BlockSpec((bm, f, H), lambda i: (i, 0, 0)),
        ],
        out_specs=pl.BlockSpec((bm, HD), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((Mp, HD), jnp.float32),
        interpret=interpret_mode(),
    )(scores, z_nbr, mask)
    return out[:M]
