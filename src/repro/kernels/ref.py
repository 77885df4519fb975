"""Pure-jnp oracles for every Pallas kernel (the allclose ground truth).

Also home of the ONE composed serve-layer reference (``serve_layer_ref``):
the offline chunk engines and the fused serve kernel's parity tests all
call this function, so the "composed jnp serve layer" can never drift
across the three places that used to spell it out independently.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.models.gnn.common import (gather_neighbors, hash_uniform,
                                     masked_mean)


def fused_update_ref(agg, self_h, wn, ws, b, *, relu=True, dropout=0.0,
                     seed=jnp.uint32(0)):
    """dropout(ReLU(agg@Wn + self@Ws + b)) — paper eq. 1 UPDATE."""
    out = (agg.astype(jnp.float32) @ wn.astype(jnp.float32)
           + self_h.astype(jnp.float32) @ ws.astype(jnp.float32)
           + b.astype(jnp.float32))
    if relu:
        out = jax.nn.relu(out)
    if dropout > 0.0:
        u = hash_uniform(seed, jnp.arange(out.shape[0], dtype=jnp.int32),
                         jnp.arange(out.shape[1], dtype=jnp.int32))
        out = jnp.where(u >= dropout, out / (1.0 - dropout), 0.0)
    return out


def sage_agg_ref(h_src, nbr_idx, src_valid):
    """Masked mean over sampled neighbors. h_src [N,D]; nbr_idx [M,f]."""
    idx = jnp.maximum(nbr_idx, 0)
    mask = (nbr_idx >= 0) & src_valid[idx]
    feats = h_src[idx] * mask[..., None]
    cnt = mask.sum(axis=1, keepdims=True).astype(h_src.dtype)
    return feats.sum(axis=1) / jnp.maximum(cnt, 1.0)


def serve_layer_ref(p, h_src, nbr_idx, src_valid, self_h=None, *, relu=True):
    """The composed jnp serve layer: gather + masked mean + UPDATE.

    Single source of truth for the serve-path layer math — the online
    schedulers' non-fused path, the offline chunk engines, and the
    ``serve_fused`` parity tests all funnel through this exact op
    sequence (serving always runs with dropout off).

    p         layer param dict with "wn" [D,K], "ws" [D,K], "b" [K]
    h_src     [N, D] source activations
    nbr_idx   [M, f] neighbor rows into h_src, -1 = padded/absent
    src_valid [N]    bool validity of each source row
    self_h    [M, D] self activations (default: ``h_src[:M]`` prefix)
    """
    from repro.models.gnn import graphsage as sage_lib

    feats, mask = gather_neighbors(h_src, nbr_idx, src_valid)
    agg = masked_mean(feats, mask)
    if self_h is None:
        self_h = h_src[: nbr_idx.shape[0]]
    return sage_lib.update(p, agg, self_h, relu=relu, dropout=0.0,
                           seed=jnp.uint32(0))


def gat_edge_ref(z, e_u, e_v, nbr_idx, src_valid):
    """Edge-softmax broadcast aggregation (paper eq. 2 AGG).

    z [N_src, H, dh]; e_u [N_src, H]; e_v [N_dst, H]; nbr_idx [N_dst, f].
    Returns [N_dst, H, dh].
    """
    n_dst = nbr_idx.shape[0]
    idx = jnp.maximum(nbr_idx, 0)
    mask = (nbr_idx >= 0) & src_valid[idx]
    scores = jax.nn.leaky_relu(e_u[idx] + e_v[:n_dst, None, :], 0.2)
    scores = jnp.where(mask[..., None], scores, -1e30)
    alpha = jax.nn.softmax(scores, axis=1)
    alpha = jnp.where(mask[..., None], alpha, 0.0)
    return jnp.einsum("nfh,nfhe->nhe", alpha, z[idx])
