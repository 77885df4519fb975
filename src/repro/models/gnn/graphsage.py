"""GraphSAGE (paper eq. 1):

    h^l_N(v) = mean({ f_u^{l-1} | u in N(v) })
    h^l_v    = Dropout(ReLU(W_n h^l_N(v) + W_s h^l_v + b))

The UPDATE (two matmuls + bias + ReLU + Dropout) is exactly the operator
the paper fuses via LIBXSMM; our Pallas analogue lives in
kernels/update_fused.py and computes the same function (same hash-dropout
mask).  The model calls the jnp path by default and the kernel path when
``use_kernel=True`` (validated against each other in tests).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.models.gnn.common import (gather_neighbors, hash_dropout,
                                     masked_mean)


def init_params(key, feat_dim: int, hidden: int, num_classes: int,
                num_layers: int):
    """num_layers GNN layers: feat -> hidden x (L-1) -> classes."""
    dims = [feat_dim] + [hidden] * (num_layers - 1) + [num_classes]
    layers = []
    for l in range(num_layers):
        k1, k2, key = jax.random.split(key, 3)
        din, dout = dims[l], dims[l + 1]
        s = (2.0 / din) ** 0.5
        layers.append({
            "wn": jax.random.normal(k1, (din, dout), jnp.float32) * s,
            "ws": jax.random.normal(k2, (din, dout), jnp.float32) * s,
            "b": jnp.zeros((dout,), jnp.float32),
        })
    return {"layers": layers}


def update(p, agg, self_h, *, relu: bool, dropout: float, seed,
           use_kernel: bool = False):
    if use_kernel:
        from repro.kernels import ops as kops
        return kops.fused_update(agg, self_h, p["wn"], p["ws"], p["b"],
                                 relu=relu, dropout=dropout, seed=seed)
    out = agg @ p["wn"] + self_h @ p["ws"] + p["b"]
    if relu:
        out = jax.nn.relu(out)
    if dropout > 0:
        out = hash_dropout(out, dropout, seed)
    return out


def forward(params, h0, valid0, blocks, *, dropout: float = 0.0,
            seed=None, halo_hook=None, use_kernel: bool = False):
    """h0: [N_0, F] input-layer features; valid0: [N_0] bool.

    blocks: MinibatchBlocks-like dict with nbr_idx list (device arrays).
    halo_hook(k, h, valid) -> (h, valid): substitutes HEC embeddings for
    halo rows after layer k is computed (k=0 substitutes input features).
    Returns (h_final [B, C], valid [B]).
    """
    seed = jnp.uint32(0) if seed is None else seed
    h, valid = h0, valid0
    if halo_hook is not None:
        h, valid = halo_hook(0, h, valid)
    L = len(params["layers"])
    for k in range(L):
        nbr = blocks["nbr_idx"][k]
        with jax.named_scope(f"layer{k}_aggregate"):
            feats, mask = gather_neighbors(h, nbr, valid)
            agg = masked_mean(feats, mask)
        n_dst = nbr.shape[0]
        self_h = h[:n_dst]
        last = k == L - 1
        with jax.named_scope(f"layer{k}_update"):
            h_new = update(params["layers"][k], agg, self_h,
                           relu=not last, dropout=0.0 if last else dropout,
                           seed=seed + jnp.uint32(k + 1),
                           use_kernel=use_kernel)
        valid = valid[:n_dst]
        if halo_hook is not None and not last:
            h_new, valid = halo_hook(k + 1, h_new, valid)
        h = h_new
    return h, valid
