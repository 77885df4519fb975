"""GAT (paper eq. 2, with the paper's modification: bias + non-linearity
applied to the projection BEFORE computing attention coefficients):

    z_u = ReLU(W f_u + b)
    e_u = a_u . z_u ;  e_v = a_v . z_v
    alpha_uv = EdgeSoftmax(LeakyReLU(e_u + e_v))
    h_v = sum_u alpha_uv z_u

The per-head broadcast edge-softmax aggregation is the operation the paper
adds SIMD broadcast support for (LIBXSMM); the Pallas analogue is
kernels/gat_edge.py.

Each layer picks the order of projection and neighbour gather from its
widths (``gat_layer``).  Where the input is narrower than the projection
(``din < H*dh``) it gathers each sampled edge's input and projects it there;
otherwise it projects every source row once and gathers the projection.
The projection acts row by row, so both orders give the same ``z`` per
edge.  On the paper's GAT only layer 0 gathers first (128-wide features
against 4 heads x 256): its input is the minibatch's features, which need
no gradient, so no scatter-add of per-edge gradients back into source rows
is left in its backward.  The hidden layers project first: layer 1's input
is as wide as its projection, and layer 2's is wider.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.models.gnn.common import hash_dropout


def init_params(key, feat_dim: int, hidden: int, num_classes: int,
                num_layers: int, num_heads: int):
    layers = []
    dims_in = [feat_dim] + [hidden * num_heads] * (num_layers - 1)
    dims_out = [hidden] * (num_layers - 1) + [num_classes]
    heads = [num_heads] * (num_layers - 1) + [1]
    for l in range(num_layers):
        k1, k2, k3, key = jax.random.split(key, 4)
        din, dh, H = dims_in[l], dims_out[l], heads[l]
        s = (2.0 / din) ** 0.5
        layers.append({
            "w": jax.random.normal(k1, (din, H, dh), jnp.float32) * s,
            "b": jnp.zeros((H, dh), jnp.float32),
            "a_u": jax.random.normal(k2, (H, dh), jnp.float32) * dh ** -0.5,
            "a_v": jax.random.normal(k3, (H, dh), jnp.float32) * dh ** -0.5,
        })
    return {"layers": layers}


def gat_layer(p, h_src, nbr_idx, valid, *, layer: int, use_kernel=False):
    """h_src [N_src, din] -> h_dst [N_dst, H*dh] (pre-dropout); ``layer``
    names its scopes.  Destination rows are the prefix of the source rows.

    Order, from the widths: where ``din < H*dh`` the layer gathers each
    edge's ``h_src[idx]`` and projects it (and the destination rows) under
    ``layer{k}_update``, so the gather, and any scatter-add in its
    backward, runs at the narrower width; an input that needs no gradient
    (layer 0's features) leaves no scatter at all.  Otherwise, and always
    with ``use_kernel``, it projects all ``N_src`` rows and gathers ``z``.

    Inside ``layer{k}_aggregate`` the attention's parts are scoped as
    ``edge_scores`` (e_u/e_v, LeakyReLU, mask), ``edge_softmax`` and
    ``edge_gather_sum`` (the weighted sum, and the gather ``z[idx]`` where
    the layer projects first)."""
    n_dst = nbr_idx.shape[0]
    H, dh = p["b"].shape
    idx = jnp.maximum(nbr_idx, 0)

    def project(x):
        return jax.nn.relu(jnp.einsum("...d,dhe->...he", x, p["w"]) + p["b"])

    if not use_kernel and h_src.shape[1] < H * dh:
        with jax.named_scope(f"layer{layer}_update"):
            z_e = project(h_src[idx])                      # [N_dst, f, H, dh]
            z_v = project(h_src[:n_dst])                   # [N_dst, H, dh]
        with jax.named_scope(f"layer{layer}_aggregate"):
            with jax.named_scope("edge_scores"):
                e_u = (z_e * p["a_u"]).sum(-1)             # [N_dst, f, H]
                e_v = (z_v * p["a_v"]).sum(-1)             # [N_dst, H]
            h = _attend(e_u, e_v, z_e, nbr_idx, valid)
        return h.reshape(n_dst, -1)

    with jax.named_scope(f"layer{layer}_update"):
        z = project(h_src)                                 # [N_src, H, dh]
    with jax.named_scope(f"layer{layer}_aggregate"):
        with jax.named_scope("edge_scores"):
            e_u = (z * p["a_u"]).sum(-1)                   # [N_src, H]
            e_v = (z * p["a_v"]).sum(-1)
        if use_kernel:
            from repro.kernels import ops as kops
            h = kops.gat_edge_aggregate(z, e_u, e_v, nbr_idx, valid)
        else:
            with jax.named_scope("edge_scores"):
                e_u = e_u[idx]                             # [N_dst, f, H]
            with jax.named_scope("edge_gather_sum"):
                z_e = z[idx]                               # [N_dst, f, H, dh]
            h = _attend(e_u, e_v[:n_dst], z_e, nbr_idx, valid)
    return h.reshape(n_dst, -1)


def _attend(e_u, e_v, z_e, nbr_idx, valid):
    """Per-edge scores e_u [N_dst, f, H] and e_v [N_dst, H] -> the softmax
    over each destination's valid slots, weighting z_e [N_dst, f, H, dh]
    into [N_dst, H, dh]; a destination with no valid slot gets zeros."""
    with jax.named_scope("edge_scores"):
        mask = (nbr_idx >= 0) & valid[jnp.maximum(nbr_idx, 0)]  # [N_dst, f]
        scores = jax.nn.leaky_relu(e_u + e_v[:, None, :], 0.2)
        scores = jnp.where(mask[..., None], scores, -1e30)
    with jax.named_scope("edge_softmax"):
        alpha = jax.nn.softmax(scores, axis=1)
        alpha = jnp.where(mask[..., None], alpha, 0.0)
    with jax.named_scope("edge_gather_sum"):
        return jnp.einsum("nfh,nfhe->nhe", alpha, z_e)


def forward(params, h0, valid0, blocks, *, dropout: float = 0.0,
            seed=None, halo_hook=None, use_kernel: bool = False):
    seed = jnp.uint32(0) if seed is None else seed
    h, valid = h0, valid0
    if halo_hook is not None:
        h, valid = halo_hook(0, h, valid)
    L = len(params["layers"])
    for k in range(L):
        nbr = blocks["nbr_idx"][k]
        h_new = gat_layer(params["layers"][k], h, nbr, valid,
                          use_kernel=use_kernel, layer=k)
        last = k == L - 1
        if not last and dropout > 0:
            h_new = hash_dropout(h_new, dropout, seed + jnp.uint32(k + 1))
        valid = valid[:nbr.shape[0]]
        if halo_hook is not None and not last:
            h_new, valid = halo_hook(k + 1, h_new, valid)
        h = h_new
    return h, valid
