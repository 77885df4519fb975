"""GAT (the paper's eq. 2, with its change: bias and ReLU before attention)
for the plain reference and the FLOP count:

    z_u = ReLU(W h_u + b);  alpha_uv = softmax_u(LeakyReLU(a_u.z_u + a_v.z_v))
    h_v = sum_u alpha_uv z_u

``num_heads`` heads on each hidden layer, concatenated; one head on the
class layer.  The ReLU is inside the block, so ``between`` is the
identity.  Weights: ``W`` normal scaled by sqrt(2 / fan_in), zero biases,
attention vectors scaled by dh^-0.5, under the program's leaf names,
drawn in the program's order.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def _widths(m):
    """Per layer: input width, heads, width of one head."""
    L = m.num_layers
    d_in = [m.feat_dim] + [m.hidden * m.num_heads] * (L - 1)
    heads = [m.num_heads] * (L - 1) + [1]
    d_out = [m.hidden] * (L - 1) + [m.num_classes]
    return d_in, heads, d_out


def init_params(key, m):
    d_in, heads, d_out = _widths(m)
    layers = []
    for l in range(m.num_layers):
        k1, k2, k3, key = jax.random.split(key, 4)
        s = (2.0 / d_in[l]) ** 0.5
        H, dh = heads[l], d_out[l]
        layers.append({
            "w": jax.random.normal(k1, (d_in[l], H, dh)) * s,
            "b": jnp.zeros((H, dh)),
            "a_u": jax.random.normal(k2, (H, dh)) * dh ** -0.5,
            "a_v": jax.random.normal(k3, (H, dh)) * dh ** -0.5})
    return {"layers": layers}


def block(p, h, valid, nbr, self_rows, m):
    idx = jnp.maximum(nbr, 0)
    z_src = jax.nn.relu(jnp.einsum("nfd,dhe->nfhe", h[idx], p["w"]) + p["b"])
    z_dst = jax.nn.relu(jnp.einsum("nd,dhe->nhe", h[self_rows], p["w"])
                        + p["b"])
    e_u = (z_src * p["a_u"]).sum(-1)                     # [n, f, H]
    e_v = (z_dst * p["a_v"]).sum(-1)                     # [n, H]
    mask = (nbr >= 0) & valid[idx]
    s = jax.nn.leaky_relu(e_u + e_v[:, None, :], 0.2)
    s = jnp.where(mask[..., None], s, jnp.asarray(-1e30, s.dtype))
    alpha = jnp.where(mask[..., None], jax.nn.softmax(s, axis=1), 0)
    out = jnp.einsum("nfh,nfhe->nhe", alpha, z_src)
    return out.reshape(out.shape[0], -1)


def between(h, m):
    return h


def cache_dims(m):
    return _widths(m)[0]


def forward_flops(m, caps):
    """``W`` on every source row, the two score dot products on every
    source row, and the weighted sum over each destination row's
    neighbours; softmax and the elementwise work are not counted."""
    d_in, heads, d_out = _widths(m)
    parts = {"projection": 0, "scores": 0, "aggregation": 0}
    for k in range(m.num_layers):
        width = heads[k] * d_out[k]
        parts["projection"] += caps[k] * d_in[k] * width
        parts["scores"] += 2 * caps[k] * width              # e_u, e_v
        parts["aggregation"] += caps[k + 1] * m.fanouts[k] * width
    return parts
