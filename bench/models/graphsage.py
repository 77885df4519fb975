"""GraphSAGE (the paper's eq. 1) for the plain reference and the FLOP count:

    h_v = Dropout(ReLU(W_n mean_{u in N(v)} h_u + W_s h_v + b))

``block`` is the affine part, ``between`` the ReLU of a hidden layer (the
shared reference applies dropout after it).  Weights: normal scaled by
sqrt(2 / fan_in) and zero biases, under the program's leaf names, drawn
in the program's order.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def _dims(m):
    return [m.feat_dim] + [m.hidden] * (m.num_layers - 1) + [m.num_classes]


def init_params(key, m):
    dims = _dims(m)
    layers = []
    for l in range(m.num_layers):
        k1, k2, key = jax.random.split(key, 3)
        s = (2.0 / dims[l]) ** 0.5
        layers.append({
            "wn": jax.random.normal(k1, (dims[l], dims[l + 1])) * s,
            "ws": jax.random.normal(k2, (dims[l], dims[l + 1])) * s,
            "b": jnp.zeros((dims[l + 1],))})
    return {"layers": layers}


def block(p, h, valid, nbr, self_rows, m):
    idx = jnp.maximum(nbr, 0)
    mask = ((nbr >= 0) & valid[idx]).astype(h.dtype)[..., None]
    agg = (h[idx] * mask).sum(1) / jnp.maximum(mask.sum(1), 1)
    return agg @ p["wn"] + h[self_rows] @ p["ws"] + p["b"]


def between(h, m):
    return jax.nn.relu(h)


def cache_dims(m):
    return _dims(m)[:-1]


def forward_flops(m, caps):
    """``W_n`` and ``W_s`` on each destination row; the mean's adds are
    not counted."""
    dims = _dims(m)
    return {"projection": sum(2 * caps[k + 1] * dims[k] * dims[k + 1]
                              for k in range(m.num_layers))}
