"""Plain float32 reference of the paper's training step (GraphSAGE, GAT).

Written from the paper's equations, not imported from the program:

    GraphSAGE (eq. 1)  h_v = Dropout(ReLU(W_n mean_{u in N(v)} h_u + W_s h_v + b))
    GAT (eq. 2, with the paper's change: bias and ReLU before attention)
        z_u = ReLU(W h_u + b);  alpha_uv = softmax_u(LeakyReLU(a_u.z_u + a_v.z_v))
        h_v = sum_u alpha_uv z_u

then softmax cross-entropy over the seed rows, the gradient of the
example-weighted mean over every rank's batch, and Adam with the gradient
clipped to global norm 1.  Matrix products run at the precision the
configuration states: float32 at the backend's default precision (on the
TPU one bfloat16 pass of the inputs with float32 sums).

The weights come from the reference's own initialisation (the published
init: normal scaled by sqrt(2 / fan_in), zero biases, attention vectors
scaled by dh^-0.5), drawn from ``--seed`` with ``jax.random`` in the same
order as the program draws them.  Dropout is the position-hash mask the
paper's fused UPDATE uses, so a given step drops the same units.

A rank's halo rows take the historical embedding the reference's own
cache holds for them (:class:`RefCache`), filled by the reference's own
pushes: each owner pushes the rows it computed, chosen by the push rule
(up to ``nc`` random solids per peer), and a push lands ``delay`` steps
later.  ``precision="bfloat16"`` runs the same step one precision lower,
the control the comparison has to reject: weights, features, activations
and gradients in bfloat16, Adam and the master weights in float32.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Dict, List

import jax
import jax.numpy as jnp
import numpy as np

_MIX1 = np.uint32(0x85EBCA6B)
_MIX2 = np.uint32(0xC2B2AE35)


@dataclasses.dataclass(frozen=True)
class Model:
    """The sizes the reference needs, from the configuration file."""
    model: str
    feat_dim: int
    hidden: int
    num_classes: int
    num_layers: int
    num_heads: int
    fanouts: tuple
    batch_size: int
    dropout: float
    lr: float

    @classmethod
    def from_config(cls, c: dict) -> "Model":
        return cls(model=c["model"], feat_dim=c["feat_dim"],
                   hidden=c["hidden_size"], num_classes=c["num_classes"],
                   num_layers=c["num_hidden_layers"] + 1,
                   num_heads=c["num_heads"], fanouts=tuple(c["fanouts"]),
                   batch_size=c["batch_size"], dropout=c["dropout"],
                   lr=c["lr"])

    def cache_dims(self) -> List[int]:
        """Width of the embedding a halo row takes at each layer's input."""
        hid = self.hidden * (self.num_heads if self.model == "gat" else 1)
        return [self.feat_dim] + [hid] * (self.num_layers - 1)


# ---------------------------------------------------------------------------
# weights
# ---------------------------------------------------------------------------
def init_params(key, m: Model):
    L = m.num_layers
    layers = []
    if m.model == "graphsage":
        dims = [m.feat_dim] + [m.hidden] * (L - 1) + [m.num_classes]
        for l in range(L):
            k1, k2, key = jax.random.split(key, 3)
            s = (2.0 / dims[l]) ** 0.5
            layers.append({
                "wn": jax.random.normal(k1, (dims[l], dims[l + 1])) * s,
                "ws": jax.random.normal(k2, (dims[l], dims[l + 1])) * s,
                "b": jnp.zeros((dims[l + 1],))})
    else:
        d_in = [m.feat_dim] + [m.hidden * m.num_heads] * (L - 1)
        d_out = [m.hidden] * (L - 1) + [m.num_classes]
        heads = [m.num_heads] * (L - 1) + [1]
        for l in range(L):
            k1, k2, k3, key = jax.random.split(key, 4)
            s = (2.0 / d_in[l]) ** 0.5
            H, dh = heads[l], d_out[l]
            layers.append({
                "w": jax.random.normal(k1, (d_in[l], H, dh)) * s,
                "b": jnp.zeros((H, dh)),
                "a_u": jax.random.normal(k2, (H, dh)) * dh ** -0.5,
                "a_v": jax.random.normal(k3, (H, dh)) * dh ** -0.5})
    return {"layers": layers}


# ---------------------------------------------------------------------------
# one rank's forward, loss and gradient
# ---------------------------------------------------------------------------
def _keep_mask(seed, rows: int, cols: int, rate: float):
    """Position-hash dropout mask: keep where hash(seed, row, col) >= rate."""
    r = jnp.arange(rows, dtype=jnp.uint32)[:, None]
    c = jnp.arange(cols, dtype=jnp.uint32)[None, :]
    h = (r * _MIX1) ^ (c * _MIX2) ^ seed.astype(jnp.uint32)
    h = h ^ (h >> 15)
    h = h * _MIX1
    h = h ^ (h >> 13)
    u = (h >> 8).astype(jnp.float32) / jnp.float32(1 << 24)
    return u >= rate


def _dropout(x, rate, seed):
    if rate <= 0:
        return x
    keep = _keep_mask(seed, x.shape[0], x.shape[1], rate)
    return jnp.where(keep, x / jnp.asarray(1.0 - rate, x.dtype),
                     jnp.zeros((), x.dtype))


BLOCK = 8000        # destination rows per block of a layer


def _sage_block(p, h, valid, nbr, self_rows):
    idx = jnp.maximum(nbr, 0)
    m = ((nbr >= 0) & valid[idx]).astype(h.dtype)[..., None]
    agg = (h[idx] * m).sum(1) / jnp.maximum(m.sum(1), 1)
    return agg @ p["wn"] + h[self_rows] @ p["ws"] + p["b"]


def _gat_block(p, h, valid, nbr, self_rows):
    idx = jnp.maximum(nbr, 0)
    z_src = jax.nn.relu(jnp.einsum("nfd,dhe->nfhe", h[idx], p["w"]) + p["b"])
    z_dst = jax.nn.relu(jnp.einsum("nd,dhe->nhe", h[self_rows], p["w"])
                        + p["b"])
    e_u = (z_src * p["a_u"]).sum(-1)                     # [n, f, H]
    e_v = (z_dst * p["a_v"]).sum(-1)                     # [n, H]
    m = (nbr >= 0) & valid[idx]
    s = jax.nn.leaky_relu(e_u + e_v[:, None, :], 0.2)
    s = jnp.where(m[..., None], s, jnp.asarray(-1e30, s.dtype))
    alpha = jnp.where(m[..., None], jax.nn.softmax(s, axis=1), 0)
    out = jnp.einsum("nfh,nfhe->nhe", alpha, z_src)
    return out.reshape(out.shape[0], -1)


def _layer(block_fn, p, h, valid, nbr):
    """One layer over its destination rows, in blocks of ``BLOCK`` rows
    (each block recomputed in the backward pass), so that the reference
    fits beside nothing else on the chip at the timed sizes.  A source
    row is projected where it is gathered; the sums are the same."""
    n_dst = nbr.shape[0]
    blk = min(n_dst, BLOCK)
    nb = -(-n_dst // blk)
    pad = nb * blk - n_dst
    nbr_b = jnp.pad(nbr, ((0, pad), (0, 0)), constant_values=-1)
    rows = jnp.minimum(jnp.arange(nb * blk), n_dst - 1)
    body = jax.checkpoint(lambda xs: block_fn(p, h, valid, *xs))
    out = jax.lax.map(body, (nbr_b.reshape(nb, blk, -1),
                             rows.reshape(nb, blk)))
    return out.reshape(nb * blk, -1)[:n_dst]


@functools.partial(jax.jit, static_argnames=("m", "precision"))
def rank_step(params, h0, valid0, nbr_idx, subs, labels, seed_mask, seed,
              push_pos, *, m: Model, precision: str = "float32"):
    """One rank's part of a step.

    h0 [N0, F], valid0 [N0]: layer-0 rows after halo substitution;
    nbr_idx: per layer [n_dst, f] positions into the layer's input rows;
    subs: per layer k >= 1, (is_halo, use [n_k] bool, emb [n_k, d]): the
    halo rows at layer k's input, and those the cache serves (the others
    drop out of aggregation); push_pos [P] positions of the rows
    this rank pushes.  Returns (nll_sum, n_valid, correct), the gradient
    of nll_sum, and per layer the pushed rows' (embedding, valid).
    ``precision``: float32 or bfloat16 (weights, activations and
    gradients in bfloat16)."""
    block_fn = _sage_block if m.model == "graphsage" else _gat_block
    dtype = jnp.bfloat16 if precision == "bfloat16" else jnp.float32

    def nll_sum(params):
        p = jax.tree_util.tree_map(lambda a: a.astype(dtype), params)
        h, valid = h0.astype(dtype), valid0
        captured = [(h, valid)]
        L = len(nbr_idx)
        for k in range(L):
            nbr = nbr_idx[k]
            last = k == L - 1
            h = _layer(block_fn, p["layers"][k], h, valid, nbr)
            if not last and m.model == "graphsage":
                h = jax.nn.relu(h)
            valid = valid[:nbr.shape[0]]
            if not last:
                h = _dropout(h, m.dropout, seed + jnp.uint32(k + 1))
                is_halo, use, emb = subs[k]
                h = jnp.where(use[:, None], emb.astype(dtype), h)
                valid = (valid & ~is_halo) | use
                captured.append((h, valid))
        B = labels.shape[0]
        logits = h[:B].astype(jnp.float32)
        lmask = seed_mask & valid[:B]
        logz = jax.scipy.special.logsumexp(logits, -1)
        gold = jnp.take_along_axis(logits, labels[:, None], -1)[:, 0]
        nll = jnp.where(lmask, logz - gold, 0.0)
        correct = ((jnp.argmax(logits, -1) == labels) & lmask).sum()
        pushed = []
        for h_l, v_l in captured:
            n_l = h_l.shape[0]
            ok = (push_pos >= 0) & (push_pos < n_l)
            pos = jnp.clip(push_pos, 0, n_l - 1)
            pushed.append((jax.lax.stop_gradient(h_l[pos]).astype(jnp.float32),
                           ok & v_l[pos]))
        return nll.sum(), (lmask.sum(), correct, pushed)

    (loss, aux), grads = jax.value_and_grad(nll_sum, has_aux=True)(params)
    grads = jax.tree_util.tree_map(lambda g: g.astype(jnp.float32), grads)
    return loss.astype(jnp.float32), aux, grads


@functools.partial(jax.jit, static_argnames=("lr",))
def adam(params, mu, nu, grads, t, *, lr: float, b1=0.9, b2=0.999,
         eps=1e-8, clip=1.0):
    """Adam step ``t`` (1-based) on the gradient clipped to global norm."""
    leaves = jax.tree_util.tree_leaves(grads)
    gnorm = jnp.sqrt(sum(jnp.sum(g * g) for g in leaves))
    scale = jnp.minimum(1.0, clip / (gnorm + 1e-9))
    g = jax.tree_util.tree_map(lambda x: x * scale, grads)
    mu = jax.tree_util.tree_map(lambda a, b: b1 * a + (1 - b1) * b, mu, g)
    nu = jax.tree_util.tree_map(lambda a, b: b2 * a + (1 - b2) * b * b, nu, g)
    c1, c2 = 1 - b1 ** t, 1 - b2 ** t
    params = jax.tree_util.tree_map(
        lambda p, a, b: p - lr * (a / c1) / (jnp.sqrt(b / c2) + eps),
        params, mu, nu)
    return params, mu, nu, g


# ---------------------------------------------------------------------------
# the push rule and the reference's own cache
# ---------------------------------------------------------------------------
@functools.partial(jax.jit, static_argnames=("nc",))
def push_positions(owes, seed, rank, *, nc: int):
    """The push rule: per peer, up to ``nc`` of this rank's layer-0 solid
    rows that the peer holds as halos, chosen uniformly at random by the
    step's seed and the rank.  owes [R, N0] bool; returns [R, nc]
    positions, -1 where fewer rows qualify."""
    key = jax.random.fold_in(jax.random.fold_in(jax.random.PRNGKey(7), seed),
                             rank)
    u = jax.random.uniform(key, owes.shape, minval=1e-6, maxval=1.0)
    top, pos = jax.lax.top_k(jnp.where(owes, u, -1.0), nc)
    return jnp.where(top > 0, pos, -1)


class RefCache:
    """One rank's historical embeddings per layer: vid -> (row, step
    stored).  A row is served while ``step - stored <= life_span``."""

    def __init__(self, num_layers: int, life_span: int):
        self.rows: List[Dict[int, tuple]] = [dict() for _ in range(num_layers)]
        self.life_span = life_span

    def store(self, layer: int, vids: np.ndarray, embs: np.ndarray,
              step: int):
        d = self.rows[layer]
        for v, e in zip(vids.tolist(), embs):
            d[v] = (e, step)

    def live(self, layer: int, step: int) -> Dict[int, np.ndarray]:
        return {v: e for v, (e, s) in self.rows[layer].items()
                if step - s <= self.life_span}

    def lookup(self, layer: int, vids: np.ndarray, is_halo: np.ndarray,
               step: int, dim: int):
        """(use [n] bool, emb [n, dim]) for the halo rows it serves."""
        live = self.live(layer, step)
        use = np.zeros(len(vids), bool)
        emb = np.zeros((len(vids), dim), np.float32)
        if not live:
            return use, emb
        keys = np.fromiter(live.keys(), np.int64, len(live))
        order = np.argsort(keys)
        keys = keys[order]
        rows = np.stack(list(live.values()))[order]
        at = np.minimum(np.searchsorted(keys, vids), len(keys) - 1)
        use = is_halo & (keys[at] == vids)
        emb[use] = rows[at[use]]
        return use, emb


# ---------------------------------------------------------------------------
# the reference's three steps
# ---------------------------------------------------------------------------
@dataclasses.dataclass
class RankBatch:
    """One rank's minibatch of one step, in global vertex ids."""
    vids: List[np.ndarray]       # per layer 0..L: vertex id per row, -1 pad
    solid: List[np.ndarray]      # per layer: row is owned by this rank
    nbr_idx: List[np.ndarray]    # per layer 0..L-1: [n_dst, f] positions
    labels: np.ndarray           # [B]
    seed_mask: np.ndarray        # [B]
    seed: int                    # the step's dropout / push seed


@dataclasses.dataclass
class RefResult:
    losses: List[float]          # example-weighted mean loss per step
    grads: list                  # per step, the clipped gradient Adam used
    params0: dict
    params: dict                 # after the last step
    cache: List[RefCache]        # per rank, after the last step's stores
    stored: List[list]           # per step: per rank, per layer {vid: emb}


def run_reference(m: Model, features: np.ndarray, steps: List[List[RankBatch]],
                  owes: List[np.ndarray], seed: int, *, nc: int, delay: int,
                  life_span: int, precision: str = "float32") -> RefResult:
    """The reference follows ``len(steps)`` steps from its own weights.

    ``features`` [V, F] is the benchmark's own feature table; ``owes[r]``
    [V] bool says, per peer, which of rank r's vertices that peer holds
    as halos ([R, V])."""
    put = jnp.asarray
    R = len(steps[0])
    L = m.num_layers
    dims = m.cache_dims()
    params = init_params(jax.random.key(seed), m)
    params0 = jax.device_get(params)
    mu = jax.tree_util.tree_map(jnp.zeros_like, params)
    nu = jax.tree_util.tree_map(jnp.zeros_like, params)
    caches = [RefCache(L, life_span) for _ in range(R)]
    in_flight = []                      # (due step, dst rank, layer, vids, embs)
    losses, grads_seen, stored = [], [], []
    for t, batches in enumerate(steps, start=1):
        landed = [[dict() for _ in range(L)] for _ in range(R)]
        for due, j, l, vids, embs in [x for x in in_flight if x[0] == t]:
            caches[j].store(l, vids, embs, t)
            landed[j][l].update(zip(vids.tolist(), embs))
        in_flight = [x for x in in_flight if x[0] != t]
        stored.append(landed)
        total = None
        nll, n_ex = 0.0, 0
        for r, b in enumerate(batches):
            v0 = b.vids[0]
            halo0 = (v0 >= 0) & ~b.solid[0]
            use0, emb0 = caches[r].lookup(0, v0, halo0, t, dims[0])
            own0 = (v0 >= 0) & b.solid[0]
            h0 = np.where(own0[:, None], features[np.maximum(v0, 0)], 0.0)
            h0 = np.where(use0[:, None], emb0, h0).astype(np.float32)
            subs = []
            for k in range(1, L):
                vk = b.vids[k]
                halo = (vk >= 0) & ~b.solid[k]
                subs.append((halo, *caches[r].lookup(k, vk, halo, t, dims[k])))
            if R > 1:
                owes_rows = owes[r][:, np.maximum(v0, 0)] & own0[None, :]
                pos = np.asarray(push_positions(put(owes_rows),
                                                jnp.uint32(b.seed), r, nc=nc))
            else:                       # no peer to push to
                pos = np.full((1, nc), -1, np.int64)
            loss_r, (n_r, _, pushed), g = rank_step(
                params, put(h0), put(own0 | use0),
                [put(x) for x in b.nbr_idx],
                [tuple(put(x) for x in sub) for sub in subs],
                put(b.labels), put(b.seed_mask), jnp.uint32(b.seed),
                put(pos.reshape(-1)), m=m, precision=precision)
            nll += float(loss_r)
            n_ex += int(n_r)
            total = g if total is None else jax.tree_util.tree_map(
                jnp.add, total, g)
            for l, (emb, ok) in enumerate(pushed):
                emb, ok = np.asarray(emb), np.asarray(ok)
                emb, ok = emb.reshape(R, nc, -1), ok.reshape(R, nc)
                for j in range(R):
                    if j == r:
                        continue
                    sel = ok[j]
                    vids = v0[pos[j][sel]]
                    in_flight.append((t + delay, j, l, vids,
                                      emb[j][sel][:, :dims[l]]))
        denom = max(n_ex, 1)
        grads = jax.tree_util.tree_map(lambda x: x / denom, total)
        losses.append(nll / denom)
        params, mu, nu, g_used = adam(params, mu, nu, grads, float(t),
                                      lr=m.lr)
        grads_seen.append(jax.device_get(g_used))
    return RefResult(losses=losses, grads=grads_seen, params0=params0,
                     params=jax.device_get(params), cache=caches,
                     stored=stored)
