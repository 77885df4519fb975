"""HaloExchangeEngine — the ONE halo-exchange path (paper §3.4).

Every cross-rank embedding movement in the repo goes through this engine:

  * **AEP push** (training, paper Algorithm 2 lines 14-24): select up to
    ``nc`` solid embeddings per remote rank from the static push contract
    (``ExchangePlan.push_mask``, one boolean gather — no per-step
    ``searchsorted`` probes), gather the per-layer embeddings, and move
    tags + payload in ONE fused ``all_to_all`` (tags ride as exact float
    halves in the payload's leading lanes, so the legacy two-collective
    push becomes a single collective).  The received push lands in the delay-``d``
    in-flight queue (``repro.core.aep``) and is HECStore'd ``d`` steps
    later — the paper's bounded staleness, bit-exact.

    **Overlap**: the push depends only on *forward* activations, so the
    trainer dispatches it between the forward and backward passes
    (dispatch-then-wait).  XLA's scheduler overlaps the collective with
    backward compute — the paper's MPI ``AlltoallAsync`` + ``comm_wait``
    scheme — and because the pushed values are identical either way,
    overlap mode bit-matches the inline push.

  * **sync fetch** (DistDGL-like baseline): blocking request/response
    ``all_to_all`` pair answering fresh layer-0 halo features from the
    owners' feature tables via the plan's sorted owner tables.

  * **serve-side cache fetch**: the same request/response pattern, with
    the owner answering from its layer-k HEC (sharded serving's per-layer
    halo gather).

  * **exact offline exchange** (host): one exchange per layer moving
    exactly ``db_halo(i, j)`` rows per pair, via the plan's precomputed
    gather/scatter index vectors.

Device methods run *inside* shard_map on per-rank slices; host methods run
outside.  The in-flight queue ADT and the analytic communication byte
models live in ``repro.core.aep`` (the engine consumes the queue;
benchmarks consume the byte models); exact per-exchange volumes come from
``ExchangePlan.exchange_bytes``.

PR 5 — heavy-tail elimination, both engine-side mechanisms:

  * **hot-vertex tier refresh** (``hot_budget > 0``): the plan's top-K hub
    vertices leave the pairwise push contract; instead each rank
    broadcasts up to ``hot_budget`` of its *owned* hot vertices' per-layer
    embeddings to every rank, piggybacked as one extra segment of the SAME
    fused all_to_all (identical bytes to every destination — still one
    collective, no new ops).  Received hot rows ride the same delay-``d``
    in-flight queue and land in the replicated tier
    (``repro.cache.hot_tier``), aged by the HEC life-span — a stale
    replica degrades exactly like an HEC miss (the halo row is dropped
    from aggregation via the validity mask), so the paper's bounded
    staleness/degradation semantics carry over; size ``hot_budget *
    life_span`` to cover the busiest owner's hot vertices (each rank
    refreshes only hubs it owns — the trainer warns when undersized).

  * **multi-round exchange batching** (``cache_fetch(..., rounds=N)``):
    N queued serve rounds' halo requests execute as ONE fused
    request/response all_to_all pair with the rounds' per-pair slot
    budgets pooled — total coverage per owner pair never decreases vs N
    separate fetches (allocation across rounds is priority-ordered, so
    size the per-round budget for one round's worst case).  ``rounds=1``
    is bit-identical to the unbatched fetch.
"""
from __future__ import annotations

from typing import List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.cache import hec as hec_lib
from repro.cache import hot_tier as hot_lib
from repro.comm.plan import ExchangePlan, build_exchange_plan
from repro.core import aep


def row_vids(vid_o, nodes):
    """VID_o key of each sampled row (VID_p ``nodes``; -1 where padded)."""
    return jnp.where(nodes >= 0, vid_o[jnp.clip(nodes, 0, vid_o.shape[0] - 1)],
                     -1)


def _tags_to_f32(tags):
    """int32 ``[..., n]`` -> float32 ``[..., 2n]``: high and low 16-bit
    halves as exact float values.  A bitcast would turn small tags into
    denormal floats and -1 into a NaN, and the TPU compiler may route the
    payload through float arithmetic (it lowers the pack's concatenate to
    a ``maximum`` of padded operands), which flushes denormals to zero
    and rewrites NaN bits."""
    return jnp.concatenate([(tags >> 16).astype(jnp.float32),
                            (tags & 0xFFFF).astype(jnp.float32)], axis=-1)


def _f32_to_tags(halves):
    """Inverse of :func:`_tags_to_f32`."""
    n = halves.shape[-1] // 2
    hi = halves[..., :n].astype(jnp.int32)
    return (hi << 16) | halves[..., n:].astype(jnp.int32)


class HaloExchangeEngine:
    """Exchange-plan-driven halo communication for train / serve / offline.

    Construct with :meth:`from_partition` to carry an :class:`ExchangePlan`
    (host-side helpers + ``device_tables()``), or directly with just the
    shape parameters when the plan tables arrive through the sharded data
    dict (the trainer's step functions only close over shapes)."""

    def __init__(self, num_ranks: int, num_layers: int = 1,
                 push_limit: int = 1, delay: int = 1, axis: str = "data",
                 plan: Optional[ExchangePlan] = None, hot_budget: int = 0,
                 probe_kernel: bool = False):
        self.num_ranks = num_ranks
        self.num_layers = num_layers
        self.push_limit = push_limit     # nc: slots per rank pair
        self.delay = delay               # d: steps between push and consume
        self.axis = axis
        self.plan = plan
        self.hot_budget = hot_budget     # hot rows broadcast per rank per step
        self.probe_kernel = probe_kernel  # batched Pallas HEC probe in
        #                                   cache_fetch (bit-identical off/on)

    @classmethod
    def from_partition(cls, ps, num_layers: int = 1, push_limit: int = 1,
                       delay: int = 1, axis: str = "data", hot_size: int = 0,
                       hot_budget: int = 0):
        return cls(ps.num_parts, num_layers, push_limit, delay, axis,
                   plan=build_exchange_plan(ps, hot_size=hot_size),
                   hot_budget=hot_budget)

    # -- plan plumbing --------------------------------------------------------
    def device_tables(self) -> dict:
        assert self.plan is not None, "engine built without a partition plan"
        return self.plan.device_tables()

    def inflight_init(self, dim_max: int) -> dict:
        """Stacked ``[R, d, R, L, nc(, dmax)]`` in-flight push queue; with a
        hot budget the queue grows matching ``hot_*`` buffers for the
        broadcast segment (slot ids instead of vid tags)."""
        def one(_):
            q = aep.queue_init(self.delay, self.num_ranks, self.num_layers,
                               self.push_limit, dim_max)
            if self.hot_budget:
                hb = self.hot_budget
                q["hot_tags"] = jnp.full(
                    (self.delay, self.num_ranks, self.num_layers, hb), -1,
                    jnp.int32)
                q["hot_embs"] = jnp.zeros(
                    (self.delay, self.num_ranks, self.num_layers, hb,
                     dim_max), jnp.float32)
            return q
        return jax.vmap(one)(jnp.arange(self.num_ranks))

    # -- AEP push (device, inside shard_map) -----------------------------------
    def select_push(self, data: dict, mb: dict, captured: dict,
                    num_solid, seed, dims, dmax: int, me):
        """Per-remote-rank reservoir selection of up to ``nc`` solid
        embeddings this rank owes (paper lines 14-20).  Membership in the
        push contract is ONE gather into the precomputed ``push_mask``."""
        R = self.num_ranks
        L = self.num_layers
        nc = self.push_limit
        nodes0 = mb["layer_nodes"][0]
        mask0 = mb["node_mask"][0]
        is_solid = (nodes0 < num_solid) & (nodes0 >= 0) & mask0
        N0 = nodes0.shape[0]
        key = jax.random.fold_in(
            jax.random.fold_in(jax.random.PRNGKey(7), seed), me)
        u = jax.random.uniform(key, (R, N0), minval=1e-6, maxval=1.0)

        pm = data["push_mask"]                       # [R_dst, P] bool
        P = pm.shape[1]
        member = pm[:, jnp.clip(nodes0, 0, P - 1)] & is_solid[None, :]
        score = jnp.where(member, u, -1.0)           # [R, N0]
        topv, topi = jax.lax.top_k(score, nc)        # [R, nc]
        ok0 = topv > 0
        # VID_o keys of the winners only: [R, nc], not every sampled row
        base_tags = jnp.where(ok0, row_vids(data["vid_o"], nodes0[topi]), -1)
        pos = jnp.where(ok0, topi, 0)
        base_ok = base_tags >= 0

        tags = jnp.zeros((R, L, nc), jnp.int32)
        embs = jnp.zeros((R, L, nc, dmax), jnp.float32)
        for l in range(L):
            h_l, valid_l = captured[l]
            n_l = h_l.shape[0]
            p_cl = jnp.clip(pos, 0, n_l - 1)
            ok = base_ok & (pos < n_l) & valid_l[p_cl]
            e = jnp.where(ok[..., None], h_l[p_cl].astype(jnp.float32), 0.0)
            embs = embs.at[:, l, :, :dims[l]].set(e)
            tags = tags.at[:, l].set(jnp.where(ok, base_tags, -1))
        return tags, embs

    def select_hot_push(self, data, mb, captured, num_solid, seed, dims,
                        dmax: int, me):
        """Reservoir-select up to ``hot_budget`` of this rank's *owned* hot
        vertices present in the minibatch; every rank will receive the same
        rows (broadcast refresh).  Tags are dense tier SLOT indices, not
        vids — the receiver scatters them straight into its replica."""
        L = self.num_layers
        hb = self.hot_budget
        nodes0 = mb["layer_nodes"][0]
        mask0 = mb["node_mask"][0]
        vid0 = row_vids(data["vid_o"], nodes0)
        is_solid = (nodes0 < num_solid) & (nodes0 >= 0) & mask0
        slot, is_hot = hot_lib.tier_slots(data["hot_vids"], vid0)
        mine = data["hot_mine"][slot] & is_hot & is_solid
        key = jax.random.fold_in(
            jax.random.fold_in(jax.random.PRNGKey(11), seed), me)
        u = jax.random.uniform(key, nodes0.shape, minval=1e-6, maxval=1.0)
        score = jnp.where(mine, u, -1.0)
        topv, topi = jax.lax.top_k(score, hb)
        ok0 = topv > 0
        base_tags = jnp.where(ok0, slot[topi], -1)
        pos = jnp.where(ok0, topi, 0)

        tags = jnp.zeros((L, hb), jnp.int32)
        embs = jnp.zeros((L, hb, dmax), jnp.float32)
        for l in range(L):
            h_l, valid_l = captured[l]
            n_l = h_l.shape[0]
            p_cl = jnp.clip(pos, 0, n_l - 1)
            ok = (base_tags >= 0) & (pos < n_l) & valid_l[p_cl]
            e = jnp.where(ok[:, None], h_l[p_cl].astype(jnp.float32), 0.0)
            embs = embs.at[l, :, :dims[l]].set(e)
            tags = tags.at[l].set(jnp.where(ok, base_tags, -1))
        return tags, embs

    def push(self, tags, embs, hot=None):
        """ONE fused all_to_all: int32 tags ride as exact float32 halves
        (``_tags_to_f32``) in a flat prefix of the payload.  The pack is
        contiguous block copies per rank row, not an interleaved per-slot
        lane, so fusing costs no strided traffic.

        ``hot=(hot_tags [L, hb], hot_embs [L, hb, dmax])`` appends the
        hot-tier broadcast segment — identical bytes to every destination
        row, so the refresh rides the SAME collective.  Returns
        ``(rec_tags, rec_embs)`` or, with ``hot``, additionally
        ``(rec_hot_tags [R, L, hb], rec_hot_embs [R, L, hb, dmax])``."""
        R, L, nc = tags.shape
        dmax = embs.shape[-1]
        blocks = [_tags_to_f32(tags.reshape(R, L * nc)),
                  embs.reshape(R, L * nc * dmax)]
        if hot is not None:
            hot_tags, hot_embs = hot
            hb = hot_tags.shape[-1]
            ht = _tags_to_f32(hot_tags.reshape(1, L * hb))
            blocks.append(jnp.broadcast_to(ht, (R, 2 * L * hb)))
            blocks.append(jnp.broadcast_to(
                hot_embs.reshape(1, L * hb * dmax), (R, L * hb * dmax)))
        buf = jnp.concatenate(blocks, axis=-1)
        with jax.named_scope("aep_exchange"):
            rec = jax.lax.all_to_all(buf, self.axis, 0, 0)
        o = 2 * L * nc
        rec_tags = _f32_to_tags(rec[:, :o]).reshape(R, L, nc)
        rec_embs = rec[:, o:o + L * nc * dmax].reshape(R, L, nc, dmax)
        if hot is None:
            return rec_tags, rec_embs
        o += L * nc * dmax
        hb = hot[0].shape[-1]
        rec_hot_tags = _f32_to_tags(rec[:, o:o + 2 * L * hb]).reshape(R, L, hb)
        rec_hot_embs = rec[:, o + 2 * L * hb:].reshape(R, L, hb, dmax)
        return rec_tags, rec_embs, rec_hot_tags, rec_hot_embs

    def aep_push(self, data, mb, captured, num_solid, inflight, seed, dims,
                 dmax, me, fault_code=None):
        """Select + fused-push + enqueue; returns ``(inflight, stats)``.

        ``stats['push_rows']`` / ``stats['push_bytes']`` measure the
        payload this step dispatched behind the backward pass (the
        overlap metrics surfaced by the trainer/examples); with a hot
        budget, ``stats['hot_push_rows']`` counts the broadcast-segment
        rows riding the same collective.

        ``fault_code`` (a traced int32 scalar) arms the resilience path:
        non-finite payload rows are filtered BEFORE dispatch (NaN
        containment — a locally poisoned step never pollutes remote
        HECs), then the scheduled wire faults apply AFTER the filter:
        bit ``CODE_DROP_PUSH`` drops this rank's outgoing payload
        (tags -> -1), bit ``CODE_CORRUPT_PUSH`` corrupts the payload to
        NaN with tags intact, so the garbage lands in remote HEC lines
        and downstream steps must be contained by the step guard.  A
        zero code computes identical bits to ``fault_code=None``."""
        from repro.resilience.inject import (CODE_CORRUPT_PUSH,
                                             CODE_DROP_PUSH)
        with jax.named_scope("aep_pack"):
            tags, embs = self.select_push(data, mb, captured, num_solid,
                                          seed, dims, dmax, me)
        if fault_code is not None:
            rowok = jnp.isfinite(embs).all(axis=-1)       # [R, L, nc]
            tags = jnp.where(rowok, tags, -1)
            embs = jnp.where(rowok[..., None], embs, 0.0)
            drop = (fault_code & CODE_DROP_PUSH) != 0
            corrupt = (fault_code & CODE_CORRUPT_PUSH) != 0
            embs = jnp.where(corrupt & (tags >= 0)[..., None],
                             jnp.float32(jnp.nan), embs)
            tags = jnp.where(drop, -1, tags)
            embs = jnp.where(drop, 0.0, embs)
        rows = (tags >= 0).sum()
        nbytes = jnp.zeros((), jnp.float32)
        for l in range(self.num_layers):
            nbytes += (tags[:, l] >= 0).sum().astype(jnp.float32) \
                * (4.0 + 4.0 * dims[l])
        stats = {"push_rows": rows, "push_bytes": nbytes}
        if self.hot_budget and "hot_tags" in inflight:
            with jax.named_scope("aep_pack"):
                h_tags, h_embs = self.select_hot_push(
                    data, mb, captured, num_solid, seed, dims, dmax, me)
            if fault_code is not None:
                # NaN containment for the broadcast segment too (wire
                # faults target only the pairwise payload)
                h_ok = jnp.isfinite(h_embs).all(axis=-1)  # [L, hb]
                h_tags = jnp.where(h_ok, h_tags, -1)
                h_embs = jnp.where(h_ok[..., None], h_embs, 0.0)
            rec_tags, rec_embs, rec_ht, rec_he = self.push(
                tags, embs, hot=(h_tags, h_embs))
            hot_rows = (h_tags >= 0).sum() * (self.num_ranks - 1)
            for l in range(self.num_layers):
                stats["push_bytes"] += \
                    (h_tags[l] >= 0).sum().astype(jnp.float32) \
                    * (self.num_ranks - 1) * (4.0 + 4.0 * dims[l])
            stats["hot_push_rows"] = hot_rows
            out = aep.queue_pop_push(inflight, rec_tags, rec_embs)
            out["hot_tags"] = jnp.concatenate(
                [inflight["hot_tags"][1:], rec_ht[None]], 0)
            out["hot_embs"] = jnp.concatenate(
                [inflight["hot_embs"][1:], rec_he[None]], 0)
            return out, stats
        rec_tags, rec_embs = self.push(tags, embs)
        return aep.queue_pop_push(inflight, rec_tags, rec_embs), stats

    def consume_push(self, hec: List, inflight: dict, dims,
                     life_span: int, hot: Optional[List] = None):
        """Tick every layer's HEC, then store the delay-expired push slot
        (paper lines 8-9).  With a hot tier, tick + scatter the broadcast
        segment into the replica the same way — ``tier_lookup`` then
        rejects slots older than the life-span, and a stale hub halo is
        dropped from aggregation exactly like an HEC miss (hot vids left
        the pairwise contract, so the HEC holds no copy): the same
        bounded-degradation semantics, same staleness bound."""
        with jax.named_scope("aep_consume"):
            hec = [hec_lib.hec_tick(h, life_span) for h in hec]
            for l in range(self.num_layers):
                tl = inflight["tags"][0, :, l].reshape(-1)
                el = inflight["embs"][0, :, l, :, :dims[l]].reshape(
                    -1, dims[l])
                hec[l] = hec_lib.hec_store(hec[l], tl, el)
            if hot is None:
                return hec
            out_hot = []
            for l in range(self.num_layers):
                t = hot_lib.tier_tick(hot[l])
                sl = inflight["hot_tags"][0, :, l].reshape(-1)
                el = inflight["hot_embs"][0, :, l, :, :dims[l]].reshape(
                    -1, dims[l])
                out_hot.append(hot_lib.tier_store(t, sl, el))
            return hec, out_hot

    # -- sync baseline fetch (device, inside shard_map) -------------------------
    def sync_fetch(self, data, vid0, is_halo0, h0):
        """DistDGL-like blocking fetch of fresh layer-0 halo features."""
        R = self.num_ranks
        nc = self.push_limit
        N0 = vid0.shape[0]
        # request the first nc halos (by position) from every rank; the
        # owner answers.  (DistDGL prefetches remote features for the whole
        # sampled neighborhood right after minibatch creation.)
        score = jnp.where(is_halo0,
                          (jnp.arange(N0, 0, -1, dtype=jnp.float32)), -1.0)
        topv, topi = jax.lax.top_k(score, nc)
        ok = topv > 0
        req_row = jnp.where(ok, vid0[topi], -1)
        req = jnp.broadcast_to(req_row, (R, nc))
        pos_row = jnp.where(ok, topi, 0)
        got_req = jax.lax.all_to_all(req, self.axis, 0, 0)  # [R_from, nc]
        sorted_vids = data["solid_sorted_vids"]
        S = sorted_vids.shape[0]
        loc = jnp.clip(jnp.searchsorted(sorted_vids, got_req), 0, S - 1)
        own = (sorted_vids[loc] == got_req) & (got_req >= 0)
        feats = data["features"][data["solid_sorted_idx"][loc]] \
            * own[..., None]
        resp = jax.lax.all_to_all(
            jnp.concatenate([feats, own[..., None].astype(jnp.float32)], -1),
            self.axis, 0, 0)                                # [R, nc, F+1]
        got_feats, got_ok = resp[..., :-1], resp[..., -1] > 0.5
        # each requested halo answered by exactly its owner -> sum over ranks
        add = (got_feats * got_ok[..., None]).sum(0)        # [nc, F]
        any_ok = got_ok.any(0)                              # [nc]
        h0 = h0.at[pos_row].add(jnp.where(any_ok[:, None], add, 0.0))
        got = jnp.zeros(N0, bool).at[pos_row].max(any_ok)
        return h0, got & is_halo0

    # -- serve-side cache fetch (device, inside shard_map) ----------------------
    def cache_fetch(self, state, vids_o, owner, need, h,
                    slots: Optional[int] = None, rounds: int = 1,
                    alive=None):
        """One all_to_all request/response pair answering the ``need`` rows
        from the owners' layer-k caches.  Returns the substituted ``h``,
        the rows answered, and how many rows actually traveled.

        ``alive`` (a traced ``[R]`` bool, replicated) is the degraded-mode
        health mask: requests to a dead owner are suppressed (the row
        falls through to the caller's validity-mask drop path — or to a
        stale hot-tier/HEC replica if one substituted earlier) and a dead
        rank's responder side answers nothing, modeling the unresponsive
        peer.  ``alive=None`` or all-True computes identical bits to the
        unmasked fetch.

        ``rounds=N`` fuses N queued serve rounds into this ONE collective
        pair: the request buffer grows to ``[R, N * slots]`` — the N
        rounds' per-pair budgets POOL, so the TOTAL rows answered per
        owner pair never decreases
        (``min(total_need, N*slots) >= sum_i min(need_i, slots)``).
        Allocation across the fused rounds is priority-ordered, not
        per-round-fair: under overload (total demand toward one owner
        beyond ``N * slots``) an early hub-heavy round can claim slots a
        later round would have had unbatched, shifting WHICH rows drop —
        size ``slots`` (``DistServeConfig.halo_slots``) for one round's
        worst case so the pooled budget covers the batch.  ``rounds=1``
        is bit-identical to the unbatched fetch."""
        R = self.num_ranks
        N = vids_o.shape[0]
        d = h.shape[1]
        nslots = min((slots or self.push_limit) * rounds, N)
        prio = jnp.arange(N, 0, -1).astype(jnp.float32)
        req_rows, pos_rows = [], []
        for j in range(R):
            want = need & (owner == j)
            if alive is not None:
                want = want & alive[j]
            score = jnp.where(want, prio, -1.0)
            topv, topi = jax.lax.top_k(score, nslots)
            ok = topv > 0
            req_rows.append(jnp.where(ok, vids_o[topi], -1))
            pos_rows.append(jnp.where(ok, topi, N))  # N -> scatter-drop
        req = jnp.stack(req_rows).astype(jnp.int32)        # [R, nslots]
        pos = jnp.stack(pos_rows)
        got_req = jax.lax.all_to_all(req, self.axis, 0, 0)  # [R_src, nslots]
        if self.probe_kernel:
            # batched Pallas probe: all R requesters' rows in ONE kernel
            # grid (bit-identical to the flattened hec_lookup below)
            from repro.kernels.hec_search import hec_probe
            own, vals = hec_probe(state, got_req)
        else:
            own, vals = hec_lib.hec_lookup(state, got_req.reshape(-1))
            own = own.reshape(R, nslots)
            vals = vals.reshape(R, nslots, d)
        if alive is not None:
            # a dead rank answers nothing (responder side of the mask)
            own = own & alive[jax.lax.axis_index(self.axis)]
        resp = jax.lax.all_to_all(
            jnp.concatenate(
                [vals.astype(jnp.float32),
                 own[..., None].astype(jnp.float32)], -1),
            self.axis, 0, 0)                               # [R, nslots, d+1]
        r_vals, r_ok = resp[..., :-1], resp[..., -1] > 0.5
        fetched = jnp.zeros((N, d), h.dtype)
        got = jnp.zeros(N, bool)
        # request rows to distinct owners occupy disjoint positions, so
        # per-owner scatters never collide; pad slots land on N (drop)
        for j in range(R):
            fetched = fetched.at[pos[j]].set(
                r_vals[j].astype(h.dtype) * r_ok[j][:, None], mode="drop")
            got = got.at[pos[j]].max(r_ok[j], mode="drop")
        h = jnp.where(got[:, None], fetched, h)
        return h, got, (req >= 0).sum()

    # -- exact offline exchange (host) -----------------------------------------
    def exchange_halos_host(self, h_solid: List[np.ndarray]) \
            -> Tuple[List[np.ndarray], int]:
        """One exact halo exchange: every rank receives the current-layer
        embeddings of its halo replicas from their owners.

        Pair (i, j) moves exactly ``db_halo(i, j)`` rows through the
        plan's precomputed gather/scatter indices.  Returns per-rank halo
        rows (aligned with ``part.halo_vids``) and the total bytes moved
        (payload + vid tags), the number the benchmark comm model uses."""
        assert self.plan is not None and self.plan.send_local is not None, \
            "needs a plan built with host_indices=True"
        plan = self.plan
        R = self.num_ranks
        dim = h_solid[0].shape[1] if len(h_solid) else 0
        rows_out: List[np.ndarray] = []
        nbytes = 0
        rank_rows = np.zeros(R, np.int64)
        rank_bytes = np.zeros(R, np.int64)
        with obs.span("offline_exchange", ranks=R):
            for j in range(R):
                rows = np.zeros((int(plan.num_halo[j]), dim), np.float32)
                for i in range(R):
                    if i == j or not len(plan.send_local[i][j]):
                        continue
                    payload = h_solid[i][plan.send_local[i][j]]
                    rows[plan.recv_pos[i][j]] = payload
                    moved = payload.nbytes + len(plan.send_local[i][j]) * 4
                    nbytes += moved
                    rank_rows[j] += len(plan.send_local[i][j])
                    rank_bytes[j] += moved
                rows_out.append(rows)
        obs.count("offline_exchange_bytes", nbytes)
        # per-rank inbound series for the health plane: one exchange's
        # receiver-side rows/bytes, published as rank-labeled counters +
        # cluster skew views (the live counterpart of the plan-time
        # expectation in ExchangePlan.expected_inbound_rows)
        reg = obs.get().registry
        if reg.enabled:
            obs.publish_rank_series(
                reg, {"rank_exchange_rows": rank_rows,
                      "rank_exchange_bytes": rank_bytes})
        return rows_out, nbytes
