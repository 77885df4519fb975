"""Sharded multi-rank GNN serving: one serving shard per mesh rank.

Scaling the serving subsystem the same way training scales (paper §3.1):
the graph is partitioned across ``R`` mesh ranks, each shard holds its
partition's CSR + features + per-layer HEC cache, and a compiled shard_map
``serve_step`` answers one synchronized round of per-rank fixed-slot
microbatches.  Per round:

  1. **routing** (host): the ``QueryRouter`` maps each queried VID_o to its
     owner rank (``PartitionSet.route``) and packs up to ``num_slots``
     seeds per rank — one compiled ``[R, slots]`` shape covers every rank,
     however skewed the query stream,
  2. **cache-aware partition-local sampling** (host, per rank): the
     pipeline's vectorized sampler with this shard's ``expandable`` masks —
     cache-resident vertices (solids *and* halos) become leaves,
  3. **serve_step** (device, one shard_map program): forward through the
     model.  Layer-0 halo rows read the shard's static **feature mirror**
     (features never go stale, so they are replicated at build time and
     never travel).  At every hidden layer the local shard cache is
     consulted first (``hec_lookup``), then the *remaining* cross-cut halo
     rows are gathered from their owners' caches with ONE all_to_all
     request/response pair — ``HaloExchangeEngine.cache_fetch``, the same
     engine the trainer pushes through, with fixed ``halo_slots`` per rank
     pair.  Fetched halo embeddings are stored
     back into the local shard cache, so repeated cross-cut neighborhoods
     stop traveling — the cached-halo fraction is a first-class metric,
  4. **residency sync** (host): device tags mirrored per shard.

A halo row whose owner cannot answer (cold owner cache, or more misses
than ``halo_slots``) is dropped from aggregation via the validity mask —
the same bounded-degradation semantics training uses for HEC misses.  With
owner caches pre-warmed from distributed offline inference the answers are
exact and bit-match single-rank serving.

``update_params`` bumps the model version and drops every cached line on
every shard at once — no shard can serve a stale answer after a
checkpoint update.

PR 5 heavy-tail elimination, all three knobs off by default (the disabled
scheduler is bit-compatible with PR 4):

  * ``hot_size=K`` — the plan's top-K hub vertices get a replicated
    **hot tier** slot on every shard (``repro.cache.hot_tier``): a halo
    row whose hub embedding is valid in the local replica never enters
    the ``cache_fetch`` request, and a query whose *output* slot is valid
    is answered fast-path on ANY shard's replica.  Cold/invalidated
    replicas fall back to the normal fetch path (bit-identical answers),
  * ``dedup=True`` — **cross-query neighborhood dedup**: queries for the
    same VID_o within a round are compacted to ONE slot (sorted
    unique-VID grouping at packing time; the sampler's unique-VID
    compaction already dedups shared subtrees *within* a microbatch),
    computed once, and the answer is scattered back to every requesting
    query,
  * ``round_batch=N`` — **multi-round fused exchange batching**: N rounds
    are fused into one block-diagonal compiled step
    (``concat_blocks``, bit-exact vs N separate forwards), so each hidden
    layer's halo gather becomes ONE all_to_all pair carrying all N
    rounds' requests with pooled per-pair budgets
    (``cache_fetch(rounds=N)`` — total coverage per owner pair never
    decreases vs N separate fetches; keep ``halo_slots`` sized for one
    round's worst case so no round starves under overload).
"""
from __future__ import annotations

import contextlib
import dataclasses
import time
from typing import List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro import obs
from repro.cache import hec as hec_lib
from repro.cache import hot_tier as hot_lib
from repro.cache.hot_tier import HotTierCache
from repro.comm.engine import HaloExchangeEngine
from repro.comm.plan import _pad_stack, hot_set_tables
from repro.graph.partition import PartitionSet
from repro.models.gnn import gat as gat_lib
from repro.models.gnn import graphsage as sage_lib
from repro.pipeline.vectorized_sampler import (concat_blocks,
                                               sample_blocks_vectorized,
                                               stack_ranks)
from repro.resilience.failover import RankHealthMask
from repro.serve.gnn.distributed.router import QueryRouter
from repro.serve.gnn.distributed.sharded_cache import ShardedServingCache
from repro.serve.gnn.embedding_cache import ServeCacheConfig
from repro.serve.gnn.offline import serve_layer_dims
from repro.serve.gnn.scheduler import GNNRequest, ServeFrontend


@dataclasses.dataclass(frozen=True)
class DistServeConfig:
    num_slots: int = 32            # seeds per rank per round (compiled shape)
    halo_slots: int = 256          # all_to_all request slots per rank pair
    cache: ServeCacheConfig = dataclasses.field(
        default_factory=ServeCacheConfig)
    sample_seed: int = 0           # base seed of the per-round RNG
    max_queue_depth: Optional[int] = None  # admission cap across all shards
    hot_size: int = 0              # K: replicated hot-tier slots (0 = off)
    dedup: bool = False            # cross-query neighborhood dedup
    round_batch: int = 1           # rounds fused into one step/collective
    fused_kernel: bool = False     # fused Pallas serve layer (graphsage
    #                                only; off = composed jnp, byte-identical)
    probe_kernel: bool = False     # batched Pallas HEC probe inside
    #                                cache_fetch (off = jnp hec_lookup)
    failover: bool = False         # degraded-mode serving: per-rank health
    #                                mask + circuit breaker; a marked-dead
    #                                rank's halo traffic is suppressed and
    #                                its owned queries answer from stale
    #                                replicas (all-alive = bit-identical)
    probe_timeout_s: float = 1.0   # re-probe timeout (a hung probe = dead)
    breaker_cooldown: int = 1      # rounds OPEN before the half-open probe
    breaker_threshold: int = 1     # failures that open a rank's breaker


def build_serve_data(ps: PartitionSet) -> dict:
    """Per-rank stacked serving tables (the serve-side ``build_dist_data``):
    features, partition id maps, per-VID_p owner ranks, and a **halo
    feature mirror** — each shard carries the input features of its halo
    replicas.  Features are static and model-version-independent, so the
    mirror never goes stale; it removes the layer-0 all_to_all entirely
    (training keeps halos feature-less because features *change* there —
    they don't in serving)."""
    num_solid = np.array([p.num_solid for p in ps.parts], np.int32)
    feats = _pad_stack([p.features for p in ps.parts], 0.0)
    halo_feats = []
    for p in ps.parts:
        owner, local = ps.route(p.halo_vids) if p.num_halo else (
            np.empty(0, np.int64), np.empty(0, np.int64))
        hf = np.zeros((max(p.num_halo, 1), feats.shape[-1]), np.float32)
        for r in range(ps.num_parts):
            mine = owner == r
            hf[np.flatnonzero(mine)] = ps.parts[r].features[local[mine]]
        halo_feats.append(hf)
    vid_o = _pad_stack([p.vid_p_to_o().astype(np.int32) for p in ps.parts],
                       -1)
    owner_p = _pad_stack(
        [np.concatenate([np.full(p.num_solid, r, np.int32),
                         p.halo_owner.astype(np.int32)])
         for r, p in enumerate(ps.parts)], -1)
    return {
        "features": jnp.asarray(feats, jnp.float32),
        "halo_features": jnp.asarray(_pad_stack(halo_feats, 0.0),
                                     jnp.float32),
        "num_solid": jnp.asarray(num_solid),
        "vid_o": jnp.asarray(vid_o),
        "owner_p": jnp.asarray(owner_p),
    }


class DistGNNServeScheduler(ServeFrontend):
    """Sharded serving over a ``PartitionSet`` on a 1-D ``("data",)`` mesh."""

    def __init__(self, cfg, params, ps: PartitionSet, mesh,
                 serve_cfg: Optional[DistServeConfig] = None,
                 health: Optional["obs.HealthPlane"] = None,
                 quality: Optional["obs.QualityPlane"] = None):
        self.cfg = cfg
        self.scfg = serve_cfg or DistServeConfig()
        self.ps = ps
        self.mesh = mesh
        self.num_ranks = ps.num_parts
        self.params = params
        # cluster health plane: per-round per-rank telemetry + detectors
        # (load skew, edge-cut drift vs `num_halo`, SLO burn on the serve
        # latency histogram, hot-tier decay).  Host-side only — the
        # compiled serve step is identical with or without it.
        self.health = health \
            if (health is not None and health.enabled) else None
        # quality plane: shard-cache + hot-replica staleness telemetry and
        # the on-demand exactness audit (`audit`); host-side reads only
        self.quality = quality \
            if (quality is not None and quality.enabled) else None
        self.data = build_serve_data(ps)
        self.cache = ShardedServingCache(serve_layer_dims(cfg), ps,
                                         self.scfg.cache)
        self.router = QueryRouter(ps)
        self.engine = HaloExchangeEngine(self.num_ranks, cfg.num_layers,
                                         push_limit=self.scfg.halo_slots,
                                         probe_kernel=self.scfg.probe_kernel)
        # replicated hot tier over the plan's static hot set (hubs that
        # are halos somewhere); needs the normal cache machinery on.
        # Only the hot tables are derived — serving never consumes the
        # push_mask/db_halo side of a full ExchangePlan.
        self.hot: Optional[HotTierCache] = None
        if self.scfg.hot_size and self.scfg.cache.enabled:
            hot_vids, _, _ = hot_set_tables(ps, self.scfg.hot_size)
            if len(hot_vids):
                self.hot = HotTierCache(serve_layer_dims(cfg),
                                        hot_vids, self.num_ranks)
                self.data["hot_vids"] = jnp.asarray(np.broadcast_to(
                    hot_vids, (self.num_ranks, len(hot_vids))))
                self._hot_vid_p = self._hot_local_positions(hot_vids)
        self._init_frontend()
        # degraded-mode failover (PR 10): per-rank circuit breaker.  A dead
        # rank's owned queries answer from stale replicas (hot tier / any
        # alive shard's output cache) and the compiled step's `alive` mask
        # suppresses halo traffic to/from it; with every rank alive the
        # masked step computes bit-identical outputs, so arming the knob
        # on a healthy cluster changes nothing.
        self.breaker: Optional[RankHealthMask] = None
        self.probe_fn = None   # Callable[[int], bool]; None = probe succeeds
        self.degraded_answers = 0
        self.degraded_dropped = 0
        if self.scfg.failover:
            self.breaker = RankHealthMask(
                self.num_ranks, cooldown=self.scfg.breaker_cooldown,
                threshold=self.scfg.breaker_threshold)
        # fused Pallas serve layer — graphsage only, GAT keeps composed jnp
        self._fused = bool(self.scfg.fused_kernel) and cfg.model == "graphsage"
        self._step = self._build_step()
        self._lookup = jax.jit(jax.vmap(
            lambda state, vids: hec_lib.hec_lookup(state, vids)))
        if self.hot is not None:
            hv = jnp.asarray(self.hot.hot_vids, jnp.int32)
            self._tier_lookup = jax.jit(jax.vmap(
                lambda state, vids: hot_lib.tier_lookup(state, hv, vids)))

    def _hot_local_positions(self, hot_vids: np.ndarray) -> List[np.ndarray]:
        """Per shard, the VID_p of each hot vertex (solid or halo) or -1
        when the vertex does not appear in that shard's partition — used
        to turn tier-valid hubs into sampling leaves."""
        out = []
        owner, local = self.ps.route(hot_vids)
        for r, p in enumerate(self.ps.parts):
            arr = np.full(len(hot_vids), -1, np.int64)
            mine = owner == r
            arr[mine] = local[mine]
            if p.num_halo:
                pos = np.clip(np.searchsorted(p.halo_vids, hot_vids), 0,
                              p.num_halo - 1)
                halo = (p.halo_vids[pos] == hot_vids) & ~mine
                arr[halo] = p.num_solid + pos[halo]
            out.append(arr)
        return out

    def _expandable(self, rank: int):
        """The shard's cache-residency leaf masks, additionally marking
        tier-valid hub vertices as leaves (their layer-k embedding will be
        substituted from the local replica — the widest rows in the graph
        stop being sampled at all)."""
        masks = self.cache.expandable_masks(rank)
        if self.hot is None:
            return masks
        hot_p = self._hot_vid_p[rank]
        for k in range(1, len(masks)):
            if masks[k] is None:
                continue
            sel = hot_p[(hot_p >= 0) & self.hot.valid[k - 1][rank]]
            if len(sel):
                masks[k] = masks[k].copy()
                masks[k][sel] = False
        return masks

    # -- compiled shard_map serve step --------------------------------------
    def _build_step(self):
        cfg = self.cfg
        L = cfg.num_layers
        engine = self.engine
        rounds = self.scfg.round_batch
        with_hot = self.hot is not None
        hot_layers = L if with_hot else 0
        if self._fused:
            from repro.kernels import serve_fused
            serve_fused.require_interpreter()
            fwd = serve_fused.forward
        else:
            fwd = sage_lib.forward if cfg.model == "graphsage" \
                else gat_lib.forward

        def body(params, states, tstates, data, mb, alive):
            sq = lambda t: jax.tree_util.tree_map(lambda a: a[0], t)
            data, mb = sq(data), sq(mb)
            states = [sq(s) for s in states]
            tstates = [sq(s) for s in tstates]
            num_solid = data["num_solid"]
            Pmax = data["vid_o"].shape[0]
            lut = lambda tab, n: jnp.where(
                n >= 0, tab[jnp.clip(n, 0, Pmax - 1)], -1)
            vid_o_nodes = [lut(data["vid_o"], n)
                           for n in mb["layer_nodes"]]
            owner_nodes = [lut(data["owner_p"], n)
                           for n in mb["layer_nodes"]]

            nodes0 = mb["layer_nodes"][0]
            mask0 = mb["node_mask"][0]
            is_halo0 = (nodes0 >= num_solid) & mask0
            Smax = data["features"].shape[0]
            Hmax = data["halo_features"].shape[0]
            # layer 0: solids read their own features, halos the static
            # per-shard mirror — no layer-0 communication at all
            h_sol = data["features"][jnp.clip(nodes0, 0, Smax - 1)]
            h_hal = data["halo_features"][
                jnp.clip(nodes0 - num_solid, 0, Hmax - 1)]
            h0 = jnp.where(is_halo0[:, None], h_hal, h_sol) * mask0[:, None]
            valid0 = mask0

            captured = {}
            hits, lookups, hot_hits = [], [], []
            halo_seen, halo_local = [], []
            halo_fetched, halo_requested = [], []

            def tier_sub(k, h, maskk, already):
                """Local-replica substitution for hub rows the HEC
                missed; a hot row answered here never enters the fetch."""
                if not with_hot:
                    return h, jnp.zeros_like(maskk)
                t_hit, t_emb = hot_lib.tier_lookup(
                    tstates[k - 1], data["hot_vids"], vid_o_nodes[k])
                use = t_hit & maskk & ~already
                return jnp.where(use[:, None], t_emb, h), use

            def hook(k, h, valid):
                if k == 0:
                    return h, valid
                vids = vid_o_nodes[k]
                maskk = mb["node_mask"][k]
                is_halo = (mb["layer_nodes"][k] >= num_solid) & maskk
                # local shard cache first: cached solids AND cached halos
                hit, emb = hec_lib.hec_lookup(states[k - 1], vids)
                hit = hit & maskk
                h = jnp.where(hit[:, None], emb, h)
                # then the hot tier: hub rows read the local replica
                h, hot_hit = tier_sub(k, h, maskk, hit)
                # remaining halo rows travel: the engine's request/response
                # all_to_all pair, answered from the owners' layer-k caches
                # — ONE fused pair for all `rounds` fused segments
                # (layer-0 halo features come from the static per-shard
                # mirror and never travel)
                # the failover health mask rides into the fetch: requests
                # to a dead owner are suppressed (the row falls to the
                # validity-mask drop below) and a dead rank's responder
                # side answers nothing
                need = is_halo & ~hit & ~hot_hit
                h, got, nreq = engine.cache_fetch(states[k - 1], vids,
                                                  owner_nodes[k], need, h,
                                                  rounds=rounds, alive=alive)
                # a halo is valid only if substituted (its local partial
                # compute never aggregated its remote neighborhood)
                valid = ((valid & ~is_halo) | hit | hot_hit | got) & maskk
                hits.append(hit.sum())
                lookups.append(maskk.sum())
                hot_hits.append((is_halo & hot_hit).sum())
                halo_seen.append(is_halo.sum())
                halo_local.append((is_halo & (hit | hot_hit)).sum())
                halo_fetched.append(got.sum())
                halo_requested.append(nreq)
                captured[k] = (h, valid)
                return h, valid

            out, valid = fwd(params, h0, valid0,
                             {"nbr_idx": mb["nbr_idx"]}, dropout=0.0,
                             seed=jnp.uint32(0), halo_hook=hook)
            B = mb["seeds"].shape[0]
            out = out[:B].astype(jnp.float32)
            hitL, embL = hec_lib.hec_lookup(states[L - 1], vid_o_nodes[L])
            hitL = hitL & mb["seed_mask"]
            out = jnp.where(hitL[:, None], embL, out)
            out, hotL = tier_sub(L, out, mb["seed_mask"], hitL)
            out_valid = (valid[:B] | hitL | hotL) & mb["seed_mask"]
            hits.append(hitL.sum())
            lookups.append(mb["seed_mask"].sum())

            # store-back: freshly computed/fetched layer-k embeddings enter
            # THIS shard's cache keyed by VID_o (fetched halos included);
            # hot rows additionally refresh the local tier replica
            new_states = list(states)
            new_t = list(tstates)

            def tier_put(k, vids_k, h_k, valid_k):
                if not with_hot:
                    return
                slot, is_hot = hot_lib.tier_slots(data["hot_vids"], vids_k)
                new_t[k - 1] = hot_lib.tier_store(
                    new_t[k - 1], jnp.where(valid_k & is_hot, slot, -1),
                    h_k)

            for k in range(1, L):
                h_k, valid_k = captured[k]
                vids_k = jnp.where(valid_k, vid_o_nodes[k], -1)
                new_states[k - 1] = hec_lib.hec_store(
                    new_states[k - 1], vids_k, h_k)
                tier_put(k, vid_o_nodes[k], h_k, valid_k)
            vids_L = jnp.where(out_valid, vid_o_nodes[L], -1)
            new_states[L - 1] = hec_lib.hec_store(new_states[L - 1],
                                                  vids_L, out)
            tier_put(L, vid_o_nodes[L], out, out_valid)
            zl = lambda xs: jnp.stack(xs) if xs else jnp.zeros(0, jnp.int32)
            stats = {
                "hits": jnp.stack(hits),
                "lookups": jnp.stack(lookups),
                "halo_l0": is_halo0.sum(),          # mirror-served features
                "halo_seen": zl(halo_seen),         # hidden layers only
                "halo_local": zl(halo_local),
                "halo_fetched": zl(halo_fetched),
                "halo_requested": zl(halo_requested),
                "hot_hits": zl(hot_hits),
            }
            exp = lambda t: jax.tree_util.tree_map(lambda a: a[None], t)
            return (exp(out), exp(out_valid), [exp(s) for s in new_states],
                    [exp(s) for s in new_t], exp(stats))

        shard, repl = P("data"), P()
        if self.scfg.failover:
            # failover step: one extra replicated [R] bool health mask
            def stepf(params, states, tstates, data, mb, alive):
                return body(params, states, tstates, data, mb, alive)
            in_specs = (repl, [shard] * L, [shard] * hot_layers, shard,
                        shard, repl)
        else:
            def stepf(params, states, tstates, data, mb):
                return body(params, states, tstates, data, mb, None)
            in_specs = (repl, [shard] * L, [shard] * hot_layers, shard,
                        shard)
        smapped = jax.shard_map(
            stepf, mesh=self.mesh, in_specs=in_specs,
            out_specs=(shard, shard, [shard] * L, [shard] * hot_layers,
                       shard), check_vma=False)
        return jax.jit(smapped)

    # -- public API ----------------------------------------------------------
    def submit(self, vid: int) -> GNNRequest:
        req = self._admit(vid, len(self.router))
        self.router.enqueue(req)
        return req

    def pump(self) -> int:
        """Serve everything queued; returns shard_map rounds executed
        (each round covers ``round_batch`` fused segments)."""
        R = self.num_ranks
        cap = self.scfg.num_slots * self.scfg.round_batch
        ran = 0
        # pending compute work is held as GROUPS (local_vid, [requests]):
        # with dedup on, queries for the same vertex share one group — one
        # compute slot answers them all (scatter-back at finish time)
        pending: List[List] = [[] for _ in range(R)]
        index: List[dict] = [dict() for _ in range(R)]
        while len(self.router) or any(pending):
            if self.breaker is not None:
                # advance circuit breakers (cooldown-expired ranks get the
                # timed re-probe), then answer queries owned by a
                # still-dead rank from stale replicas right away — a dead
                # shard never stalls the round loop
                self._breaker_tick()
                for r in self.breaker.dead_ranks:
                    if self.router.queues[r]:
                        drained = self.router.drain(
                            r, len(self.router.queues[r]))
                        self._answer_degraded([e[0] for e in drained])
                    if pending[r]:
                        self._answer_degraded(
                            [q for _, reqs in pending[r] for q in reqs])
                        pending[r] = []
                        index[r].clear()
            # fill FULL per-rank microbatches with cache misses: output-cache
            # hits are answered by the stacked fast-path lookup and never
            # occupy a compute slot
            fast: List[List] = [[] for _ in range(R)]
            for r in range(R):
                while self.router.queues[r] and len(pending[r]) < cap:
                    wave = self.router.drain(r, cap - len(pending[r]))
                    if self.scfg.cache.enabled:
                        hits, misses = self._split_fast_path(r, wave)
                        fast[r].extend(hits)
                    else:
                        misses = wave
                    self._absorb(pending[r], index[r], misses)
            for r, misses in enumerate(self._answer_fast_path(fast)):
                self._absorb(pending[r], index[r], misses)  # mirror stale
            if any(pending):
                take = [p[:cap] for p in pending]
                self._run_round(take)
                for r in range(R):
                    for local, _ in take[r]:
                        index[r].pop(local, None)
                    pending[r] = pending[r][cap:]
                ran += 1
        return ran

    def _absorb(self, groups: List, index: dict, entries):
        """Fold routed (request, local_vid) entries into pending groups;
        with dedup on, a repeat vid joins the existing group instead of
        taking a fresh compute slot."""
        for req, local in entries:
            if self.scfg.dedup and local in index:
                index[local][1].append(req)
                self.dedup_merged += 1
            else:
                g = (local, [req])
                groups.append(g)
                if self.scfg.dedup:
                    index[local] = g

    def serve(self, vids: Sequence[int]) -> np.ndarray:
        """Convenience: submit ``vids``, pump, return outputs in order."""
        reqs = [self.submit(v) for v in vids]
        self.pump()
        return np.stack([r.result for r in reqs])

    def update_params(self, params) -> int:
        """Install a new checkpoint; every shard drops its cache — and
        every hot-tier replica — at once."""
        self.params = params
        if self.hot is not None:
            self.hot.on_model_update()
        return self.cache.on_model_update()

    def metrics(self) -> dict:
        out = self.cache.metrics()
        out.update(self._frontend_metrics(len(self.router)))
        out["round_batch"] = self.scfg.round_batch
        if self.hot is not None:
            out.update(self.hot.metrics())
        if self.breaker is not None:
            out["serve_degraded"] = float(self.breaker.any_dead)
            out["dead_ranks"] = list(self.breaker.dead_ranks)
            out["degraded_answers"] = self.degraded_answers
            out["degraded_dropped"] = self.degraded_dropped
        return out

    # -- degraded-mode failover ----------------------------------------------
    def mark_dead(self, rank: int) -> None:
        """Externally declare a rank dead (failed liveness probe, hung
        RPC): its breaker opens immediately, halo traffic to/from it is
        suppressed from the next round, and its owned queries answer
        from stale replicas until the half-open re-probe succeeds."""
        if self.breaker is None:
            raise RuntimeError("mark_dead requires DistServeConfig"
                               "(failover=True)")
        self.breaker.force_open(rank, self.steps_run)
        obs.get().registry.log_event("serve_rank_dead", rank=rank,
                                     round=self.steps_run)
        if self.health:
            self.health.recorder.note("rank_dead", rank=rank,
                                      round=self.steps_run)
        self._publish_mask()

    def record_rank_failure(self, rank: int) -> bool:
        """Count one failure against ``rank``; returns True when the
        accumulated failures reach ``breaker_threshold`` and the breaker
        opens (at which point the rank is treated exactly as
        ``mark_dead``)."""
        if self.breaker is None:
            raise RuntimeError("record_rank_failure requires "
                               "DistServeConfig(failover=True)")
        opened = self.breaker.record_failure(rank, self.steps_run)
        if opened:
            obs.get().registry.log_event("serve_rank_dead", rank=rank,
                                         round=self.steps_run)
            if self.health:
                self.health.recorder.note("rank_dead", rank=rank,
                                          round=self.steps_run)
            self._publish_mask()
        return opened

    def _breaker_tick(self) -> None:
        """Advance every rank's circuit breaker by one serve round: a
        rank OPEN past its cooldown goes HALF_OPEN and gets one timed
        re-probe (``probe_fn``; ``None`` probes succeed).  A passing
        probe closes the breaker — full bit-normal routing resumes next
        round; a failing/hung probe re-opens it for another cooldown."""
        recovered = self.breaker.tick(self.steps_run, probe=self.probe_fn,
                                      timeout_s=self.scfg.probe_timeout_s)
        for r in recovered:
            obs.get().registry.log_event("serve_rank_recovered", rank=r,
                                         round=self.steps_run)
            if self.health:
                self.health.recorder.note("rank_recovered", rank=r,
                                          round=self.steps_run)
        if recovered:
            self._publish_mask()

    def _publish_mask(self) -> None:
        dead = self.breaker.dead_ranks
        obs.set_gauge("serve_degraded", float(bool(dead)))
        obs.set_gauge("serve_dead_ranks", float(len(dead)))

    def _answer_degraded(self, reqs) -> None:
        """Answer queries owned by a dead rank from stale replicas:
        any alive shard whose output cache holds the vertex (residency
        mirrors are host-side, so the scan is free), else any alive
        hot-tier replica.  A query with no replica anywhere finishes
        with a zero vector and ``served_by="degraded_dropped"`` —
        bounded degradation, never a stall."""
        L = self.cfg.num_layers
        dim = serve_layer_dims(self.cfg)[-1]
        alive = [r for r in range(self.num_ranks)
                 if bool(self.breaker.alive[r])]
        for req in reqs:
            vid = req.vid
            src, tier = None, False
            if self.scfg.cache.enabled:
                for r in alive:
                    if self.cache.output_resident(r, vid):
                        src = r
                        break
            if src is None and self.hot is not None:
                for r in alive:
                    if self.hot.output_resident(r, vid):
                        src, tier = r, True
                        break
            if src is None:
                self.degraded_dropped += 1
                obs.count("serve_degraded_dropped")
                self._finish(req, np.zeros(dim, np.float32),
                             "degraded_dropped")
                continue
            vids = np.full((self.num_ranks, 1), -1, np.int32)
            vids[src, 0] = vid
            if tier:
                _, emb = self._tier_lookup(self.hot.states[L - 1],
                                           jnp.asarray(vids))
            else:
                _, emb = self._lookup(self.cache.states[L - 1],
                                      jnp.asarray(vids))
            self.degraded_answers += 1
            obs.count("serve_degraded_answers")
            self._finish(req, np.asarray(emb)[src, 0], "degraded_replica")

    def audit(self, epoch: Optional[int] = None):
        """On-demand exactness audit across every shard: sample cached
        lines per layer (tags are VID_o, so the distributed offline pass's
        global ``[V, d]`` embeddings index directly), recompute exact, and
        publish relative-L2 error — plus the hot-tier replica divergence.
        Shards warmed from the offline pass audit to exactly 0.0."""
        q = self.quality
        assert q is not None, "audit needs DistGNNServeScheduler(quality=...)"
        from repro.serve.gnn.distributed.offline import \
            layerwise_embeddings_dist
        exact = layerwise_embeddings_dist(self.cfg, self.params, self.ps)
        layer_samples = []
        for k in range(self.cache.num_layers):
            vids, cached, ages = self.cache.cached_entries(
                k, sample=q.cfg.audit_samples, rng=q.rng)
            layer_samples.append((k + 1, cached, exact[k][vids], ages))
        hot_samples = None
        if self.hot is not None:
            # per-layer pairs: tier widths differ across layers, so the
            # quality plane concatenates error vectors, not rows
            hot_samples = []
            for k, st in enumerate(self.hot.states):
                vids, vals, _ = hot_lib.tier_entries(st, self.hot.hot_vids)
                if len(vids):
                    hot_samples.append((vals, exact[k][vids]))
            self.hot.publish_ages()
        q.publish_staleness(self.cache.states, layer_of=lambda i: i + 1)
        return q.run_audit(
            self.steps_run if epoch is None else epoch,
            layer_samples, hot_samples=hot_samples, source="serve_dist")

    # -- internals -----------------------------------------------------------
    def _record_rank_round(self, stats: dict, wall_s: float):
        """Per-rank round telemetry: the serve step's sharded stats are
        already on the host (the same transfer `_run_round` consumes), so
        this is pure bookkeeping — rank-labeled registry series + cluster
        views, and one health-plane window per round."""
        reg = obs.get().registry
        if not (reg.enabled or self.health):
            return
        dims = serve_layer_dims(self.cfg)
        sum_layers = lambda a: a.sum(axis=1).astype(np.float64) \
            if a.ndim == 2 and a.shape[1] else np.zeros(self.num_ranks)
        fetched = stats["halo_fetched"]
        # response payload: fetched rows carry the layer-k embedding + a
        # 4-byte vid tag (the comm model's accounting)
        bytes_per_rank = np.zeros(self.num_ranks)
        for i in range(fetched.shape[1] if fetched.ndim == 2 else 0):
            bytes_per_rank += fetched[:, i].astype(np.float64) \
                * (dims[i] * 4 + 4)
        totals = {
            "rank_serve_lookups": sum_layers(stats["lookups"]),
            "rank_serve_hits": sum_layers(stats["hits"]),
            "rank_serve_halo_rows": sum_layers(stats["halo_seen"]),
            "rank_serve_halo_local": sum_layers(stats["halo_local"]),
            "rank_serve_halo_fetched": sum_layers(fetched),
            "rank_serve_halo_requested": sum_layers(stats["halo_requested"]),
            "rank_serve_halo_bytes": bytes_per_rank,
            "rank_serve_hot_hits": sum_layers(stats["hot_hits"]),
            "rank_serve_round_seconds": np.full(self.num_ranks, wall_s),
        }
        if reg.enabled:
            obs.publish_rank_series(reg, totals)
        if self.health:
            self.health.observe_round(totals, wall_s=wall_s,
                                      latency_hist=self.latency)

    def _split_fast_path(self, rank: int, wave):
        """Split a wave into (answerable-without-compute, needs-compute):
        output-cache-resident on the owner, or hot-tier-valid in the
        owner's replica."""
        hits, misses = [], []
        for entry in wave:
            vid = entry[0].vid
            ok = self.cache.output_resident(rank, vid) or (
                self.hot is not None
                and self.hot.output_resident(rank, vid))
            (hits if ok else misses).append(entry)
        return hits, misses

    def _answer_fast_path(self, fast: List[List]) -> List[List]:
        """Stacked ``[R, slots]`` lookups answer every output-cache- or
        tier-resident query without sampling or compute; returns per-rank
        entries the device unexpectedly missed (sent to the compute path,
        never re-queued — no fast-path livelock)."""
        misses: List[List] = [[] for _ in range(self.num_ranks)]
        if not any(fast):
            return misses
        L = self.cfg.num_layers
        slots = self.scfg.num_slots
        for s in range(0, max(len(f) for f in fast), slots):
            chunk = [f[s:s + slots] for f in fast]
            vids = np.full((self.num_ranks, slots), -1, np.int32)
            for r, lst in enumerate(chunk):
                vids[r, :len(lst)] = [e[0].vid for e in lst]
            hit, emb = self._lookup(self.cache.states[L - 1],
                                    jnp.asarray(vids))
            hit, emb = np.asarray(hit), np.asarray(emb)
            t_hit = np.zeros_like(hit)
            if self.hot is not None:
                t_hit, t_emb = self._tier_lookup(self.hot.states[L - 1],
                                                 jnp.asarray(vids))
                t_hit, t_emb = np.asarray(t_hit), np.asarray(t_emb)
            for r, lst in enumerate(chunk):
                for i, entry in enumerate(lst):
                    if hit[r, i]:       # guaranteed by the residency mirror
                        self._finish(entry[0], emb[r, i], "output_cache")
                        self.cache.fast_path_hits += 1
                    elif t_hit[r, i]:   # hub answered from the local replica
                        self._finish(entry[0], t_emb[r, i], "hot_tier")
                        self.hot.fast_path_hits += 1
                    else:
                        misses[r].append(entry)
        return misses

    def _run_round(self, round_groups: List[List]):
        """Sample every shard's ``round_batch`` fused segments, run ONE
        shard_map serve step, scatter each slot's answer back to every
        request in its group."""
        cfg = self.cfg
        NB = self.scfg.round_batch
        slots = self.scfg.num_slots
        t_round0 = time.perf_counter()
        with obs.span("serve_round", rounds=NB):
            with obs.span("serve_sample", microbatch=self._mb_counter):
                blocks = []
                for r in range(self.num_ranks):
                    expandable = self._expandable(r)
                    segs = []
                    for n in range(NB):
                        grp = round_groups[r][n * slots:(n + 1) * slots]
                        seeds = np.array([local for local, _ in grp],
                                         np.int64)
                        rng = np.random.default_rng(
                            [self.scfg.sample_seed, self._mb_counter, r] +
                            ([n] if NB > 1 else []))
                        segs.append(sample_blocks_vectorized(
                            self.ps.parts[r], seeds, cfg.fanouts, rng,
                            slots, expandable=expandable))
                    blocks.append(concat_blocks(segs))
            self._mb_counter += 1
            mb = jax.tree_util.tree_map(jnp.asarray, stack_ranks(blocks))
            states = self.cache.states if self.scfg.cache.enabled \
                else self.cache.init_states()
            tstates = self.hot.states if self.hot is not None else []
            step_span = (obs.span("kernel_serve_fused", rounds=NB)
                         if self._fused else contextlib.nullcontext())
            step_args = (self.params, states, tstates, self.data, mb)
            if self.breaker is not None:
                step_args += (jnp.asarray(self.breaker.alive),)
            with step_span:
                out, out_valid, new_states, new_t, stats = \
                    self._step(*step_args)
            out = np.asarray(out)
            out_valid = np.asarray(out_valid)
            stats = jax.tree_util.tree_map(np.asarray, stats)
            self.cache.record(stats["hits"].sum(0), stats["lookups"].sum(0))
            self.cache.record_halo(stats)
            if self.scfg.cache.enabled:
                self.cache.states = new_states
                self.cache.sync_host()
            if self.hot is not None:
                self.hot.states = new_t
                n_hot = int(stats["hot_hits"].sum())
                self.hot.hot_hits += n_hot
                obs.count("hot_hits", n_hot)
                self.hot.sync_host()
            self.steps_run += 1
            self._record_rank_round(stats, time.perf_counter() - t_round0)
            for r, groups in enumerate(round_groups):
                for i, (local, reqs) in enumerate(groups):
                    if out_valid[r, i]:
                        for req in reqs:
                            self._finish(req, out[r, i], "compute")
                    elif self.breaker is not None and self.breaker.any_dead:
                        # halo starvation under degraded routing: the
                        # row's remote neighborhood lives on a dead rank,
                        # so fall back to stale replicas (or a bounded
                        # zero-vector drop) instead of stalling the round
                        self._answer_degraded(list(reqs))
                    else:
                        raise RuntimeError(
                            f"requests {[q.rid for q in reqs]} "
                            f"(vid {reqs[0].vid}) not served")
