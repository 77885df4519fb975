"""Batched GNN inference scheduler: fixed-shape microbatches over the
on-demand sampler, with HEC-backed reuse of overlapping neighborhoods.

Mirrors the LM scheduler's slot design (``serve/scheduler.py``): per-vertex
inference requests queue up and are packed into microbatches of exactly
``num_slots`` seeds, so the compiled ``serve_step`` shape never changes.
Each microbatch:

  1. **cache-aware sampling** (host): the queue is drained against the
     serving cache's residency mirror — queries whose *output* embedding is
     resident skip sampling and compute entirely (answered by a tiny
     fixed-shape lookup step); the rest are sampled with
     ``sample_blocks_vectorized(expandable=...)`` so any vertex whose
     layer-k embedding is resident becomes a leaf, exactly as training
     treats halo vertices,
  2. **serve_step** (device, one compiled program): forward through the
     model with a per-layer hook that substitutes cached embeddings
     (device-side ``hec_lookup``), then stores every freshly computed
     layer-k embedding back (``hec_store``), returning outputs + hit/miss
     counters + the updated cache states,
  3. **residency sync** (host): the authoritative device tags are mirrored
     back so the next microbatch's sampling sees the new contents.

All lookups of a microbatch read the cache state at step entry and all
stores happen after the forward, so a leaf decided at sampling time is
always backed by a device hit — OCF eviction can never strand a leaf.

``update_params`` installs a new checkpoint and bumps the cache's model
version, dropping every cached embedding (they are functions of the
parameters).  Single-partition serving; the sharded multi-rank path
(owner routing + serve-side halo all_to_all) lives in
``serve/gnn/distributed/``.

Admission control: ``max_queue_depth`` caps the request queue — ``submit``
raises ``AdmissionRejected`` (the query is rejected with immediate
backpressure, never silently dropped) and per-request enqueue->answer
latency is tracked with p50/p99 in ``metrics()``.

Cross-query neighborhood dedup (``dedup=True``, PR 5): queries for the
same vertex that are pending together are compacted to ONE compute slot
(the sampler's sorted unique-VID compaction already dedups shared
subtrees *within* a microbatch); the slot's answer is scattered back to
every requesting query.  ``dedup_merged`` counts the slots saved.
"""
from __future__ import annotations

import contextlib
import dataclasses
import time
from collections import deque
from typing import List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.cache import hec as hec_lib
from repro.graph.partition import Partition
from repro.models.gnn import gat as gat_lib
from repro.models.gnn import graphsage as sage_lib
from repro.pipeline.vectorized_sampler import sample_blocks_vectorized
from repro.serve.gnn.embedding_cache import ServeCacheConfig, ServingCache
from repro.serve.gnn.offline import serve_layer_dims


@dataclasses.dataclass(frozen=True)
class GNNServeConfig:
    num_slots: int = 64            # seeds per microbatch (compiled shape)
    cache: ServeCacheConfig = dataclasses.field(
        default_factory=ServeCacheConfig)
    sample_seed: int = 0           # base seed of the per-microbatch RNG
    max_queue_depth: Optional[int] = None  # admission cap; None = unbounded
    dedup: bool = False            # cross-query dedup: same-vid queries in
    #                                a microbatch share ONE compute slot
    fused_kernel: bool = False     # fused Pallas serve layer (graphsage
    #                                only; off = composed jnp, byte-identical)


class AdmissionRejected(RuntimeError):
    """Raised by ``submit`` when the queue is at ``max_queue_depth``.

    The query is *rejected*, never silently dropped: the caller gets the
    backpressure signal immediately (retry / shed upstream) instead of an
    unbounded enqueue->answer latency tail."""


class LatencyStats(obs.Histogram):
    """Per-request enqueue->answer latency accumulator (p50/p99 metrics).

    Now just the obs :class:`~repro.obs.registry.Histogram` — the
    bounded-window exact-percentile accumulator both schedulers used to
    duplicate — kept under its old name; ``metrics()`` produces the
    identical latency dict (``tests/test_obs.py`` pins the equivalence)."""


@dataclasses.dataclass
class GNNRequest:
    rid: int
    vid: int
    result: Optional[np.ndarray] = None   # [num_classes] once served
    model_version: int = -1               # version that served it
    served_by: str = ""                   # "output_cache" | "compute"
    t_submit: float = 0.0                 # perf_counter at enqueue
    t_done: float = 0.0                   # perf_counter at answer

    @property
    def done(self) -> bool:
        return self.result is not None


class ServeFrontend:
    """Request lifecycle shared by the single-rank and sharded schedulers:
    admission control, latency stamping, served/rejected counters."""

    def _init_frontend(self):
        self._rid = 0
        self._mb_counter = 0
        self.latency = LatencyStats()
        self.reset_frontend()

    def reset_frontend(self):
        """Zero steps/served/rejected counters and the latency window —
        call between measurement passes (request ids keep advancing and
        queued requests are untouched)."""
        self.steps_run = 0
        self.queries_served = 0
        self.queries_rejected = 0
        self.dedup_merged = 0          # queries answered by a shared slot
        self.latency.reset()

    def _admit(self, vid: int, queue_depth: int) -> GNNRequest:
        """Admission-checked request creation (raises when over the cap)."""
        cap = self.scfg.max_queue_depth
        if cap is not None and queue_depth >= cap:
            self.queries_rejected += 1
            raise AdmissionRejected(
                f"queue at max_queue_depth={cap}; query {int(vid)} rejected")
        req = GNNRequest(rid=self._rid, vid=int(vid),
                         t_submit=time.perf_counter())
        self._rid += 1
        return req

    def _finish(self, req: GNNRequest, result: np.ndarray, served_by: str):
        req.result = result
        req.model_version = self.cache.model_version
        req.served_by = served_by
        req.t_done = time.perf_counter()
        self.latency.observe(req.t_done - req.t_submit)
        obs.observe("serve_latency_s", req.t_done - req.t_submit,
                    subsystem="serve")
        self.queries_served += 1

    def _frontend_metrics(self, queue_depth: int) -> dict:
        out = {"steps_run": self.steps_run,
               "queries_served": self.queries_served,
               "queries_rejected": self.queries_rejected,
               "dedup_merged": self.dedup_merged,
               "queue_depth": queue_depth}
        out.update(self.latency.metrics())
        return out


class GNNServeScheduler(ServeFrontend):
    def __init__(self, cfg, params, part: Partition,
                 serve_cfg: Optional[GNNServeConfig] = None,
                 health: Optional["obs.HealthPlane"] = None,
                 quality: Optional["obs.QualityPlane"] = None):
        assert part.num_halo == 0, "serving is single-partition"
        self.cfg = cfg
        self.scfg = serve_cfg or GNNServeConfig()
        self.part = part
        self.params = params
        # health plane (num_ranks=1 here): SLO-burn detection over the
        # serve latency histogram + flight recording; pure host bookkeeping
        self.health = health \
            if (health is not None and health.enabled) else None
        # quality plane: cache staleness telemetry + the on-demand
        # exactness audit (`audit`); host-side reads only
        self.quality = quality \
            if (quality is not None and quality.enabled) else None
        self.features = jnp.asarray(part.features)
        self.cache = ServingCache(serve_layer_dims(cfg), part.num_solid,
                                  self.scfg.cache)
        self.queue: deque[GNNRequest] = deque()
        self._init_frontend()
        # fused Pallas serve layer — graphsage only, GAT keeps composed jnp
        self._fused = bool(self.scfg.fused_kernel) and cfg.model == "graphsage"
        self._step = self._build_step()
        self._lookup = jax.jit(
            lambda state, vids: hec_lib.hec_lookup(state, vids))

    # -- compiled serve step ------------------------------------------------
    def _build_step(self):
        cfg = self.cfg
        L = cfg.num_layers
        if self._fused:
            from repro.kernels import serve_fused
            serve_fused.require_interpreter()
            fwd = serve_fused.forward
        else:
            fwd = sage_lib.forward if cfg.model == "graphsage" \
                else gat_lib.forward

        def stepf(params, states, features, mb):
            nodes0 = mb["layer_nodes"][0]
            mask0 = mb["node_mask"][0]
            h0 = features[jnp.clip(nodes0, 0, features.shape[0] - 1)] \
                * mask0[:, None]
            valid0 = mask0
            captured = {}
            hits, lookups = [], []

            def hook(k, h, valid):
                if k == 0:
                    return h, valid
                vids = mb["layer_nodes"][k]
                maskk = mb["node_mask"][k]
                hit, emb = hec_lib.hec_lookup(states[k - 1], vids)
                hit = hit & maskk
                h = jnp.where(hit[:, None], emb, h)
                valid = (valid | hit) & maskk
                hits.append(hit.sum())
                lookups.append(maskk.sum())
                captured[k] = (h, valid)
                return h, valid

            out, valid = fwd(params, h0, valid0,
                             {"nbr_idx": mb["nbr_idx"]}, dropout=0.0,
                             seed=jnp.uint32(0), halo_hook=hook)
            B = mb["seeds"].shape[0]
            out = out[:B].astype(jnp.float32)
            seed_vids = mb["seeds"]
            hitL, embL = hec_lib.hec_lookup(states[L - 1], seed_vids)
            hitL = hitL & mb["seed_mask"]
            out = jnp.where(hitL[:, None], embL, out)
            out_valid = (valid[:B] | hitL) & mb["seed_mask"]
            hits.append(hitL.sum())
            lookups.append(mb["seed_mask"].sum())

            # store-back AFTER every lookup: newly computed (or refreshed)
            # layer-k embeddings enter the cache for later microbatches
            new_states = list(states)
            for k in range(1, L):
                h_k, valid_k = captured[k]
                vids_k = jnp.where(valid_k, mb["layer_nodes"][k], -1)
                new_states[k - 1] = hec_lib.hec_store(
                    new_states[k - 1], vids_k, h_k)
            vids_L = jnp.where(out_valid, seed_vids, -1)
            new_states[L - 1] = hec_lib.hec_store(new_states[L - 1], vids_L,
                                                  out)
            stats = {"hits": jnp.stack(hits), "lookups": jnp.stack(lookups)}
            return out, out_valid, new_states, stats

        return jax.jit(stepf)

    # -- host-side microbatch construction ----------------------------------
    def _sample(self, vids: Sequence[int]) -> dict:
        rng = np.random.default_rng(
            [self.scfg.sample_seed, self._mb_counter])
        self._mb_counter += 1
        with obs.span("serve_sample", microbatch=self._mb_counter - 1):
            blocks = sample_blocks_vectorized(
                self.part, np.asarray(vids, np.int64), self.cfg.fanouts,
                rng, self.scfg.num_slots,
                expandable=self.cache.expandable_masks())
        return {
            "seeds": jnp.asarray(blocks.seeds.astype(np.int32)),
            "seed_mask": jnp.asarray(blocks.seed_mask),
            "nbr_idx": [jnp.asarray(x.astype(np.int32))
                        for x in blocks.nbr_idx],
            "layer_nodes": [jnp.asarray(x.astype(np.int32))
                            for x in blocks.layer_nodes],
            "node_mask": [jnp.asarray(x) for x in blocks.node_mask],
        }

    # -- public API ----------------------------------------------------------
    def submit(self, vid: int) -> GNNRequest:
        req = self._admit(vid, len(self.queue))
        self.queue.append(req)
        return req

    def pump(self) -> int:
        """Serve everything queued; returns microbatches executed."""
        ran = 0
        # pending compute work as GROUPS (vid, [requests]): with dedup on,
        # repeat queries for one vertex share ONE compute slot and the
        # answer is scattered back to every request in the group
        pending: List = []
        index: dict = {}
        while self.queue or pending:
            # fill a FULL microbatch with cache misses: output-cache hits
            # are answered inline and never occupy a slot, so warm-cache
            # traffic doesn't run partially-empty compiled steps
            while self.queue and len(pending) < self.scfg.num_slots:
                n = min(len(self.queue),
                        self.scfg.num_slots - len(pending))
                wave = [self.queue.popleft() for _ in range(n)]
                misses = (self._answer_from_output_cache(wave)
                          if self.scfg.cache.enabled else wave)
                for req in misses:
                    if self.scfg.dedup and req.vid in index:
                        index[req.vid][1].append(req)
                        self.dedup_merged += 1
                    else:
                        g = (req.vid, [req])
                        pending.append(g)
                        if self.scfg.dedup:
                            index[req.vid] = g
            if pending:
                take = pending[:self.scfg.num_slots]
                self._run_microbatch(take)
                for vid, _ in take:
                    index.pop(vid, None)
                pending = pending[self.scfg.num_slots:]
                ran += 1
        return ran

    def serve(self, vids: Sequence[int]) -> np.ndarray:
        """Convenience: submit ``vids``, pump, return outputs in order."""
        reqs = [self.submit(v) for v in vids]
        self.pump()
        return np.stack([r.result for r in reqs])

    def update_params(self, params) -> int:
        """Install a new checkpoint; stale cached embeddings are dropped."""
        self.params = params
        return self.cache.on_model_update()

    def metrics(self) -> dict:
        out = self.cache.metrics()
        out.update(self._frontend_metrics(len(self.queue)))
        return out

    def audit(self, epoch: Optional[int] = None):
        """On-demand exactness audit: sample cached lines from every
        serving layer, recompute their exact ``h^k`` with the offline
        layerwise pass, publish relative-L2 error (+ staleness ages).

        Serving stores full-graph-equivalent activations (dropout 0.0,
        cached leaves are themselves exact), so a cache warmed from the
        offline embeddings audits to EXACTLY 0.0 — the fresh-cache pin in
        ``tests/test_quality.py``.  Cache layer ``k`` (0-based) holds
        ``h^{k+1}``; tags are local vids."""
        q = self.quality
        assert q is not None, "audit needs GNNServeScheduler(quality=...)"
        from repro.serve.gnn.offline import layerwise_embeddings
        exact = [np.asarray(e) for e in layerwise_embeddings(
            self.cfg, self.params, self.part)]
        layer_samples = []
        for k in range(self.cache.num_layers):
            vids, cached, ages = self.cache.cached_entries(
                k, sample=q.cfg.audit_samples, rng=q.rng)
            layer_samples.append((k + 1, cached, exact[k][vids], ages))
        q.publish_staleness(self.cache.states,
                            layer_of=lambda i: i + 1)
        return q.run_audit(
            self.steps_run if epoch is None else epoch,
            layer_samples, source="serve")

    # -- internals -----------------------------------------------------------
    def _answer_from_output_cache(self, wave: List[GNNRequest]):
        """Answer output-cache-resident queries without sampling or compute;
        returns the requests that still need a microbatch."""
        L = self.cfg.num_layers
        flags = self.cache.resident[L - 1]
        candidates = [r for r in wave if flags[r.vid]]
        misses = [r for r in wave if not flags[r.vid]]
        if candidates:
            vids = np.full(self.scfg.num_slots, -1, np.int32)
            vids[:len(candidates)] = [r.vid for r in candidates]
            hit, emb = self._lookup(self.cache.states[L - 1],
                                    jnp.asarray(vids))
            hit, emb = np.asarray(hit), np.asarray(emb)
            for i, r in enumerate(candidates):
                if hit[i]:              # guaranteed by the residency mirror
                    self._finish(r, emb[i], "output_cache")
                    self.cache.fast_path_hits += 1
                else:                   # defensive: mirror out of sync
                    misses.append(r)
        return misses

    def _run_microbatch(self, groups: List):
        """One compiled step over the groups' unique vids; every request
        in a group receives the same slot's answer (dedup scatter-back)."""
        t_round0 = time.perf_counter()
        with obs.span("serve_round", slots=len(groups)):
            mb = self._sample([vid for vid, _ in groups])
            states = self.cache.states
            if not self.scfg.cache.enabled:
                # baseline mode: every microbatch sees an empty cache, so
                # "disabled" really is pure on-demand sampling + compute
                states = self.cache.init_states()
            step_span = (obs.span("kernel_serve_fused", slots=len(groups))
                         if self._fused else contextlib.nullcontext())
            with step_span:
                out, out_valid, new_states, stats = self._step(
                    self.params, states, self.features, mb)
            out = np.asarray(out)
            out_valid = np.asarray(out_valid)
            self.cache.record(np.asarray(stats["hits"]),
                              np.asarray(stats["lookups"]))
            if self.scfg.cache.enabled:
                self.cache.states = new_states
                self.cache.sync_host()
            self.steps_run += 1
            for i, (vid, reqs) in enumerate(groups):
                assert out_valid[i], \
                    f"requests {[q.rid for q in reqs]} (vid {vid}) not served"
                for req in reqs:
                    self._finish(req, out[i], "compute")
        if self.health:
            wall = time.perf_counter() - t_round0
            self.health.observe_round(
                {"rank_serve_lookups":
                     np.asarray([float(np.asarray(stats["lookups"]).sum())]),
                 "rank_serve_hits":
                     np.asarray([float(np.asarray(stats["hits"]).sum())]),
                 "rank_serve_round_seconds": np.asarray([wall])},
                wall_s=wall, latency_hist=self.latency)
