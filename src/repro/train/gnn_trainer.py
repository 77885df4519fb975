"""Distributed minibatch GNN training (paper Algorithms 1 & 2).

One shard_map shard on mesh axis "data" == one paper "rank".  Per rank:
graph partition, per-layer HECs, exchange-plan tables — stacked [R, ...]
arrays sharded on the leading axis.  Model params are replicated;
gradients are psum'ed (the paper's blocking All-Reduce).

All halo communication goes through ``repro.comm.HaloExchangeEngine``
over a static :class:`~repro.comm.plan.ExchangePlan` built once per
partitioning: the Asynchronous Embedding Push (one fused all_to_all whose
result is carried in a delay-``d`` in-flight buffer and HECStore'd at step
k+d — the exact bounded-staleness semantics of the paper's MPI
AlltoallAsync + comm_wait), and the sync-baseline blocking fetch.  With
``overlap=True`` (default, the paper's scheme) the push is dispatched
between the forward and backward passes so XLA overlaps the collective
with backward compute; ``overlap=False`` pushes inline after the backward.
Both modes move identical bits, so model params bit-match
(pinned in ``tests/test_comm.py``).

Modes:
  aep  — paper: HEC + delayed push (DistGNN-MB)
  sync — DistDGL-like baseline: fresh layer-0 halo features fetched with a
         blocking request/response all_to_all pair every iteration
  drop — LLCG-like: cut edges ignored (halos invalid everywhere)

Minibatches have one source, ``repro.pipeline.MinibatchPipeline``
(vectorized host CSR sampler -> background prefetch -> double-buffered
staging, paper §3.3/§3.4 overlap), for training and evaluation alike.
"""
from __future__ import annotations

import contextlib
import dataclasses
import functools
import time
from typing import List, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro import obs
from repro.cache import hec as hec_lib
from repro.cache import hot_tier as hot_lib
from repro.comm.engine import HaloExchangeEngine, row_vids
from repro.comm.plan import _pad_stack, build_exchange_plan
from repro.configs.gnn import GNNConfig
from repro.graph.partition import PartitionSet
from repro.graph.sampling import layer_capacities
from repro.pipeline.staging import MinibatchPipeline
from repro.resilience.inject import CODE_NAN_STEP
from repro.models.gnn import gat as gat_lib
from repro.models.gnn import graphsage as sage_lib
from repro.train import optimizer as opt_lib


# ---------------------------------------------------------------------------
# host-side data preparation
# ---------------------------------------------------------------------------
def rank_sharding(mesh) -> NamedSharding:
    """Leading ``[R, ...]`` axis split over the mesh: one rank per device."""
    return NamedSharding(mesh, P("data"))


def build_dist_data(ps: PartitionSet, cfg: GNNConfig, mesh=None) -> dict:
    """Stacked per-rank device tables: features/labels/id maps plus the
    static exchange-plan tables (db_halo, push_mask, sorted owner tables,
    and — when ``cfg.hec.hot_size`` — the hot-set tables) the
    ``HaloExchangeEngine`` consumes — all computed once per partitioning,
    never per step.  With a ``mesh`` each rank's rows go straight to its
    own device."""
    sharding = rank_sharding(mesh) if mesh is not None else None
    plan_tables = build_exchange_plan(
        ps, host_indices=False,
        hot_size=cfg.hec.hot_size).device_tables(sharding)
    host = {
        "features": _pad_stack([p.features for p in ps.parts], 0.0),
        "labels": _pad_stack([p.labels.astype(np.int32) for p in ps.parts],
                             0),
        "num_solid": np.array([p.num_solid for p in ps.parts], np.int32),
        "vid_o": _pad_stack([p.vid_p_to_o().astype(np.int32)
                             for p in ps.parts], -1),
    }
    return {**jax.device_put(host, sharding), **plan_tables}


def has_halos(dist_data) -> bool:
    """Whether any rank holds a halo vertex: a rank's ``vid_o`` rows past
    its solids are its halos.  Read once from the host, when the step is
    built; without ``dist_data`` the answer is yes, which is always exact
    (probing a layer with no halos substitutes nothing)."""
    if dist_data is None:
        return True
    rows = (np.asarray(dist_data["vid_o"]) >= 0).sum(axis=1)
    return bool((rows > np.asarray(dist_data["num_solid"])).any())


def substitute_halos(h, nodes, is_halo, data, hec, hot, life_span):
    """Replace a layer's halo rows of ``h`` by fresh hot-tier replicas,
    else by HEC hits, probing every row by its VID_o key; returns
    ``(h, use, use_hot)``, the rows the HEC and the tier served.  ``hot``
    is the layer's tier state or ``None``."""
    with jax.named_scope("hec_lookup"):
        keys = row_vids(data["vid_o"], nodes)
        dim = h.shape[1]
        if hot is not None:
            t_hit, t_emb = hot_lib.tier_lookup(hot, data["hot_vids"], keys,
                                               life_span)
            use_hot = is_halo & t_hit
            h = jnp.where(use_hot[:, None], t_emb[:, :dim], h)
        else:
            use_hot = jnp.zeros_like(is_halo)
        hit, emb = hec_lib.hec_lookup(hec, keys)
        use = is_halo & hit & ~use_hot
        h = jnp.where(use[:, None], emb[:, :dim], h)
    return h, use, use_hot


def _epoch_mean(ep_metrics):
    """Aggregate per-step metrics: loss/acc weighted by real example count
    (padded empty batches contribute zero weight), counters plain-averaged.
    Per-epoch cache hit rates are derived by the obs registry's sum-ratio
    aggregation (``repro.obs.hit_rate_metrics``): epoch-summed hits over
    epoch-summed halos — ``hec_hit_rate_l{l}`` for the HEC, and, when the
    replicated hot tier is on, ``hot_hit_rate_l{l}`` (fraction of halo
    rows the local replica served — hot hits share the halo denominator,
    so HEC + hot rates compose to the total locally-served fraction)."""
    if not ep_metrics:                   # zero-step epoch: no train seeds
        return {"examples": 0.0, "loss": 0.0, "acc": 0.0}
    w = np.array([m.get("examples", 1.0) for m in ep_metrics], np.float64)
    total = w.sum()
    out = {}
    for key in ep_metrics[0]:
        vals = np.array([m[key] for m in ep_metrics], np.float64)
        if key in ("loss", "acc"):
            out[key] = float((vals * w).sum() / max(total, 1.0))
        elif key == "examples":
            out[key] = float(total)
        else:
            out[key] = float(vals.mean())
    # epoch-local registry: counters sum across steps, rates derive once
    # (independent of the process-wide obs config — these rates are part
    # of the training history contract, not optional telemetry)
    reg = obs.MetricsRegistry(enabled=True)
    for m in ep_metrics:
        for key, v in m.items():
            if key.startswith(("hec_hits_l", "hec_halos_l", "hot_hits_l")):
                reg.counter(key).inc(v)
    out.update(obs.hit_rate_metrics(reg))
    return out


# ---------------------------------------------------------------------------
# model dispatch
# ---------------------------------------------------------------------------
def init_model_params(key, cfg: GNNConfig):
    if cfg.model == "graphsage":
        return sage_lib.init_params(key, cfg.feat_dim, cfg.hidden_size,
                                    cfg.num_classes, cfg.num_layers)
    return gat_lib.init_params(key, cfg.feat_dim, cfg.hidden_size,
                               cfg.num_classes, cfg.num_layers, cfg.num_heads)


def _forward(cfg, params, h0, valid0, blocks, dropout, seed, halo_hook,
             use_kernel=False):
    fwd = sage_lib.forward if cfg.model == "graphsage" else gat_lib.forward
    return fwd(params, h0, valid0, blocks, dropout=dropout, seed=seed,
               halo_hook=halo_hook, use_kernel=use_kernel)


def layer_dims(cfg: GNNConfig) -> List[int]:
    """Embedding dim held in HEC_l for l = 0..L-1 (inputs + hidden)."""
    hid = cfg.hidden_size if cfg.model == "graphsage" \
        else cfg.hidden_size * cfg.num_heads
    return [cfg.feat_dim] + [hid] * (cfg.num_layers - 1)


# ---------------------------------------------------------------------------
# the trainer
# ---------------------------------------------------------------------------
@dataclasses.dataclass
class DistTrainer:
    cfg: GNNConfig
    mesh: object
    num_ranks: int
    mode: str = "aep"           # aep | sync | drop
    use_kernel: bool = False
    overlap: bool = True        # aep: dispatch push before the backward pass
    engine: Optional[HaloExchangeEngine] = None
    # cluster health plane (obs.HealthPlane): per-rank epoch aggregation,
    # straggler/skew/drift detectors, flight-recorder dump when a detector
    # fires or an exception escapes the step loop.  Host-side only — the
    # compiled step is identical with or without it.
    health: Optional["obs.HealthPlane"] = None
    # embedding quality plane (obs.QualityPlane): HEC/hot-tier staleness
    # telemetry + convergence series every epoch, and — when its
    # audit_interval is armed — the online exactness audit (`audit`).
    # Host-side reads of existing state with its own RNG, so the training
    # trajectory is bit-identical with the plane off or on.
    quality: Optional["obs.QualityPlane"] = None
    # resilience plane (repro.resilience.ResiliencePlane): epoch-boundary
    # checkpoints of the full state pytree, scheduled fault injection, and
    # the NaN/Inf step guard.  When it is *step-armed* (nan_guard or a
    # fault schedule) the compiled step takes one extra per-rank int32
    # fault-code input and routes the param/opt update through a
    # finite-guard select; with all-zero codes every select takes the
    # same branch, so a clean armed run computes identical bits — and a
    # plane that only checkpoints leaves the step untouched entirely.
    resilience: Optional["object"] = None

    def __post_init__(self):
        if self.engine is None:
            self.engine = HaloExchangeEngine(
                self.num_ranks, self.cfg.num_layers,
                self.cfg.hec.push_limit, self.cfg.hec.delay,
                hot_budget=self.cfg.hec.hot_budget)

    def init_state(self, key, dist_data=None):
        cfg = self.cfg
        R = self.num_ranks
        # replicated exactly as the step returns them, so the step's
        # first call compiles the same program as every later one
        params = init_model_params(key, cfg)
        params, opt_state = jax.device_put(
            (params, opt_lib.adam_init(params)), NamedSharding(self.mesh, P()))
        dims = layer_dims(cfg)

        def per_rank(make):
            """[R, ...] stack of make()'s pytree, each rank's slice built
            on its own device (no device ever holds another rank's)."""
            return jax.jit(lambda: jax.vmap(lambda _: make())(jnp.arange(R)),
                           out_shardings=rank_sharding(self.mesh))()

        hec = [per_rank(functools.partial(hec_lib.hec_init, cfg.hec.cache_size,
                                          cfg.hec.ways, dims[l]))
               for l in range(cfg.num_layers)]
        # replicated hot-vertex tier: one [R, K, dim] replica stack per
        # layer, alive only when the plan derived a non-empty hot set (a
        # partitioning with no halos has no communication tail to cut)
        hot = []
        if self.engine.hot_budget and self.mode != "aep":
            self.engine.hot_budget = 0     # the tier is an AEP mechanism
        elif self.engine.hot_budget:
            if dist_data is None:
                # build_dist_data already stripped hot vids from the
                # pairwise push contract; silently training without the
                # tier would leave hub halos served by NEITHER mechanism
                raise ValueError(
                    "hec.hot_size/hot_budget are enabled: init_state "
                    "needs dist_data (build_dist_data(ps, cfg)) so the "
                    "tier replicas match the plan's hot tables")
            if "hot_vids" not in dist_data:
                # the plan found no hot candidates (no halos), so the
                # push contract was not filtered either: tier off is safe
                self.engine.hot_budget = 0
            else:
                K = dist_data["hot_vids"].shape[1]
                # each rank refreshes only hubs it OWNS, so the binding
                # constraint is the busiest owner, not the aggregate
                owned_max = int(np.asarray(
                    dist_data["hot_mine"]).sum(axis=1).max())
                if cfg.hec.hot_budget * cfg.hec.life_span < owned_max:
                    import warnings
                    warnings.warn(
                        f"hot tier refresh budget is undersized: the "
                        f"busiest rank owns {owned_max} of {K} hot "
                        f"vertices but can refresh only hot_budget*"
                        f"life_span = "
                        f"{cfg.hec.hot_budget * cfg.hec.life_span} per "
                        f"staleness window; unrefreshed replicas go "
                        f"stale and those hub halos degrade like HEC "
                        f"misses (dropped from aggregation)")
                hot = [per_rank(functools.partial(hot_lib.tier_init, K,
                                                  dims[l]))
                       for l in range(cfg.num_layers)]
        inflight = jax.jit(lambda: self.engine.inflight_init(max(dims)),
                           out_shardings=rank_sharding(self.mesh))()
        return {"params": params, "opt_state": opt_state, "hec": hec,
                "hot": hot, "inflight": inflight,
                "step": jnp.zeros((), jnp.int32)}

    # -- per-rank step body (inside shard_map) ------------------------------
    def _rank_step(self, params, opt_state, hec, hot, inflight, data, mb,
                   seed, fault=None, *, probe_halos):
        """One rank's training step.  ``probe_halos`` (static,
        :func:`has_halos`) False compiles no HEC or tier probe at all:
        with no halo on any rank none could substitute a row."""
        cfg = self.cfg
        L = cfg.num_layers
        dims = layer_dims(cfg)
        dmax = max(dims)
        me = jax.lax.axis_index("data")

        sq = lambda t: jax.tree_util.tree_map(lambda a: a[0], t)
        data, mb = sq(data), sq(mb)
        hec = [sq(h) for h in hec]
        hot = [sq(h) for h in hot]
        inflight = sq(inflight)
        fcode = sq(fault) if fault is not None else None

        num_solid = data["num_solid"]

        # (1) HEC tick + consume the delayed push (paper lines 8-9); the
        # hot tier ticks/consumes its broadcast segment the same way
        if self.mode == "aep":
            with jax.named_scope("hec_store"):
                if hot:
                    hec, hot = self.engine.consume_push(
                        hec, inflight, dims, cfg.hec.life_span, hot=hot)
                else:
                    hec = self.engine.consume_push(hec, inflight, dims,
                                                   cfg.hec.life_span)

        # (2) layer-0 inputs
        nodes0 = mb["layer_nodes"][0]
        mask0 = mb["node_mask"][0]
        is_halo0 = (nodes0 >= num_solid) & mask0
        with jax.named_scope("feature_gather"):
            solid_idx = jnp.clip(nodes0, 0, data["features"].shape[0] - 1)
            h0 = data["features"][solid_idx] * (mask0 & ~is_halo0)[:, None]
        valid0 = mask0 & ~is_halo0

        def halo_sub(k, h, nodes, is_halo):
            if not probe_halos:
                none = jnp.zeros_like(is_halo)
                return h, none, none
            return substitute_halos(h, nodes, is_halo, data, hec[k],
                                    hot[k] if hot else None,
                                    cfg.hec.life_span)

        zero = jnp.zeros((), jnp.int32)
        if self.mode == "aep":
            h0, use0, use_hot0 = halo_sub(0, h0, nodes0, is_halo0)
            valid0 = valid0 | use0 | use_hot0
            hits0 = (jnp.sum(use0 | use_hot0), jnp.sum(is_halo0),
                     jnp.sum(use_hot0))
        elif self.mode == "sync":
            h0, got = self.engine.sync_fetch(
                data, row_vids(data["vid_o"], nodes0), is_halo0, h0)
            valid0 = valid0 | got
            hits0 = (got.sum(), jnp.sum(is_halo0), zero)
        else:
            hits0 = (zero, jnp.sum(is_halo0), zero)

        if fcode is not None:
            # nan_step fault: poison this rank's layer-0 activations AFTER
            # every cache substitution, so the whole forward/backward goes
            # non-finite and the step guard below must contain it.  A
            # clean rank multiplies by 1.0 — bit-identity preserved.
            h0 = h0 * jnp.where((fcode & CODE_NAN_STEP) != 0,
                                jnp.float32(jnp.nan), jnp.float32(1.0))

        def loss_fn(params):
            captured = {}
            hits = [hits0]

            def halo_hook(k, h, valid):
                if k == 0:
                    captured[0] = (h, valid)
                    return h, valid
                nodes_k = mb["layer_nodes"][k]
                maskk = mb["node_mask"][k]
                is_halo = (nodes_k >= num_solid) & maskk
                if self.mode == "aep" and k < L:
                    h, use, use_hot = halo_sub(k, h, nodes_k, is_halo)
                    valid = (valid & ~is_halo) | use | use_hot
                    hits.append((jnp.sum(use | use_hot), jnp.sum(is_halo),
                                 jnp.sum(use_hot)))
                else:
                    valid = valid & ~is_halo
                if k < L:
                    captured[k] = (h, valid)
                return h, valid

            blocks = {"nbr_idx": mb["nbr_idx"]}
            out, valid = _forward(cfg, params, h0, valid0, blocks,
                                  cfg.dropout, seed, halo_hook,
                                  self.use_kernel)
            with jax.named_scope("loss"):
                B = mb["seeds"].shape[0]
                logits = out[:B].astype(jnp.float32)
                lmask = mb["seed_mask"] & valid[:B]
                labels = mb["labels"]
                logz = jax.scipy.special.logsumexp(logits, -1)
                gold = jnp.take_along_axis(logits, labels[:, None], -1)[:, 0]
                nll = (logz - gold) * lmask
                n_valid = lmask.sum()
                loss = nll.sum() / jnp.maximum(n_valid, 1)
                correct = ((jnp.argmax(logits, -1) == labels) & lmask).sum()
            return loss, (nll.sum(), correct, n_valid, captured, hits)

        # (3) backward + AEP push (paper lines 14-24).  The push depends
        # only on forward activations, so with overlap=True it is
        # dispatched BETWEEN the forward and backward passes (the paper's
        # AlltoallAsync-then-comm_wait): XLA overlaps the collective with
        # backward compute.  overlap=False keeps the legacy inline push
        # after the backward — both move identical bits, so model params
        # bit-match across the two schedules.
        push_stats = None
        if self.mode == "aep" and self.overlap:
            loss, vjp_fn, (nll_sum, correct, n_valid, captured, hits) = \
                jax.vjp(loss_fn, params, has_aux=True)
            inflight, push_stats = self.engine.aep_push(
                data, mb, captured, num_solid, inflight, seed, dims, dmax,
                me, fault_code=fcode)
            grads, = vjp_fn(jnp.ones_like(loss))
        else:
            (loss, (nll_sum, correct, n_valid, captured, hits)), grads = \
                jax.value_and_grad(loss_fn, has_aux=True)(params)
            if self.mode == "aep":
                inflight, push_stats = self.engine.aep_push(
                    data, mb, captured, num_solid, inflight, seed, dims,
                    dmax, me, fault_code=fcode)
        # per-rank telemetry shard: the pre-psum values, captured BEFORE the
        # cross-rank reductions below and returned as one extra sharded
        # output.  The host reads it with the metrics it already transfers
        # every step — no new collectives — and the output is emitted
        # unconditionally, so the compiled program (and the computed
        # numerics) are identical with the health plane on or off.
        rank_stats = {
            "rank_examples": n_valid,
            "rank_sample_rows": sum(m.sum() for m in mb["node_mask"]),
            "rank_halo_rows": sum(t for _, t, _ in hits),
            "rank_hec_hits": sum(h for h, _, _ in hits),
        }
        if hot:
            rank_stats["rank_hot_hits"] = sum(c for _, _, c in hits)
        if push_stats is not None:
            rank_stats["rank_push_rows"] = push_stats["push_rows"]
            rank_stats["rank_push_bytes"] = push_stats["push_bytes"]
        rank_stats = {k: jnp.asarray(v, jnp.float32)
                      for k, v in rank_stats.items()}

        # gradients and metrics are example-weighted across ranks, so ranks
        # padded with an empty seed batch (epoch-length imbalance) neither
        # dilute the update toward zero nor skew the numbers: the all-reduce
        # yields the gradient of the *global* batch mean
        examples = jax.lax.psum(n_valid, "data")
        denom = jnp.maximum(examples, 1)
        weight = n_valid.astype(jnp.float32)
        denom_f = jnp.maximum(examples.astype(jnp.float32), 1.0)
        grads = jax.tree_util.tree_map(
            lambda g: jax.lax.psum(g * weight, "data") / denom_f, grads)
        loss_m = jax.lax.psum(nll_sum, "data") / denom
        acc_m = jax.lax.psum(correct, "data") / denom

        with jax.named_scope("optimizer"):
            new_params, new_opt, diag = opt_lib.adam_update(
                grads, opt_state, params,
                opt_lib.AdamConfig(lr=cfg.lr, grad_clip=1.0))
        grad_norm = diag["grad_norm"]
        skipped = None
        if fcode is None:
            params, opt_state = new_params, new_opt
        else:
            # NaN/Inf step guard: loss and grads are already psum'ed, so
            # `ok` is uniform across ranks — either every rank applies
            # this minibatch's update or every rank skips it.  A clean
            # step selects the `new` branch everywhere, bit-exactly.
            ok = jnp.isfinite(loss_m)
            for g in jax.tree_util.tree_leaves(grads):
                ok = ok & jnp.isfinite(g).all()
            sel = lambda n, o: jnp.where(ok, n, o)
            params = jax.tree_util.tree_map(sel, new_params, params)
            opt_state = jax.tree_util.tree_map(sel, new_opt, opt_state)
            loss_m = jnp.where(ok, loss_m, 0.0)
            acc_m = jnp.where(ok, acc_m, 0.0)
            examples = jnp.where(ok, examples, 0)
            grad_norm = jnp.where(ok, grad_norm, 0.0)
            skipped = 1.0 - ok.astype(jnp.float32)

        metrics = {"loss": loss_m, "acc": acc_m, "examples": examples,
                   "grad_norm": grad_norm}
        if skipped is not None:
            metrics["skipped"] = skipped
        if push_stats is not None:
            metrics["aep_push_rows"] = jax.lax.psum(
                push_stats["push_rows"], "data")
            metrics["aep_push_bytes"] = jax.lax.psum(
                push_stats["push_bytes"], "data")
            if "hot_push_rows" in push_stats:
                metrics["hot_push_rows"] = jax.lax.psum(
                    push_stats["hot_push_rows"], "data")
        for l, (h_cnt, t_cnt, hot_cnt) in enumerate(hits):
            metrics[f"hec_hits_l{l}"] = jax.lax.psum(h_cnt, "data")
            metrics[f"hec_halos_l{l}"] = jax.lax.psum(t_cnt, "data")
            if hot:
                metrics[f"hot_hits_l{l}"] = jax.lax.psum(hot_cnt, "data")
        for l in range(L):
            metrics[f"hec_occ_l{l}"] = jax.lax.pmean(
                hec_lib.hec_occupancy(hec[l]), "data")

        exp = lambda t: jax.tree_util.tree_map(lambda a: a[None], t)
        return (params, opt_state, [exp(h) for h in hec],
                [exp(h) for h in hot], exp(inflight), exp(rank_stats),
                metrics)

    # -- public API ----------------------------------------------------------
    def _pipeline(self, ps, seed0):
        """The minibatch source: the host sampler behind prefetch and
        double-buffered staging, seeded by ``seed0``."""
        inj = getattr(self.resilience, "injector", None) \
            if self.resilience is not None else None
        return MinibatchPipeline(ps, self.cfg, base_seed=seed0,
                                 mesh=self.mesh, injector=inj)

    def make_step(self, dist_data=None, donate=True):
        cfg = self.cfg
        shard = P("data")
        repl = P()
        # the tier adds one sharded state list when enabled (init_state
        # clears engine.hot_budget when the plan has no hot set, so build
        # the step after init_state)
        hot_layers = cfg.num_layers \
            if (self.mode == "aep" and self.engine.hot_budget) else 0
        armed = self.resilience is not None \
            and getattr(self.resilience, "step_armed", False)
        # no rank holds a halo (one partition): the step probes nothing
        probe = has_halos(dist_data)
        if self.mode == "aep":
            rows = layer_capacities(cfg.batch_size, cfg.fanouts)
            for k in range(cfg.num_layers):
                obs.set_gauge("hec_probe_rows", rows[k] if probe else 0,
                              layer=k)

        if armed:
            # step-armed resilience: one extra sharded [R] int32 fault-code
            # input (see repro.resilience.inject); zero codes compute the
            # exact bits of the unarmed step
            def stepf(params, opt_state, hec, hot, inflight, data, mb,
                      seed, fault):
                return self._rank_step(params, opt_state, hec, hot,
                                       inflight, data, mb, seed, fault,
                                       probe_halos=probe)
            in_specs = (repl, repl, [shard] * cfg.num_layers,
                        [shard] * hot_layers, shard, shard, shard, repl,
                        shard)
        else:
            def stepf(params, opt_state, hec, hot, inflight, data, mb,
                      seed):
                return self._rank_step(params, opt_state, hec, hot,
                                       inflight, data, mb, seed,
                                       probe_halos=probe)
            in_specs = (repl, repl, [shard] * cfg.num_layers,
                        [shard] * hot_layers, shard, shard, shard, repl)

        smapped = jax.shard_map(
            stepf, mesh=self.mesh,
            in_specs=in_specs,
            out_specs=(repl, repl, [shard] * cfg.num_layers,
                       [shard] * hot_layers, shard, shard, repl),
            check_vma=False)
        return jax.jit(smapped,
                       donate_argnums=(1, 2, 3, 4) if donate else ())

    def train_epochs(self, ps, dist_data, state, num_epochs, seed0=0,
                     step_fn=None, log_every=0, start_epoch=0):
        """Train for ``num_epochs`` (epochs ``start_epoch`` onward).

        Minibatches come from a ``MinibatchPipeline`` seeded by ``seed0``.
        Ranks with fewer minibatches than the epoch maximum contribute empty
        (fully masked) batches; metrics count only real examples.

        ``start_epoch`` is the crash-resume entry point: every minibatch
        is a pure function of ``(seed0, epoch, step)``, so restoring the
        epoch-``k`` checkpoint and continuing with ``start_epoch=k+1``
        replays the exact sampler streams of the uninterrupted run.
        """
        cfg = self.cfg
        pipeline = self._pipeline(ps, seed0)
        step_fn = step_fn or self.make_step(dist_data)
        history = []
        step_idx = int(state["step"])
        reg = obs.get().registry
        phases = ("sample", "host_prep", "stage", "step")
        phase_at = lambda: {p: reg.value("phase_seconds", phase=p)
                            for p in phases}
        # per-rank telemetry: the step's sharded rank_stats output is
        # accumulated host-side per epoch, published as rank-labeled
        # registry series + cluster views, and fed to the health-plane
        # detectors.  Pure host bookkeeping — the step itself is identical
        # whether anyone reads rank_stats or not.
        health = self.health \
            if (self.health is not None and self.health.enabled) else None
        quality = self.quality \
            if (self.quality is not None and self.quality.enabled) else None
        acc = obs.RankAccumulator(self.num_ranks) \
            if (reg.enabled or health) else None
        guard = health.guard("train_step_loop") if health \
            else contextlib.nullcontext()
        rz = self.resilience
        armed = rz is not None and getattr(rz, "step_armed", False)
        with guard:
            for ep in range(start_epoch, start_epoch + num_epochs):
                ph0, wall0 = phase_at(), time.perf_counter()
                # the loop thread's spans tile the epoch: epoch_fill (up to
                # the first minibatch in hand), then per step the step span
                # and the batch_wait for the next minibatch, then epoch_end
                with obs.span("epoch_fill", epoch=ep):
                    mb_iter = pipeline.epoch_batches(ep)
                    mb = next(mb_iter, None)
                ep_metrics = []
                t_step_ep = 0.0
                k_ep = 0
                while mb is not None:
                    # the span covers dispatch AND the blocking host
                    # transfer of the metrics — i.e. the device step's wall
                    # time as seen by the training loop
                    ts0 = time.perf_counter()
                    # scheduled fault codes for this (epoch, step-in-epoch)
                    # — zeros (the bit-identical clean path) unless a
                    # FaultSchedule entry matches; delay_rank faults sleep
                    # inside step_codes
                    fargs = (jnp.asarray(
                        rz.step_codes(ep, k_ep, self.num_ranks)),) \
                        if armed else ()
                    with obs.span("step", epoch=ep, step=step_idx):
                        (state["params"], state["opt_state"], state["hec"],
                         state["hot"], state["inflight"], rank_stats,
                         metrics) = step_fn(
                            state["params"], state["opt_state"],
                            state["hec"], state["hot"], state["inflight"],
                            dist_data, mb, jnp.uint32(step_idx), *fargs)
                        # one device-to-host transfer for everything the
                        # host reads of the step
                        with obs.span("step_sync"):
                            metrics, rank_stats = jax.device_get(
                                (metrics,
                                 rank_stats if acc is not None else None))
                        ep_metrics.append(
                            {k_: float(v) for k_, v in metrics.items()})
                    t_step_ep += time.perf_counter() - ts0
                    if armed:
                        rz.on_step(ep, k_ep,
                                   ep_metrics[-1].get("skipped", 0.0))
                    if acc is not None:
                        acc.add(rank_stats)
                    step_idx += 1
                    k_ep += 1
                    with obs.span("batch_wait", epoch=ep):
                        mb = next(mb_iter, None)
                with obs.span("epoch_end", epoch=ep):
                    mean = _epoch_mean(ep_metrics)
                    wall = time.perf_counter() - wall0
                    if reg.enabled:
                        reg.counter("train_epochs_total").inc()
                        # per-epoch phase seconds (sample/host_prep run on the
                        # prefetch workers, so an epoch is credited with
                        # whatever preparation completed during it — exact at
                        # depth 1); EpochBreakdown.from_history renders the
                        # paper table
                        ph1 = phase_at()
                        for p in phases:
                            mean[f"t_{p}"] = ph1[p] - ph0[p]
                        mean["t_wall"] = wall
                    if acc is not None:
                        totals = acc.finish()
                        # in-process shard_map has ONE clock for the fused
                        # program, so every rank is credited the same step
                        # wall time; multi-host deployments feed real per-rank
                        # timings here and the straggler detector bites
                        totals["rank_step_seconds"] = np.full(
                            self.num_ranks, t_step_ep, np.float64)
                        if reg.enabled:
                            obs.publish_rank_series(reg, totals)
                        if health:
                            health.observe_epoch(totals, wall_s=wall)
                    if quality:
                        # instruments 1+3: staleness read off the live device
                        # state (one host transfer per layer), convergence
                        # point into the event log.  Instrument 2 (the audit,
                        # an extra offline forward pass) only on its interval.
                        quality.observe_epoch(ep, metrics=mean)
                        quality.publish_staleness(state["hec"])
                        if state["hot"]:
                            hot_lib.publish_replica_ages(
                                state["hot"], life_span=cfg.hec.life_span)
                        if quality.should_audit(ep):
                            self.audit(ps, dist_data, state, epoch=ep)
                    history.append(mean)
                    if rz is not None \
                            and getattr(rz, "ckpt", None) is not None:
                        # epoch-boundary checkpoint of the FULL state pytree
                        # (params, opt state, HEC, hot tier, inflight queue).
                        # state["step"] is stamped first so a resumed run
                        # continues the device-seed sequence bit-exactly.
                        state["step"] = jnp.asarray(step_idx, jnp.int32)
                        rz.maybe_checkpoint(state, ep)
                    if log_every and (ep % log_every == 0
                                      or ep == start_epoch + num_epochs - 1):
                        hl = " ".join(
                            f"l{l}:" + format(
                                mean.get(f"hec_hits_l{l}", 0)
                                / max(mean.get(f"hec_halos_l{l}", 1), 1),
                                ".2f")
                            for l in range(cfg.num_layers))
                        print(f"[{self.mode}] epoch {ep}: "
                              f"loss={mean['loss']:.4f} "
                              f"acc={mean['acc']:.3f} hit-rates {hl}")
        state["step"] = jnp.asarray(step_idx, jnp.int32)
        if rz is not None:
            # one FLIGHT_resilience.json per run that saw faults or skips,
            # through the PR 7 flight-recorder contract
            rz.finalize(health)
        return state, history

    def audit(self, ps, dist_data, state, epoch: int = 0):
        """Online exactness audit: sample cached lines from each training
        HEC (and fresh hot-tier replicas), recompute their exact ``h^l``
        via the offline inference path, and publish relative-L2 error.

        ``HEC_0`` caches raw input features — exact at any age.  Hidden
        layers cache sampled-neighborhood forward activations (with the
        live dropout), so even a freshly pushed line carries the paper's
        minibatch approximation error relative to full-graph inference;
        that gap is exactly what this instrument measures, on top of the
        staleness drift.  Reads the training state, never writes it — the
        trajectory is untouched."""
        q = self.quality
        assert q is not None, "audit needs DistTrainer(quality=...)"
        cfg = self.cfg
        V = len(ps.owner)
        # exact references in global VID_o order (the training HECs' tag
        # space): layer 0 = the raw features, layers >= 1 = full-graph
        # layerwise inference (deterministic; dropout off)
        feats = np.zeros((V, cfg.feat_dim), np.float32)
        for p in ps.parts:
            feats[p.solid_vids] = np.asarray(p.features, np.float32)
        exact = [feats]
        if cfg.num_layers > 1:
            from repro.serve.gnn.distributed.offline import \
                layerwise_embeddings_dist
            exact += layerwise_embeddings_dist(
                cfg, state["params"], ps)[:cfg.num_layers - 1]
        layer_samples = []
        for l in range(cfg.num_layers):
            vids, cached, ages = hec_lib.hec_entries(
                state["hec"][l], sample=q.cfg.audit_samples, rng=q.rng)
            layer_samples.append((l, cached, exact[l][vids], ages))
        hot_samples = None
        if state["hot"] and dist_data is not None \
                and "hot_vids" in dist_data:
            hv = np.asarray(dist_data["hot_vids"])[0]   # same table per rank
            # per-layer pairs (layer widths differ; the plane concatenates
            # error vectors, not rows); tier storage may be padded wider
            # than the layer, so slice to the exact reference's width
            hot_samples = []
            for l, st in enumerate(state["hot"]):
                vids, vals, _ = hot_lib.tier_entries(
                    st, hv, life_span=cfg.hec.life_span)
                if len(vids):
                    hot_samples.append(
                        (vals[:, :exact[l].shape[1]], exact[l][vids]))
        return q.run_audit(epoch, layer_samples, hot_samples=hot_samples,
                           source="train")

    def evaluate(self, ps, dist_data, state, num_batches=8, seed0=123,
                 step_fn=None):
        """Test accuracy via sampled minibatches over test vertices."""
        cfg = self.cfg
        R = self.num_ranks
        if step_fn is None:
            ecfg = dataclasses.replace(cfg, dropout=0.0)
            step_fn = dataclasses.replace(self, cfg=ecfg).make_step(
                donate=False)
        mb_iter = self._pipeline(ps, seed0).eval_batches(num_batches,
                                                         seed=seed0)
        # a step-armed trainer's compiled step takes the fault-code input;
        # evaluation always runs clean (all-zero codes — same bits)
        fargs = ((jnp.zeros((R,), jnp.int32),)
                 if (self.resilience is not None
                     and getattr(self.resilience, "step_armed", False))
                 else ())
        accs, weights = [], []
        for k, mb in enumerate(mb_iter):
            (_, _, _, _, _, _, metrics) = step_fn(
                state["params"], state["opt_state"], state["hec"],
                state["hot"], state["inflight"], dist_data, mb,
                jnp.uint32(10_000 + k), *fargs)
            accs.append(float(metrics["acc"]))
            weights.append(float(metrics["examples"]))
        if not sum(weights):
            return 0.0
        return float(np.average(accs, weights=weights))
