"""Static halo-exchange plans: every per-step index computation the
trainer and serving schedulers used to redo each iteration, precomputed
ONCE from the ``PartitionSet`` at setup.

The partition contract is static for the lifetime of a partitioning:
``db_halo(i, j)`` (what rank i owes rank j), each rank's sorted solid
owner tables, and the per-pair scatter/gather indices of an exact halo
exchange never change between steps.  ``build_exchange_plan`` derives them
all once; ``ExchangePlan.device_tables()`` stacks the device-side pieces
``[R, ...]`` so a shard_map program (sharded on the mesh's ``data`` axis)
reads its slice with plain gathers:

  * ``db_halo [R, R, D]``       — sorted, sentinel-padded push contract
  * ``push_mask [R, R, P]``     — ``push_mask[i, j, p]``: solid VID_p ``p``
    of rank i is a halo on rank j.  Replaces the per-step ``searchsorted``
    membership probes of the legacy AEP push with ONE boolean gather.
  * ``solid_sorted_vids/idx [R, S]`` — per-rank sorted owner tables: any
    rank answers "which feature/embedding row is VID_o v?" with one
    ``searchsorted`` + gather (trainer sync fetch, serve halo gather).

Host-side, ``send_local[i][j]`` / ``recv_pos[i][j]`` are the gather/scatter
index vectors of one exact exchange (offline inference): rank j receives
``h_solid[i][send_local[i][j]]`` into its halo rows at ``recv_pos[i][j]``.

``hot_size > 0`` additionally derives the static **hot set** (PR 5, the
heavy-tail elimination): the top-K highest-degree vertices among those
that are halos *anywhere*.  Hot vertices are removed from the pairwise
``push_mask`` contract — their embeddings are replicated on every rank by
the hot-vertex tier (``repro.cache.hot_tier``) and refreshed by a
broadcast segment piggybacked on the fused AEP push — and
``modeled_remote_rows`` quantifies the remote-row win (the number the
benchmarks and the CI smoke gate check).
"""
from __future__ import annotations

import dataclasses
from typing import List, Optional

import jax
import numpy as np

from repro.graph.partition import PartitionSet

_SENTINEL = np.int32(2 ** 30)    # sorts after every real VID_o


def _pad_stack(arrays, pad_value=0, dtype=None) -> np.ndarray:
    """Stack ragged per-rank arrays into ``[R, max_len, ...]`` with padding."""
    n = max(len(a) for a in arrays)
    rest = arrays[0].shape[1:]
    out = np.full((len(arrays), n) + rest, pad_value,
                  dtype or arrays[0].dtype)
    for i, a in enumerate(arrays):
        out[i, :len(a)] = a
    return out


def partition_degrees(ps: PartitionSet) -> np.ndarray:
    """Global vertex degrees ``[V]`` from the per-partition CSRs (every
    vertex is solid in exactly one partition, and its local CSR row holds
    its full neighbor list — halos included)."""
    deg = np.zeros(len(ps.owner), np.int64)
    for p in ps.parts:
        deg[p.solid_vids] = p.indptr[1:] - p.indptr[:-1]
    return deg


def hot_set_tables(ps: PartitionSet, hot_size: int):
    """Degree-ranked hot set: ``(hot_vids [K], hot_owner [K],
    hot_replicas [K])``, sorted by VID_o (so slot lookup is one
    ``searchsorted``).

    Candidates are vertices that appear as a halo on at least one rank —
    a vertex nobody ever fetches gains nothing from replication.  Among
    those, the top ``hot_size`` by degree (ties by vid, deterministic);
    ``hot_replicas[k]`` counts the ranks holding ``hot_vids[k]`` as a
    halo, the per-exchange rows replication removes from the wire."""
    if hot_size <= 0 or ps.num_parts <= 1:
        z = np.empty(0, np.int32)
        return z, z.copy(), np.empty(0, np.int64)
    halos = np.concatenate([p.halo_vids for p in ps.parts])
    cand, reps = np.unique(halos, return_counts=True)
    if not len(cand):
        z = np.empty(0, np.int32)
        return z, z.copy(), np.empty(0, np.int64)
    deg = partition_degrees(ps)[cand]
    order = np.lexsort((cand, -deg))[:hot_size]
    keep = np.sort(order)                       # vid-ascending hot table
    return (cand[keep].astype(np.int32),
            ps.owner[cand[keep]].astype(np.int32),
            reps[keep].astype(np.int64))


def solid_lookup_tables(ps: PartitionSet):
    """Per-rank sorted owner tables: ``(vids [R, Smax], idx [R, Smax])``.

    ``vids[r]`` is rank r's solid VID_o sorted ascending (sentinel-padded);
    ``idx[r]`` the matching solid VID_p via ``PartitionSet.route`` — so any
    rank can answer "which feature/embedding row is VID_o v?" with one
    searchsorted + gather.  Shared by the trainer's sync-mode fetch and the
    serve-side halo gather."""
    svids, sidx = [], []
    for p in ps.parts:
        vs = np.sort(p.solid_vids)
        _, li = ps.route(vs)
        svids.append(vs.astype(np.int32))
        sidx.append(li.astype(np.int32))
    return (_pad_stack(svids, _SENTINEL), _pad_stack(sidx, 0))


@dataclasses.dataclass
class ExchangePlan:
    """Precomputed static exchange tables for one ``PartitionSet``."""
    num_ranks: int
    num_vertices: int
    db_halo: np.ndarray            # [R, R, D] int32, sorted + sentinel pad
    push_mask: np.ndarray          # [R, R, P] bool (P = padded VID_p width)
    solid_sorted_vids: np.ndarray  # [R, S] int32, sentinel pad
    solid_sorted_idx: np.ndarray   # [R, S] int32
    pair_rows: np.ndarray          # [R, R] int64: |db_halo(i, j)|
    num_halo: np.ndarray           # [R] int64: halo replicas per rank
    # offline-exchange index vectors (None when host_indices=False):
    send_local: Optional[List[List[np.ndarray]]]  # [i][j]: VID_p rows i -> j
    recv_pos: Optional[List[List[np.ndarray]]]    # [i][j]: halo slots on j
    # hot-vertex tier tables (empty when hot_size=0 — bit-compatible off):
    hot_vids: np.ndarray = dataclasses.field(
        default_factory=lambda: np.empty(0, np.int32))   # [K] sorted VID_o
    hot_owner: np.ndarray = dataclasses.field(
        default_factory=lambda: np.empty(0, np.int32))   # [K] owner rank
    hot_replicas: np.ndarray = dataclasses.field(
        default_factory=lambda: np.empty(0, np.int64))   # [K] halo ranks

    @property
    def hot_size(self) -> int:
        return len(self.hot_vids)

    @property
    def halo_rows_total(self) -> int:
        """Rows one exact full exchange moves (sum over off-diagonal pairs)."""
        return int(self.pair_rows.sum() - np.trace(self.pair_rows))

    @property
    def hot_rows_total(self) -> int:
        """Of ``halo_rows_total``, the rows owed for HOT vertices — the
        heavy tail the replicated tier removes from the pairwise wire."""
        return int(self.hot_replicas.sum())

    def exchange_bytes(self, dim: int, itemsize: int = 4) -> int:
        """Exact payload (+ vid tags) of one full halo exchange at ``dim``."""
        return self.halo_rows_total * (dim * itemsize + 4)

    def expected_inbound_rows(self) -> np.ndarray:
        """[R] plan-time expectation of halo rows each rank RECEIVES in
        one full exchange (off-diagonal column sums of ``pair_rows``).

        This is the static edge-cut profile the partitioner committed to;
        the health plane's edge-cut-drift detector compares the live
        per-rank halo-row distribution against it — sustained divergence
        means the graph (or the access pattern) has drifted from the
        partition and is the re-partitioning trigger."""
        inbound = self.pair_rows.sum(axis=0) - np.diag(self.pair_rows)
        return inbound.astype(np.int64)

    def modeled_remote_rows(self, degrees: np.ndarray, rounds: int = 1,
                            refresh_every: int = 1) -> dict:
        """Remote-fetch row model over a window of ``rounds`` sampled
        rounds (minibatch training fetches / serve-side halo gathers).

        A halo replica travels when its vertex lands in a sampled
        neighborhood; for ego-net sampling that appearance rate grows with
        degree, so each replica of ``v`` is weighted
        ``w(v) = deg(v) / deg_max`` (the busiest hub is requested about
        once per round, the tail proportionally less — the power-law
        heavy-tail in one number).  Baseline: every replica travels at its
        appearance rate every round.  Hot tier: hot replicas read the
        local replica instead; each refresh broadcast moves every hot row
        to the ``R - 1`` non-owners once per ``refresh_every`` rounds (the
        staleness window — serving refreshes once per checkpoint, training
        once per HEC life-span).  Replication is never a single-round win
        (``replicas <= R - 1``); amortization over the validity window is
        the entire point — hubs are fetched every round but refreshed
        rarely."""
        degrees = np.asarray(degrees, np.float64)
        w = degrees / max(degrees.max(), 1.0)
        base_round = 0.0
        hot_round = 0.0
        hot_set = set(self.hot_vids.tolist())
        for j in range(self.num_ranks):
            for i in range(self.num_ranks):
                if i == j:
                    continue
                vids = self.db_halo[i, j]
                vids = vids[vids != _SENTINEL]
                ws = w[vids]
                base_round += float(ws.sum())
                if hot_set:
                    cold = ~np.isin(vids, self.hot_vids,
                                    assume_unique=True)
                    hot_round += float(ws[cold].sum())
                else:
                    hot_round += float(ws.sum())
        refreshes = -(-rounds // max(refresh_every, 1))
        base = base_round * rounds
        hot = hot_round * rounds \
            + self.hot_size * (self.num_ranks - 1) * refreshes
        return {"rounds": rounds, "refresh_every": refresh_every,
                "baseline_rows": base, "hot_rows": hot,
                "reduction": 1.0 - hot / base if base else 0.0}

    def device_tables(self, sharding=None) -> dict:
        """The ``[R, ...]``-stacked tables a shard_map step consumes
        (merged into the trainer's / server's sharded data dict), placed
        with ``sharding`` (default: the default device).
        ``db_halo`` itself stays host-side: the push membership it encodes
        travels as the (denser to probe) ``push_mask``.  With a hot set,
        the sorted hot-vid table (every rank's copy is identical) and the
        per-rank ownership mask ride along."""
        out = {
            "push_mask": self.push_mask,
            "solid_sorted_vids": self.solid_sorted_vids,
            "solid_sorted_idx": self.solid_sorted_idx,
        }
        if self.hot_size:
            R = self.num_ranks
            out["hot_vids"] = np.broadcast_to(self.hot_vids,
                                              (R, self.hot_size))
            out["hot_mine"] = self.hot_owner[None, :] == np.arange(R)[:, None]
        return jax.device_put(out, sharding)


def build_exchange_plan(ps: PartitionSet,
                        host_indices: bool = True,
                        hot_size: int = 0) -> ExchangePlan:
    """Derive every static exchange table from the partition contract.

    ``host_indices=False`` skips the offline-exchange gather/scatter index
    vectors (an extra route + searchsorted per rank pair) — consumers that
    only need the device tables (the trainer) save that setup cost.

    ``hot_size=K`` derives the degree-ranked hot set and removes hot
    vertices from the pairwise ``push_mask``: the replicated tier services
    them, so no rank spends pairwise push slots on the heavy tail.  The
    ``db_halo`` table and the offline indices are NOT filtered — they
    encode the partition contract (the exact offline exchange still moves
    every halo row).  ``hot_size=0`` (default) is byte-identical to the
    pre-tier plan."""
    R = ps.num_parts
    dbs = [[ps.db_halo(i, j) for j in range(R)] for i in range(R)]
    D = max(1, max(len(d) for row in dbs for d in row))
    db_halo = np.full((R, R, D), _SENTINEL, np.int32)
    pair_rows = np.zeros((R, R), np.int64)
    for i in range(R):
        for j in range(R):
            db_halo[i, j, :len(dbs[i][j])] = dbs[i][j]
            pair_rows[i, j] = len(dbs[i][j])

    hot_vids, hot_owner, hot_reps = hot_set_tables(ps, hot_size)

    P = max(p.num_solid + p.num_halo for p in ps.parts)
    push_mask = np.zeros((R, R, P), bool)
    send_local = [[np.empty(0, np.int64)] * R
                  for _ in range(R)] if host_indices else None
    recv_pos = [[np.empty(0, np.int64)] * R
                for _ in range(R)] if host_indices else None
    for i in range(R):
        pi = ps.parts[i]
        for j in range(R):
            vids = dbs[i][j]
            if i != j and len(vids):
                # db vids are owned by i: membership over i's solid VID_p;
                # hot vids leave the pairwise contract (tier-broadcast)
                cold = vids if not len(hot_vids) else \
                    vids[~np.isin(vids, hot_vids, assume_unique=True)]
                push_mask[i, j, :pi.num_solid] = np.isin(
                    pi.solid_vids, cold, assume_unique=True)
                if host_indices:
                    _, local = ps.route(vids)
                    send_local[i][j] = local.astype(np.int64)
                    recv_pos[i][j] = np.searchsorted(
                        ps.parts[j].halo_vids, vids).astype(np.int64)

    svids, sidx = solid_lookup_tables(ps)
    return ExchangePlan(
        num_ranks=R, num_vertices=len(ps.owner), db_halo=db_halo,
        push_mask=push_mask, solid_sorted_vids=svids, solid_sorted_idx=sidx,
        pair_rows=pair_rows,
        num_halo=np.array([p.num_halo for p in ps.parts], np.int64),
        send_local=send_local, recv_pos=recv_pos,
        hot_vids=hot_vids, hot_owner=hot_owner, hot_replicas=hot_reps)
