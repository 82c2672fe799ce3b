"""`ShardedQueryServer`: K client queries × S shards in one pass.

The `db.query_serve.QueryServer` queue/batch pattern lifted onto a
`ShardedTable`: a drained batch of K queries routes to ALL shards in a
single vectorized sweep —

  * every scan atom of every query joins ONE `[S, ΣA_i, N_sp]`
    shard-parallel raw-eval launch (`shard_map` on a usable mesh);
  * every index-eligible leaf joins ONE fan-out binary search per
    indexed column — the `[S, 2K]` probe grid resolves all queries'
    boundary lanes against all shards' indexes together, each step one
    batched Eval;
  * per-query combine / merge-order stages then run on each query's
    global mask (cross-shard top-k and order-by via the merge networks).

So K clients querying an S-shard table still cost one fused filter
launch + one lane-batched search per indexed column per batch — the
shard dim rides inside the launches instead of multiplying them.

MUTATIONS interleave exactly as on the single-table server
(`query_serve.QueryServer`): same-kind runs drain in submit order,
query batches answer over base ∪ delta (the shard-parallel scan widens
by the delta block; the fan-out searches add one per-delta-run search
per column per shard holding pending rows), and `compact()` /
`compact_threshold` retire deltas cooperatively between batches through
the per-shard merge networks.
"""
from __future__ import annotations

import contextlib
import dataclasses
import time
from typing import Dict, List, Optional, Tuple

import numpy as np

from repro import obs
from repro.core.ckks import eps_to_tau
from repro.core.keys import KeySet
from repro.db import executor as X
from repro.db import plan as P
from repro.db.index import _stack_cts
from repro.db.query_serve import MutationResult, _QueuedMutation
from repro.db.shard import executor as SX
from repro.db.shard.index import ShardedIndex
from repro.db.shard.table import ShardedTable


@dataclasses.dataclass
class ShardedBatchStats:
    """Shared-launch accounting for one drained batch across all shards
    (the fused shard-parallel Eval and the fan-out searches count ONCE
    here; per-query shares live on each result's own stats)."""
    queries: int = 0
    shards: int = 0
    eval_calls: int = 0
    scan_compares: int = 0
    per_shard_scan_compares: int = 0
    index_compares: int = 0
    delta_build_compares: int = 0
    merge_compares: int = 0
    wall_s: float = 0.0


class ShardedQueryServer:
    """Queue + batch executor over one sharded encrypted table."""

    def __init__(self, ks: KeySet, stable: ShardedTable, *,
                 indexes: Optional[Dict[str, ShardedIndex]] = None,
                 batch: int = 4, engine: str = "jnp",
                 compact_threshold: Optional[int] = None,
                 lane_budget: Optional[int] = None):
        self.ks = ks
        self.stable = stable
        self.indexes = indexes or {}
        self.batch = int(batch)
        self.engine = engine
        self.compact_threshold = compact_threshold
        # per-launch eval-lane cap for the shard-parallel fused scans
        # (None = the kernels.ops policy default)
        self.lane_budget = lane_budget
        self._queue: List[Tuple[int, P.Query]] = []
        self._next_id = 0
        self.batch_log: List[ShardedBatchStats] = []
        self.compaction_log: list = []
        self._tenants: Dict[int, str] = {}     # request id -> tenant label

    # -- queue -------------------------------------------------------------

    def _enqueue(self, item, tenant: Optional[str]) -> int:
        """Assign the next request id, remember its tenant, enqueue."""
        qid = self._next_id
        self._next_id += 1
        if tenant is not None:
            self._tenants[qid] = tenant
        self._queue.append((qid, item))
        return qid

    def clear_queue(self) -> int:
        """Drop every queued, not-yet-drained request; returns how many
        were dropped.  The fault-recovery reset: after `run()` raises,
        the queue may hold a partially-consumed drain — callers that
        retry (e.g. `ServeLoop`) clear it before re-submitting."""
        dropped = len(self._queue)
        self._queue = []
        return dropped

    @contextlib.contextmanager
    def batch_size(self, n: int):
        """Temporarily set the drain batch size (restored on exit, even
        if the drain raises) — how `ServeLoop` runs a drafted batch as
        ONE shared launch without clobbering the configured size."""
        old, self.batch = self.batch, max(1, int(n))
        try:
            yield self
        finally:
            self.batch = old

    def _bill_tenant(self, qid: int, stats) -> None:
        """Per-tenant served-query + compare-lane attribution (counted
        only when the obs layer is enabled)."""
        if not obs.is_enabled():
            return
        tenant = self._tenants.get(qid, "default")
        obs.count("server.queries", 1, tenant=tenant)
        obs.count("server.compares", stats.filter_compares, tenant=tenant)

    def submit(self, query, *, tenant: Optional[str] = None) -> int:
        """Enqueue a Query (or bare predicate); returns a request id.
        `tenant` labels the request for per-tenant metrics attribution."""
        if isinstance(query, P.Predicate):
            query = P.Query(where=query)
        return self._enqueue(query, tenant)

    def submit_insert(self, data, key, *,
                      tenant: Optional[str] = None) -> int:
        """Enqueue an insert (routed to the least-loaded shards' delta
        runs); resolves to a `MutationResult` with the new global ids."""
        return self._enqueue(_QueuedMutation("insert", data=data, key=key),
                             tenant)

    def submit_delete(self, rows, *, tenant: Optional[str] = None) -> int:
        """Enqueue a tombstone of global row ids; resolves to a
        `MutationResult` with the newly-dead count."""
        return self._enqueue(_QueuedMutation(
            "delete", rows=np.asarray(rows, np.int64)), tenant)

    def submit_update(self, rows, data, key, *,
                      tenant: Optional[str] = None) -> int:
        """Enqueue an update (tombstone + re-insert); resolves to a
        `MutationResult` with the replacement global ids."""
        return self._enqueue(_QueuedMutation(
            "update", rows=np.asarray(rows, np.int64), data=data, key=key),
            tenant)

    def run(self) -> Dict[int, X.QueryResult]:
        """Drain the queue in submit order: maximal same-kind runs —
        query runs in shared-launch batches, mutation runs sequentially
        (reads observe exactly the writes submitted before them), with
        `compact_threshold` optionally triggering a cooperative
        compaction after a mutation run."""
        results: Dict[int, X.QueryResult] = {}
        while self._queue:
            is_mut = isinstance(self._queue[0][1], _QueuedMutation)
            n = 1
            while (n < len(self._queue) and isinstance(
                    self._queue[n][1], _QueuedMutation) == is_mut):
                n += 1
            chunk, self._queue = self._queue[:n], self._queue[n:]
            if is_mut:
                for qid, m in chunk:
                    results[qid] = self._apply_mutation(m)
                if (self.compact_threshold is not None
                        and self.stable.n_delta >= self.compact_threshold):
                    self.compact()
            else:
                for i in range(0, len(chunk), self.batch):
                    results.update(self._run_batch(chunk[i:i + self.batch]))
        return results

    # -- mutations ---------------------------------------------------------

    def _apply_mutation(self, m: _QueuedMutation) -> MutationResult:
        stable = self.stable
        with obs.span("server.mutation", kind=m.kind):
            deleted = 0
            if m.rows is not None:
                deleted = stable.delete(m.rows)
            row_ids = np.zeros(0, np.int64)
            if m.data is not None:
                row_ids = stable.insert(self.ks, m.data, m.key)
        return MutationResult(m.kind, row_ids, deleted=deleted)

    def compact(self):
        """Retire all shards' pending delta runs between batches: per
        shard, fold delta onto base and merge the (base run, delta run)
        pair of every served `ShardedIndex` through the log-depth merge
        network.  Returns the `CompactionStats` (also appended to
        `compaction_log`)."""
        from repro.db.delta import compact as _compact
        stats = _compact(self.ks, self.stable, self.indexes)
        self.compaction_log.append(stats)
        return stats

    # -- batch execution ---------------------------------------------------

    def _run_batch(self, chunk: List[Tuple[int, P.Query]],
                   ) -> Dict[int, X.QueryResult]:
        with obs.span("server.shard_batch", size=len(chunk),
                      shards=self.stable.num_shards,
                      qids=[qid for qid, _ in chunk]) as bsp:
            return self._run_batch_traced(chunk, bsp)

    def _run_batch_traced(self, chunk: List[Tuple[int, P.Query]], bsp,
                          ) -> Dict[int, X.QueryResult]:
        t0 = time.perf_counter()
        ks, stable = self.ks, self.stable
        S, N = stable.num_shards, stable.n_padded_per_shard
        W = stable.shard_scan_width   # base block ∪ pending delta block
        plans = [(qid, P.compile_plan(q)) for qid, q in chunk]
        bstats = ShardedBatchStats(queries=len(chunk), shards=S)

        # partition leaves into fan-out index lanes vs scan atoms
        scan_atoms: List[P.Atom] = []
        scan_ref: List[Tuple[int, int, int, int]] = []
        lane_cts: Dict[str, list] = {}
        lane_strict: Dict[str, list] = {}
        lane_taus: Dict[str, list] = {}
        lane_ref: Dict[str, list] = {}
        for pi, (_, plan) in enumerate(plans):
            for li, leaf in enumerate(plan.leaves):
                idx = self.indexes.get(leaf.column)
                if idx is not None:
                    lo, hi = ((leaf.lo, leaf.hi)
                              if isinstance(leaf, P.Range)
                              else (leaf.value, leaf.value))
                    tau = (ks.params.tau if leaf.eps is None
                           else eps_to_tau(ks.params, leaf.eps))
                    lane_cts.setdefault(leaf.column, []).extend([lo, hi])
                    lane_strict.setdefault(leaf.column, []).extend(
                        [False, True])
                    lane_taus.setdefault(leaf.column, []).extend([tau, tau])
                    lane_ref.setdefault(leaf.column, []).append((pi, li))
                else:
                    atoms = plan.scan_atoms(li)
                    scan_ref.append((pi, li, len(scan_atoms), len(atoms)))
                    scan_atoms.extend(atoms)

        leaf_masks: List[List[Optional[List[np.ndarray]]]] = [
            [None] * plan.num_leaves for _, plan in plans]
        qstats = [SX.ShardedExecStats(shards=S,
                                      mesh_devices=stable.spec.mesh_devices)
                  for _ in plans]

        # ONE fan-out search per indexed column: all queries' boundary
        # lanes against all shards' indexes together ([S, 2K] probe
        # grid); every shard holding a pending delta run adds ONE more
        # lane-batched search against its own per-run index
        for column, cts in lane_cts.items():
            idx = self.indexes[column]
            lanes = _stack_cts(cts)
            strict = np.asarray(lane_strict[column])
            taus = np.asarray(lane_taus[column], np.int64)
            before = idx.search_compares
            pos = idx.search(ks, lanes, strict, taus)
            bstats.index_compares += idx.search_compares - before
            base_counts = idx.last_probe_counts.copy()
            dsearch = {}
            for s in range(S):
                didx = SX.shard_delta_probe_index(ks, stable, column, s,
                                                  bstats)
                if didx is None:
                    continue
                before = didx.search_compares
                dsearch[s] = (didx, didx.search(ks, lanes, strict, taus),
                              didx.last_probe_counts.copy())
                bstats.index_compares += didx.search_compares - before
            for j, (pi, li) in enumerate(lane_ref[column]):
                masks = idx.lane_masks(pos, j, W)
                # per-query share of the shared launches: this query's
                # two boundary lanes, base fan-out AND every delta-run
                # search (sums across queries reconcile with bstats)
                qstats[pi].index_compares += int(
                    base_counts[2 * j] + base_counts[2 * j + 1])
                for s, (didx, dpos, dcounts) in dsearch.items():
                    dl, dr = int(dpos[2 * j]), int(dpos[2 * j + 1])
                    masks[s][N + np.asarray(didx.perm[dl:dr],
                                            np.int64)] = True
                    qstats[pi].index_compares += int(
                        dcounts[2 * j] + dcounts[2 * j + 1])
                leaf_masks[pi][li] = masks
                qstats[pi].indexed_leaves += 1

        # ONE shard-parallel fused Eval for every scan atom in the batch
        # (over the union scan width — base blocks AND delta runs)
        if scan_atoms:
            vals = SX.sharded_fused_eval(ks, stable, scan_atoms,
                                         engine=self.engine,
                                         lane_budget=self.lane_budget)
            bstats.eval_calls += 1
            bstats.scan_compares += len(scan_atoms) * S * W
            bstats.per_shard_scan_compares += len(scan_atoms) * W
            for pi, li, start, count in scan_ref:
                leaf_masks[pi][li] = [
                    X.scan_leaf_mask(ks, scan_atoms, vals[s], start, count)
                    for s in range(S)]
                qstats[pi].scan_leaves += 1
                qstats[pi].scan_compares += count * S * W
                qstats[pi].per_shard_scan_compares += count * W
                qstats[pi].eval_calls = 1

        # per-query combine + merge-order/limit/project
        results: Dict[int, X.QueryResult] = {}
        for pi, (qid, plan) in enumerate(plans):
            stats = qstats[pi]
            mask = SX.combine_shard_masks(stable, plan, leaf_masks[pi])
            row_ids = np.nonzero(mask)[0]
            row_ids = SX.order_rows_sharded(ks, stable, plan.query,
                                            row_ids, stats)
            columns = {c: stable.gather_global(c, row_ids)
                       for c in plan.query.select}
            bstats.merge_compares += stats.merge_compares
            results[qid] = X.QueryResult(row_ids=row_ids, mask=mask,
                                         columns=columns, stats=stats)
            self._bill_tenant(qid, stats)
        bstats.wall_s = time.perf_counter() - t0
        bsp.set(queries=bstats.queries, eval_calls=bstats.eval_calls)
        obs.absorb_batch_stats(bstats, shards=str(S))
        self.batch_log.append(bstats)
        return results
