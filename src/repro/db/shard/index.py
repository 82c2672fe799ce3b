"""`ShardedIndex`: one HADES sorted index per shard, probed fan-out.

Build: every shard's valid rows pad to one common block and a bitonic
network sorts each block on the device that holds the shard (each stage
a single batched Eval — `merge.shard_block_sort`); the per-shard
`SortedIndex` objects are carved out by id-stripping.

Lookups broadcast the client's encrypted trapdoor to every shard and
binary-search ALL shards' indexes in one lane-batched launch: a probe
step evaluates the `[S, B]` grid of (shard, lane) probes in one Eval,
so a range query over S shards still costs only ~log₂(max shard size)
launches.  Boundary lanes then combine per shard into local row masks
(the executor lifts them to the global mask).  Per-lane decode
thresholds ride exactly as in `SortedIndex.search` — ε-band lanes work
unchanged.
"""
from __future__ import annotations

from typing import Callable, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.core import compare as C
from repro.core.encrypt import Ciphertext
from repro.core.keys import KeySet
from repro.db.index import SortedIndex, _stack_cts, eps_lane_taus
from repro.db.shard import merge as M
from repro.db.shard.table import ShardedTable
from repro.db.table import rows_to_mask


class ShardedIndex:
    """Per-shard SortedIndexes, each on its shard's device, probed
    together (fan-out search)."""

    def __init__(self, column: str, shards: List[SortedIndex], *,
                 build_compares: int = 0):
        self.column = column
        self.shards = shards
        self.counts = np.asarray([ix.n_rows for ix in shards], np.int64)
        self.build_compares = build_compares
        self.search_compares = 0
        # per-lane probe totals (summed over shards) from the LAST
        # `search` call — the per-query attribution the batched servers
        # bill from (the scalar above is only the cumulative total)
        self.last_probe_counts = np.zeros(0, np.int64)
        self._cmp: Optional[Callable] = None

    # -- construction ------------------------------------------------------

    @classmethod
    def build(cls, ks: KeySet, stable: ShardedTable,
              column: str) -> "ShardedIndex":
        """Sort every shard's column, each padded to the common block,
        on the device that holds the shard: no device ever holds more
        than its own shard's network.  (Real rows land exactly where
        one batched network over all shards would put them: the pads
        are sentinels above every value, so no decision involving a
        real row depends on which pad it meets.)  The pads encrypt on
        the default device, one shard at a time, each block complete on
        its shard's device before the next shard's pads start; then
        every shard's network is dispatched before any result is read
        back, so on a mesh the shards sort concurrently, each on its
        own device."""
        from repro.db.executor import jitted_comparator
        cmp = jitted_comparator(ks)
        block = C.next_pow2(int(stable.shard_rows.max()))
        blocks = []
        for s in range(stable.num_shards):
            m = int(stable.shard_rows[s])
            base = stable.shard_block(column, s)
            rows = Ciphertext(base.c0[:m], base.c1[:m])
            del base                     # one shard-sized copy at a time
            ct, ids = M.pad_shard_blocks(
                ks, [(rows, np.arange(m, dtype=np.int64))], block=block,
                pad_value=ks.params.max_operand // 2, num_blocks=1,
                pad_salt=s)
            del rows
            blocks.append((jax.block_until_ready(ct), jnp.asarray(ids)))
            del ct
        networks = []
        while blocks:                    # each block freed as it sorts
            ct, ids = blocks.pop(0)
            networks.append(M.shard_block_sort(
                ks, cmp, ct.c0, ct.c1, ids, block=block))
            del ct
        shards = []
        for c0, c1, gid, n in networks:
            gid = np.asarray(gid)
            keep = np.nonzero(gid >= 0)[0]
            # each shard rode a block-row network (the common padded
            # block, not its own row count)
            shards.append(SortedIndex(
                column, Ciphertext(c0[keep], c1[keep]), gid[keep],
                build_compares=n))
        return cls(column, shards,
                   build_compares=sum(ix.build_compares for ix in shards))

    # -- fan-out search ----------------------------------------------------

    @property
    def num_shards(self) -> int:
        """Number of per-shard SortedIndexes (= the table's shard count)."""
        return len(self.shards)

    def _eval(self, ks: KeySet) -> Callable:
        if self._cmp is None:
            from repro.db.executor import jitted_eval
            self._cmp = jitted_eval(ks)
        return self._cmp

    def search(self, ks: KeySet, values: Ciphertext, strict: np.ndarray,
               taus: Optional[np.ndarray] = None) -> np.ndarray:
        """Fan-out boundary search: B lanes against ALL S shards.

        values: trapdoor ciphertexts with leading batch dim B — sent
        ONCE by the client, broadcast to every shard server-side.
        Returns [S, B] sorted positions; every binary-search step is ONE
        batched Eval over the S·B live probes.  strict/taus semantics
        match `SortedIndex.search` lane for lane.
        """
        strict = np.asarray(strict, bool)
        B = values.c0.shape[0]
        assert strict.shape == (B,)
        if taus is None:
            taus = np.full(B, ks.params.tau, dtype=np.int64)
        taus = np.asarray(taus, np.int64)
        assert taus.shape == (B,)
        S = self.num_shards
        ev = self._eval(ks)
        lo = np.zeros((S, B), np.int64)
        hi = np.broadcast_to(self.counts[:, None], (S, B)).copy()
        home = values.c0.devices().pop()
        # shards whose probed rows leave their chip for the home chip
        away = [bool(ix.n_rows) and ix.sorted_ct.c0.devices() != {home}
                for ix in self.shards]

        def gather(probe):
            # each shard's probed rows, gathered where the shard lives,
            # then brought beside the trapdoors: [S, B, ...]
            out0, out1, moved = [], [], 0
            for s, ix in enumerate(self.shards):
                c0, c1 = ix.sorted_ct
                if ix.n_rows:
                    c0, c1 = c0[probe[s]], c1[probe[s]]
                else:          # an empty shard's lanes are all dead
                    c0 = c1 = jnp.zeros((B,) + c0.shape[1:], c0.dtype)
                if away[s]:
                    moved += c0.nbytes + c1.nbytes
                out0.append(jax.device_put(c0, home))
                out1.append(jax.device_put(c1, home))
            obs.count("shard.index.bytes_moved", moved)
            return Ciphertext(jnp.stack(out0), jnp.stack(out1))
        lane_probes = np.zeros(B, np.int64)
        with obs.span("shard.index.search", column=self.column,
                      shards=S, lanes=B) as sp:
            step = 0
            while np.any(lo < hi):
                active = lo < hi
                # step self time: the per-shard gathers, the moves to
                # the home chip and the eval dispatch; decode: the
                # device round trip and the host's bounds update
                with obs.span("shard.index.step", step=step,
                              lanes=int(active.sum())):
                    mid = (lo + hi) // 2
                    probe = np.where(active, mid, 0)
                    rows = gather(probe)
                    obs.jit_launch("shard.index.probe", rows.c0, values.c0)
                    obs.count("eval.launches")
                    obs.count("eval.lanes", S * B)
                    v = ev(rows, values)
                    with obs.span("shard.index.decode"):
                        v = np.asarray(v)                      # [S, B] raw
                        c = np.where(np.abs(v) < taus[None, :], 0,
                                     np.sign(v))
                        lane_probes += active.sum(axis=0)
                        go_left = np.where(strict[None, :], c > 0, c >= 0)
                        hi = np.where(active & go_left, mid, hi)
                        lo = np.where(active & ~go_left, mid + 1, lo)
                step += 1
            sp.set(probes=int(lane_probes.sum()), steps=step)
        obs.count("index.probes", int(lane_probes.sum()))
        self.search_compares += int(lane_probes.sum())
        self.last_probe_counts = lane_probes
        return lo

    # -- leaf resolution (executor plumbing) -------------------------------

    def _eps_taus(self, ks: KeySet,
                  eps: Optional[float]) -> Optional[np.ndarray]:
        return eps_lane_taus(ks, eps)

    def lane_masks(self, pos: np.ndarray, lane: int,
                   n_padded: int) -> List[np.ndarray]:
        """Boundary lane pair (2·lane, 2·lane+1) -> per-shard local row
        masks (shared by executor and ShardedQueryServer)."""
        out = []
        for s in range(self.num_shards):
            l, r = int(pos[s, 2 * lane]), int(pos[s, 2 * lane + 1])
            out.append(rows_to_mask(self.shards[s].perm[l:r], n_padded))
        return out

    def shard_masks_range(self, ks: KeySet, ct_lo: Ciphertext,
                          ct_hi: Ciphertext, n_padded: int, *,
                          eps: Optional[float] = None) -> List[np.ndarray]:
        """lo <= value <= hi as per-shard local row masks — one 2-lane
        fan-out search (`eps` makes the bounds ε-inclusive)."""
        bounds = _stack_cts([ct_lo, ct_hi])
        pos = self.search(ks, bounds, np.array([False, True]),
                          self._eps_taus(ks, eps))
        return self.lane_masks(pos, 0, n_padded)

    def shard_masks_eq(self, ks: KeySet, ct_value: Ciphertext,
                       n_padded: int, *,
                       eps: Optional[float] = None) -> List[np.ndarray]:
        """value == v (ε-band with `eps`) as per-shard local row masks —
        one 2-lane fan-out search."""
        bounds = _stack_cts([ct_value, ct_value])
        pos = self.search(ks, bounds, np.array([False, True]),
                          self._eps_taus(ks, eps))
        return self.lane_masks(pos, 0, n_padded)

    def __repr__(self) -> str:
        return (f"ShardedIndex({self.column!r}, shards={self.num_shards}, "
                f"rows={self.counts.tolist()}, "
                f"build_compares={self.build_compares})")
