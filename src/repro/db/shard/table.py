"""`ShardedTable`: an encrypted column-store partitioned across shards.

Rows split into S contiguous, balanced chunks; every chunk pads to ONE
common power-of-two block size N_sp (`pad_rows_pow2` — the same helper
and sentinel-geometry `Table` uses), so each column is a single stacked
ciphertext `[S, N_sp, K, n]` whose leading dim places on the shard mesh
(`ShardSpec.place`).  Uneven partitions (non-power-of-two row counts)
just mean shards carry different validity masks over the same block
size — static shapes survive, which is what lets every fused filter
stage compile once and run shard-parallel.

Global row ids are the original ingest order: at construction shard s
owns the contiguous id range [offsets[s], offsets[s+1]), so
`from_table` — which re-partitions an existing `Table`'s ciphertext
ROWS without touching plaintext — produces bit-identical per-row
ciphertexts, the anchor of the byte-level shard-invariance tests.

WRITE PATH.  `insert` routes new rows to the least-loaded shards and
appends them to a per-shard DELTA RUN (a plain `Table`, pow2-padded);
`delete` tombstones global ids host-side; `update` is delete+insert.
New rows take ids past the end of the id space, and compaction
(`repro.db.delta.compact`) folds each shard's delta rows onto the end
of that shard's base block — after which shard ownership is no longer
contiguous in id space.  The table therefore keeps an EXPLICIT id map
(`_gid_shard` / `_gid_pos` / `_gid_in_delta`, plus the per-shard
slot -> id map `_slot_gid`) that starts out equal to the contiguous
arithmetic and stays authoritative through every mutation; all row-id
algebra below reads the map, never the offsets.
"""
from __future__ import annotations

from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import encrypt as E
from repro.core.compare import next_pow2
from repro.core.encrypt import Ciphertext
from repro.core.keys import KeySet
from repro.db.shard.spec import ShardSpec
from repro.db.table import (Table, append_rows, concat_ct_rows,
                            encrypt_constant, widen)

# compaction-fold pad rows (encryptions of 0) derive keys from this seed
_FOLD_PAD_SEED = 0xC0FD


def partition_offsets(n_rows: int, num_shards: int) -> np.ndarray:
    """[S+1] contiguous balanced split boundaries (first n%S chunks get
    the extra row)."""
    if not (1 <= num_shards <= n_rows):
        raise ValueError(
            f"num_shards {num_shards} outside [1, {n_rows}] rows")
    base, extra = divmod(n_rows, num_shards)
    sizes = np.full(num_shards, base, np.int64)
    sizes[:extra] += 1
    return np.concatenate([[0], np.cumsum(sizes)])


class ShardedTable:
    """Stacked encrypted columns `[S, N_sp, ...]` + partition bookkeeping.
    The stacks hold the shards' `Table` blocks in their storage form
    (int32, `db.table.store`); every accessor below but `scan_stack`
    returns int64."""

    def __init__(self, name: str, columns: Dict[str, Ciphertext],
                 offsets: np.ndarray, spec: ShardSpec):
        if not columns:
            raise ValueError("sharded table needs at least one column")
        shapes = {c: ct.c0.shape[:2] for c, ct in columns.items()}
        S, n_sp = next(iter(shapes.values()))
        if any(v != (S, n_sp) for v in shapes.values()):
            raise ValueError(f"ragged column stacks: {shapes}")
        if S != spec.num_shards:
            raise ValueError(f"stack has {S} shards, spec {spec.num_shards}")
        if n_sp != next_pow2(n_sp):
            raise ValueError(f"per-shard block {n_sp} not a power of two")
        self.name = name
        self.columns = dict(columns)
        self.offsets = np.asarray(offsets, np.int64)
        self.spec = spec
        self.shard_rows = np.diff(self.offsets)          # [S] valid counts
        # empty shards (0 rows) are legal — a shard can drain to empty
        # through deletes; only overflow is a geometry error
        if int(self.shard_rows.max()) > n_sp or int(self.shard_rows.min()) < 0:
            raise ValueError(
                f"shard sizes {self.shard_rows} outside [0, {n_sp}]")
        # -- id map: starts contiguous, stays authoritative ------------
        n = int(self.offsets[-1])
        self._n_base = n
        self._gid_shard = np.repeat(np.arange(S, dtype=np.int64),
                                    self.shard_rows)
        self._gid_pos = np.concatenate(
            [np.arange(int(c), dtype=np.int64) for c in self.shard_rows]
            or [np.zeros(0, np.int64)])
        self._gid_in_delta = np.zeros(n, bool)
        slot_gid = np.full((S, n_sp), -1, np.int64)
        for s in range(S):
            c = int(self.shard_rows[s])
            slot_gid[s, :c] = np.arange(int(self.offsets[s]),
                                        int(self.offsets[s]) + c)
        self._slot_gid = slot_gid
        # -- write-path state ------------------------------------------
        self.deltas: List[Optional[Table]] = [None] * S
        self._delta_gids: List[np.ndarray] = [np.zeros(0, np.int64)
                                              for _ in range(S)]
        self._dead = np.zeros(n, bool)
        self.version = 0
        self._delta_index_cache: Dict[tuple, tuple] = {}

    # -- construction ------------------------------------------------------

    @classmethod
    def from_arrays(cls, ks: KeySet, name: str,
                    data: Dict[str, np.ndarray], key: jax.Array, *,
                    spec: ShardSpec) -> "ShardedTable":
        """Encrypt host arrays straight into the sharded layout.

        Each shard's chunk encrypts under its own fold_in key via
        `Table.from_arrays` (one batched encrypt per column per shard),
        all padded to the common N_sp block.  On a mesh, each shard
        encrypts on the default device (one compiled program serves
        every shard) and has moved to the device that holds it before
        the next shard starts (`ShardSpec.stack` then assembles in
        place), so no device holds more than its own shards and the one
        in flight.
        """
        n_rows = len(next(iter(data.values())))
        offsets = partition_offsets(n_rows, spec.num_shards)
        n_sp = next_pow2(int(np.diff(offsets).max()))
        devs = spec.shard_devices()
        stacks: Dict[str, list] = {c: [] for c in data}
        for s in range(spec.num_shards):
            lo, hi = int(offsets[s]), int(offsets[s + 1])
            chunk = {c: np.asarray(v)[lo:hi] for c, v in data.items()}
            t = Table.from_arrays(ks, f"{name}.s{s}", chunk,
                                  jax.random.fold_in(key, s), n_padded=n_sp)
            for c in data:
                stacks[c].append(t.columns[c] if devs is None else
                                 jax.block_until_ready(jax.device_put(
                                     t.columns[c], devs[s])))
            del t          # shard s has left the default device
        columns = {c: Ciphertext(spec.stack([ct.c0 for ct in cts]),
                                 spec.stack([ct.c1 for ct in cts]))
                   for c, cts in stacks.items()}
        return cls(name, columns, offsets, spec)

    @classmethod
    def from_table(cls, ks: KeySet, table: Table, *,
                   spec: ShardSpec) -> "ShardedTable":
        """Re-partition an existing `Table`'s ciphertext rows (server-side:
        slices existing encryptions, pads with public-key encryptions of 0
        exactly like `Table` ingest — no plaintext access needed).
        Tombstones carry over; a pending delta run is refused (compact
        first — the partitioner slices base slots)."""
        if table.has_delta:
            raise ValueError(
                f"table {table.name!r} has {table.n_delta} uncompacted "
                "delta rows — compact before re-partitioning "
                "(repro.db.delta.compact)")
        offsets = partition_offsets(table.n_rows, spec.num_shards)
        n_sp = next_pow2(int(np.diff(offsets).max()))
        pad_key = jax.random.PRNGKey(0x5AAD)
        columns = {}
        for ci, (cname, ct) in enumerate(table.columns.items()):
            c0s, c1s = [], []
            for s in range(spec.num_shards):
                lo, hi = int(offsets[s]), int(offsets[s + 1])
                c0, c1 = ct.c0[lo:hi], ct.c1[lo:hi]
                if hi - lo < n_sp:
                    # same pad semantics as `Table` ingest (pad_rows_pow2
                    # with pad_value=0): genuine encryptions of 0, masked
                    # out by shard validity
                    pad = encrypt_constant(
                        ks, 0, n_sp - (hi - lo),
                        jax.random.fold_in(pad_key, ci * 1024 + s))
                    c0 = jnp.concatenate([c0, pad.c0])
                    c1 = jnp.concatenate([c1, pad.c1])
                c0s.append(c0)
                c1s.append(c1)
            columns[cname] = Ciphertext(jnp.stack(c0s), jnp.stack(c1s))
        st = cls(table.name, spec.place(columns), offsets, spec)
        st._dead = table._dead.copy()
        return st

    # -- geometry ----------------------------------------------------------

    @property
    def num_shards(self) -> int:
        """Logical shard count S (the stacks' leading dim)."""
        return int(self.spec.num_shards)

    @property
    def n_rows(self) -> int:
        """Total BASE rows across all shards (excludes pending delta
        rows — see `n_total` for the full global id space)."""
        return self._n_base

    @property
    def n_padded_per_shard(self) -> int:
        """The common power-of-two per-shard block size N_sp."""
        return next(iter(self.columns.values())).c0.shape[1]

    @property
    def column_names(self) -> tuple:
        """Names of the encrypted columns."""
        return tuple(self.columns)

    def shard_valid(self, s: int) -> np.ndarray:
        """[N_sp] bool — BASE data slots of shard s."""
        return np.arange(self.n_padded_per_shard) < int(self.shard_rows[s])

    def ciphertext_bytes(self) -> int:
        """Storage footprint of all encrypted column stacks + deltas."""
        total = sum(ct.c0.nbytes + ct.c1.nbytes
                    for ct in self.columns.values())
        for d in self.deltas:
            if d is not None:
                total += d.ciphertext_bytes()
        return total

    # -- write path --------------------------------------------------------

    def delta_rows(self, s: int) -> int:
        """Rows pending in shard s's delta run."""
        d = self.deltas[s]
        return 0 if d is None else d.n_rows

    @property
    def n_delta(self) -> int:
        """Total pending delta rows across all shards."""
        return sum(self.delta_rows(s) for s in range(self.num_shards))

    @property
    def n_total(self) -> int:
        """Size of the global row-id space: base + delta rows."""
        return self._n_base + self.n_delta

    @property
    def has_delta(self) -> bool:
        """True while any shard holds an uncompacted delta run."""
        return self.n_delta > 0

    @property
    def alive(self) -> np.ndarray:
        """[n_total] bool — False exactly on tombstoned global ids."""
        return ~self._dead

    @property
    def is_mutated(self) -> bool:
        """True if any mutation is outstanding (delta rows or
        tombstones)."""
        return self.has_delta or bool(self._dead.any())

    @property
    def delta_block(self) -> int:
        """Common scan-block size for the shards' delta runs: the
        largest run's padded size (shards with smaller/no runs zero-pad
        their scan lanes — those slots are invalid and never decoded)."""
        return max((d.n_padded for d in self.deltas if d is not None),
                   default=0)

    def insert(self, ks: KeySet, data: Dict[str, np.ndarray],
               key: jax.Array) -> np.ndarray:
        """Append new rows, routed to the least-loaded shards (keeps the
        partition balanced without moving any existing row); returns
        their global ids.  Each receiving shard encrypts its chunk into
        its own delta run under `fold_in(key, s)` — one batched encrypt
        per column per touched shard."""
        if set(data) != set(self.columns):
            raise ValueError(
                f"insert columns {sorted(data)} != table columns "
                f"{sorted(self.columns)}")
        m = len(next(iter(data.values())))
        if m == 0:
            return np.zeros(0, np.int64)
        S = self.num_shards
        loads = self.shard_rows.astype(np.int64).copy()
        loads += np.asarray([self.delta_rows(s) for s in range(S)])
        counts = np.zeros(S, np.int64)
        for _ in range(m):
            s = int(np.argmin(loads))
            loads[s] += 1
            counts[s] += 1
        offs = np.concatenate([[0], np.cumsum(counts)])
        start = self.n_total
        new_pos = np.zeros(m, np.int64)
        for s in range(S):
            c = int(counts[s])
            if c == 0:
                continue
            sl = slice(int(offs[s]), int(offs[s + 1]))
            chunk = {cn: np.asarray(v)[sl] for cn, v in data.items()}
            dt = Table.from_arrays(ks, f"{self.name}.s{s}.delta", chunk,
                                   jax.random.fold_in(key, s))
            prev = self.delta_rows(s)
            self.deltas[s] = (dt if self.deltas[s] is None
                              else append_rows(ks, self.deltas[s], dt))
            gids = start + np.arange(sl.start, sl.stop, dtype=np.int64)
            self._delta_gids[s] = np.concatenate([self._delta_gids[s], gids])
            new_pos[sl] = prev + np.arange(c)
        self._gid_shard = np.concatenate(
            [self._gid_shard, np.repeat(np.arange(S, dtype=np.int64),
                                        counts)])
        self._gid_pos = np.concatenate([self._gid_pos, new_pos])
        self._gid_in_delta = np.concatenate(
            [self._gid_in_delta, np.ones(m, bool)])
        self._dead = np.concatenate([self._dead, np.zeros(m, bool)])
        self._invalidate()
        return start + np.arange(m, dtype=np.int64)

    def delete(self, rows) -> int:
        """Tombstone the given GLOBAL row ids (host-side; ciphertext
        rows stay in place and every read path masks them out).
        Returns the number of newly-dead rows."""
        idx = np.asarray(rows, np.int64)
        if idx.size and (idx.min() < 0 or idx.max() >= self.n_total):
            raise IndexError(f"row ids outside [0, {self.n_total}): {idx}")
        newly = int((~self._dead[idx]).sum())
        self._dead[idx] = True
        self._invalidate()
        return newly

    def update(self, ks: KeySet, rows, data: Dict[str, np.ndarray],
               key: jax.Array) -> np.ndarray:
        """Replace rows: tombstone `rows`, insert their new versions
        (delta-store update identity).  Returns the new global ids."""
        self.delete(rows)
        return self.insert(ks, data, key)

    def _invalidate(self) -> None:
        self.version += 1
        self._delta_index_cache.clear()

    def delta_index(self, ks: KeySet, column: str, s: int):
        """Per-shard, per-run `SortedIndex` over shard s's CURRENT delta
        run (lazily built, cached until the next mutation); None when
        shard s has no pending rows.  Probes cost <= 2·ceil(log2 d_s)
        compares per Range/Eq on top of the base fan-out search."""
        if self.delta_rows(s) == 0:
            return None
        from repro.db.index import SortedIndex
        hit = self._delta_index_cache.get((column, s))
        if hit is not None and hit[0] == self.version:
            return hit[1]
        idx = SortedIndex.build(ks, self.deltas[s], column)
        self._delta_index_cache[(column, s)] = (self.version, idx)
        return idx

    def _fold_deltas(self, ks: KeySet) -> None:
        """Compaction fold (called by `repro.db.delta.compact` AFTER the
        index merges): append each shard's delta ciphertext rows onto
        the end of that shard's base block, growing the common block to
        the next power of two if any shard overflows — fresh encryptions
        of 0 pad the slack, no existing row is re-encrypted.  Global ids
        are unchanged; the id map flips the folded rows from delta to
        base ownership."""
        if not self.has_delta:
            return
        S, n_sp = self.num_shards, self.n_padded_per_shard
        d = np.asarray([self.delta_rows(s) for s in range(S)], np.int64)
        new_rows = self.shard_rows + d
        new_sp = next_pow2(int(new_rows.max()))
        pad_key = jax.random.PRNGKey(_FOLD_PAD_SEED)
        columns = {}
        for ci, (cname, ct) in enumerate(self.columns.items()):
            c0s, c1s = [], []
            for s in range(S):
                b, ds = int(self.shard_rows[s]), int(d[s])
                parts = [Ciphertext(ct.c0[s, :b], ct.c1[s, :b])]
                if ds:
                    dct = self.deltas[s].columns[cname]
                    parts.append(Ciphertext(dct.c0[:ds], dct.c1[:ds]))
                if b + ds < new_sp:
                    salt = ci * 65536 + s * 256 + self.version % 256
                    parts.append(encrypt_constant(
                        ks, 0, new_sp - b - ds,
                        jax.random.fold_in(pad_key, salt)))
                stacked = concat_ct_rows(*parts)
                c0s.append(stacked.c0)
                c1s.append(stacked.c1)
            columns[cname] = Ciphertext(jnp.stack(c0s), jnp.stack(c1s))
        self.columns = self.spec.place(columns)
        slot_gid = np.full((S, new_sp), -1, np.int64)
        slot_gid[:, :n_sp] = self._slot_gid
        for s in range(S):
            gids = self._delta_gids[s]
            b = int(self.shard_rows[s])
            slot_gid[s, b:b + gids.size] = gids
            self._gid_in_delta[gids] = False
            self._gid_pos[gids] = b + np.arange(gids.size)
        self._slot_gid = slot_gid
        self.shard_rows = new_rows
        self._n_base = int(new_rows.sum())
        self.deltas = [None] * S
        self._delta_gids = [np.zeros(0, np.int64) for _ in range(S)]
        self._invalidate()

    # -- row-id algebra ----------------------------------------------------

    def global_ids(self, s: int) -> np.ndarray:
        """[N_sp] global row id per BASE slot of shard s (-1 on pads)."""
        return self._slot_gid[s]

    @property
    def shard_scan_width(self) -> int:
        """Uniform per-shard scan width: base block + delta block."""
        return self.n_padded_per_shard + self.delta_block

    def shard_slot_gids(self, s: int) -> np.ndarray:
        """[shard_scan_width] global id per UNION scan slot of shard s
        (-1 on pads and on other shards' share of the delta block)."""
        ids = np.full(self.shard_scan_width, -1, np.int64)
        ids[:self.n_padded_per_shard] = self._slot_gid[s]
        gids = self._delta_gids[s]
        ids[self.n_padded_per_shard:self.n_padded_per_shard + gids.size] = gids
        return ids

    def shard_slot_valid(self, s: int) -> np.ndarray:
        """[shard_scan_width] bool — live union slots of shard s (pads
        AND tombstones excluded)."""
        gids = self.shard_slot_gids(s)
        ok = gids >= 0
        ok[ok] &= self.alive[gids[ok]]
        return ok

    def shard_of(self, global_rows) -> np.ndarray:
        """Owning shard per global row id (map lookup — valid for base
        and delta rows alike)."""
        return self._gid_shard[np.asarray(global_rows, np.int64)]

    def locate(self, global_rows) -> tuple:
        """global ids -> (shard idx, position) arrays.  The position is
        a BASE slot for base-resident rows and a delta-run-local index
        for rows still pending in a delta (`_gid_in_delta`); use
        `gather_global` for ciphertext access that handles both."""
        gids = np.asarray(global_rows, np.int64)
        return self._gid_shard[gids], self._gid_pos[gids]

    # -- access ------------------------------------------------------------

    def shard(self, s: int) -> Table:
        """Shard s's BASE block as a plain `Table` view (per-shard index
        builds etc.)."""
        cols = {c: Ciphertext(ct.c0[s], ct.c1[s])
                for c, ct in self.columns.items()}
        return Table(f"{self.name}.s{s}", cols, int(self.shard_rows[s]))

    @staticmethod
    def _piece(x: jax.Array, s: int):
        """(the device-local array holding shard s of stack x, s's index
        in it)."""
        for piece in x.addressable_shards:
            start, stop, _ = piece.index[0].indices(x.shape[0])
            if start <= s < stop:
                return piece.data, s - start
        raise ValueError(f"shard {s} is not addressable here")

    def shard_block(self, name: str, s: int) -> Ciphertext:
        """Shard s's BASE block [N_sp, K, n] as int64, read where it
        lives: on a mesh, from the device holding shard s (no
        cross-device program touches the rest of the stack)."""
        ct = self.columns[name]
        if self.spec.shard_devices() is None:
            return widen(Ciphertext(ct.c0[s], ct.c1[s]))
        (d0, i), (d1, _) = self._piece(ct.c0, s), self._piece(ct.c1, s)
        return widen(Ciphertext(d0[i], d1[i]))

    def _base_rows(self, name: str, s, pos) -> Ciphertext:
        """Rows pos[j] of shard s[j]'s BASE block, as int64.  On a mesh,
        each shard's rows are gathered on the device holding it and
        brought to the default device — a gather over the sharded stack
        could have the compiler assemble the whole stack on one
        device."""
        ct = self.columns[name]
        if self.spec.shard_devices() is None:
            return widen(Ciphertext(ct.c0[s, pos], ct.c1[s, pos]))
        s, pos = np.asarray(s, np.int64), np.asarray(pos, np.int64)
        home = jax.devices()[0]
        order, parts0, parts1 = [], [], []
        for sh in np.unique(s):
            sel = np.nonzero(s == sh)[0]
            order.append(sel)
            for parts, x in ((parts0, ct.c0), (parts1, ct.c1)):
                data, i = self._piece(x, int(sh))
                parts.append(jax.device_put(data[i, pos[sel]], home))
        if not order:
            empty = jnp.zeros((0,) + ct.c0.shape[2:], jnp.int64)
            return Ciphertext(empty, empty)
        inv = np.argsort(np.concatenate(order))
        return widen(Ciphertext(jnp.concatenate(parts0)[inv],
                                jnp.concatenate(parts1)[inv]))

    def gather(self, name: str, s: int, local_rows) -> Ciphertext:
        """Ciphertext rows of shard s's BASE block at local slots (int64)."""
        idx = np.asarray(local_rows, np.int64)
        return self._base_rows(name, np.full(idx.shape, s, np.int64), idx)

    def scan_stack(self, name: str) -> Ciphertext:
        """The named column over the UNION scan: `[S, shard_scan_width,
        ...]`, stored int32 (the sharded scan widens each tile inside
        its eval program) — each shard's base block then its delta run,
        zero-padded to the common delta block (pad lanes are never
        decoded: the per-shard validity masks them before any host-side
        threshold).
        With no pending delta this is the base stack unchanged, so the
        fused launch shape — and its jit cache entry — is stable across
        the compacted steady state."""
        ct = self.columns[name]
        D = self.delta_block
        if D == 0:
            return ct
        S = self.num_shards
        dc0s, dc1s = [], []
        for s in range(S):
            d = self.deltas[s]
            z0 = jnp.zeros((D,) + ct.c0.shape[2:], ct.c0.dtype)
            z1 = jnp.zeros((D,) + ct.c1.shape[2:], ct.c1.dtype)
            if d is None:
                dc0s.append(z0)
                dc1s.append(z1)
            else:
                dct = d.columns[name]
                dc0s.append(z0.at[:dct.c0.shape[0]].set(dct.c0))
                dc1s.append(z1.at[:dct.c1.shape[0]].set(dct.c1))
        return Ciphertext(
            jnp.concatenate([ct.c0, jnp.stack(dc0s)], axis=1),
            jnp.concatenate([ct.c1, jnp.stack(dc1s)], axis=1))

    def gather_global(self, name: str, global_rows) -> Ciphertext:
        """Ciphertext rows at GLOBAL row ids, as int64 (cross-shard
        projection; resolves base slots and pending delta rows alike)."""
        gids = np.asarray(global_rows, np.int64)
        ct = self.columns[name]
        s, pos = self._gid_shard[gids], self._gid_pos[gids]
        in_delta = self._gid_in_delta[gids]
        if not in_delta.any():
            return self._base_rows(name, s, pos)
        c0 = jnp.zeros((gids.size,) + ct.c0.shape[2:], jnp.int64)
        c1 = jnp.zeros((gids.size,) + ct.c1.shape[2:], jnp.int64)
        bi = np.nonzero(~in_delta)[0]
        if bi.size:
            base = self._base_rows(name, s[bi], pos[bi])
            c0 = c0.at[bi].set(base.c0)
            c1 = c1.at[bi].set(base.c1)
        for sh in np.unique(s[in_delta]):
            di = np.nonzero(in_delta & (s == sh))[0]
            dct = self.deltas[int(sh)].columns[name]
            c0 = c0.at[di].set(dct.c0[pos[di]])
            c1 = c1.at[di].set(dct.c1[pos[di]])
        return Ciphertext(c0, c1)

    def decrypt_column(self, ks: KeySet, name: str) -> np.ndarray:
        """Client-side helper (tests only — needs sk): ALL rows of the
        global id space in id order (pending delta rows included;
        tombstoned rows included — filter with `alive`)."""
        ct = self.columns[name]
        vals = np.asarray(E.decrypt(ks, widen(
            Ciphertext(ct.c0.reshape((-1,) + ct.c0.shape[2:]),
                       ct.c1.reshape((-1,) + ct.c1.shape[2:])))))
        vals = vals.reshape(self.num_shards, self.n_padded_per_shard)
        out = np.zeros(self.n_total, vals.dtype)
        base = ~self._gid_in_delta
        g = np.nonzero(base)[0]
        out[g] = vals[self._gid_shard[g], self._gid_pos[g]]
        for s in range(self.num_shards):
            if self.delta_rows(s):
                out[self._delta_gids[s]] = (
                    self.deltas[s].decrypt_column(ks, name))
        return out

    def __repr__(self) -> str:
        return (f"ShardedTable({self.name!r}, rows={self.n_rows}, "
                f"shards={self.num_shards}x{self.n_padded_per_shard}, "
                f"cols={list(self.columns)}, spec={self.spec}"
                + (f", delta={self.n_delta}" if self.has_delta else "")
                + ")")
