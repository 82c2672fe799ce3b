"""Encrypted column-store `Table` for the repro.db engine.

A table owns named `Ciphertext` columns over the same logical rows.  Rows
are padded to the next power of two at ingest (static shapes: every
downstream sort/merge network and fused scan compiles once per table
size), with a host-side validity mask excluding the pad rows from query
results.  The pad rows are real encryptions of 0 — the server cannot
distinguish them from data rows by inspection, only the table's public
row count reveals the split.

STORAGE.  Column blocks, base and delta, live on the device as int32
residues (`store`): every tower modulus lies below 2^31, so int32 holds
a ciphertext exactly in half the bytes of the int64 that `core.ring`
computes in.  The fused scan cuts its tiles from the int32 blocks
(`scan_parts`) and widens them inside the eval program; every other
reader (`gather`, `column`, `scan_column`, `decrypt_column`) gets int64
(`widen`).  On a TPU an int64 array is a pair of 32-bit planes, and a
program that takes an int64 block splits the whole block, whatever part
of it the program reads; an int32 block is read in place.

Encryption is batched and chunked: `encrypt_rows` encrypts a column
`INGEST_CHUNK_ROWS` rows per launch straight into its preallocated int32
device buffers, so ingest peaks at the column plus one chunk of
intermediates (a paper-bfv hg38 column alone is 4 GiB).

WRITE PATH.  A table is mutable through `insert` / `update` / `delete`:

  * `insert` encrypts the new rows into a small DELTA RUN — a plain
    pow2-padded `Table` hanging off the base (`self.delta`).  Appending
    to an existing run concatenates ciphertext rows and re-pads; base
    rows are NEVER re-encrypted.  New rows take global ids past the end
    of the current id space, so ids are stable across later compaction.
  * `delete` records a host-side TOMBSTONE over global row ids (the
    comparison outcomes are host-visible anyway, so hiding liveness
    would not change the threat model); tombstoned rows stay encrypted
    in place and every read path masks them out.
  * `update` is tombstone + re-insert (the delta-store identity).

Readers answer over base ∪ delta: the SCAN VIEW (`scan_column`,
`slot_valid`, `slot_global_ids`) presents the base block and the delta
block as one concatenated slot space so a fused filter launch covers
both in a single raw-eval program.  `repro.db.delta.compact` folds the
delta run back into the base (and merges it into any `SortedIndex`
through the log-depth merge network) — see that module.
"""
from __future__ import annotations

import functools
import zlib
from typing import Dict, Iterable, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.core import encrypt as E
from repro.core.compare import next_pow2
from repro.core.encrypt import Ciphertext
from repro.core.keys import KeySet

# pad rows appended by ciphertext-level concat/re-pad (delta growth,
# compaction) encrypt 0 under keys folded from this seed — same
# public-key construction as `ShardedTable.from_table`'s 0x5AAD pads
_APPEND_PAD_SEED = 0xDE17A

# rows per encrypt launch at ingest: one chunk's NTT intermediates sit
# beside the column being filled
INGEST_CHUNK_ROWS = 4096


def rows_to_mask(rows, n_padded: int) -> np.ndarray:
    """Row-id list -> [n_padded] bool mask (shared by index + executor +
    server so mask construction has exactly one implementation)."""
    mask = np.zeros(n_padded, bool)
    mask[np.asarray(rows, dtype=np.int64)] = True
    return mask


def column_key(key: jax.Array, cname: str) -> jax.Array:
    """Per-column encryption key: fold in crc32 of the column NAME, not
    its dict position — a delta run presenting the same columns in a
    different order must encrypt under the same per-column streams as
    the base ingest (same determinism rationale as dataset seeding)."""
    return jax.random.fold_in(key, zlib.crc32(cname.encode()))


def pad_rows_pow2(arr: np.ndarray, *, n_target: Optional[int] = None,
                  pad_value: float = 0) -> np.ndarray:
    """Pad a host column to a power-of-two row count — THE row-padding
    implementation shared by `Table` and `ShardedTable` ingest.

    `n_target` (default: `next_pow2(len(arr))`) lets a sharded table pad
    every shard to one common block size so the stacks align.  Geometry
    comes from the same `next_pow2` that sizes `encrypted_sort`'s
    ciphertext-level sentinel padding (`core.compare._pad_to_pow2`), so
    ingest padding and sort-network padding can never disagree about the
    padded shape; the pad VALUE here is 0 (excluded via the validity
    mask), while the sort networks pad with in-headroom sentinels.
    An EMPTY column pads to the minimum block of one slot
    (`next_pow2(0) == 1`) — empty tables are representable.
    """
    arr = np.asarray(arr)
    n_rows = arr.shape[0]
    n_padded = next_pow2(n_rows) if n_target is None else int(n_target)
    if n_padded < max(n_rows, 1) or n_padded != next_pow2(n_padded):
        raise ValueError(
            f"n_target {n_padded} must be a power of two >= {n_rows}")
    is_float = np.issubdtype(arr.dtype, np.floating)
    padded = np.full((n_padded,), pad_value,
                     np.float64 if is_float else np.int64)
    padded[:n_rows] = arr
    return padded


def store(ct: Ciphertext) -> Ciphertext:
    """The storage form of a ciphertext: int32 residues (exact, as every
    residue lies in [0, q) with q < 2^31).  A no-op on int32."""
    return Ciphertext(ct.c0.astype(jnp.int32), ct.c1.astype(jnp.int32))


def widen(ct: Ciphertext) -> Ciphertext:
    """The int64 form `core.ring` arithmetic takes.  A no-op on int64."""
    return Ciphertext(ct.c0.astype(jnp.int64), ct.c1.astype(jnp.int64))


@jax.jit
def take_rows(ct: Ciphertext, idx: jax.Array) -> Ciphertext:
    """Rows `idx` of a stored block, widened to int64: one program per
    shape, the gather and the conversion together."""
    return widen(Ciphertext(ct.c0[idx], ct.c1[idx]))


@functools.partial(jax.jit, donate_argnums=(0, 1))
def ingest_write(c0: jax.Array, c1: jax.Array, ct: Ciphertext,
                 lo) -> tuple:
    """Write one encrypted chunk into the donated int32 column buffers at
    row `lo`, narrowing it as it is written: no int64 column is ever
    allocated."""
    return (jax.lax.dynamic_update_slice_in_dim(
                c0, ct.c0.astype(jnp.int32), lo, 0),
            jax.lax.dynamic_update_slice_in_dim(
                c1, ct.c1.astype(jnp.int32), lo, 0))


def concat_ct_rows(*cts: Ciphertext) -> Ciphertext:
    """Concatenate ciphertext row stacks along the leading (row) dim —
    the ciphertext-level append used by delta growth, compaction and the
    union scan view.  Pure slicing/stacking of existing encryptions."""
    return Ciphertext(jnp.concatenate([ct.c0 for ct in cts]),
                      jnp.concatenate([ct.c1 for ct in cts]))


def encrypt_rows(ks: KeySet, m: jax.Array, key: jax.Array, *,
                 fae: bool = False) -> Ciphertext:
    """Encrypt a [N] plaintext column on the device into its storage
    form (`store`), `INGEST_CHUNK_ROWS` rows per launch.  A column of at
    most one chunk is one `encrypt` call under `key`; a longer one (N a
    multiple of the chunk) is written chunk by chunk into preallocated
    int32 [N, K, n] buffers (`ingest_write`: donated, so updated in
    place), chunk i encrypting under `fold_in(key, i)`.  The encrypt
    program depends on the chunk size only, not on N."""
    chunk = INGEST_CHUNK_ROWS
    N = int(m.shape[0])
    if N <= chunk:
        return store((E.encrypt_fae if fae else E.encrypt)(ks, m, key))
    if N % chunk:
        raise ValueError(f"{N} rows do not split into chunks of {chunk}")
    from repro.db.executor import _jitted     # circular at module scope

    def enc(m, key, i):
        return (E.encrypt_fae if fae else E.encrypt)(
            ks, m, jax.random.fold_in(key, i))

    enc = _jitted(ks, f"ingest_encrypt_fae{fae}", enc)
    shape = (N, ks.params.num_towers, ks.params.n)
    c0 = jnp.zeros(shape, jnp.int32)
    c1 = jnp.zeros(shape, jnp.int32)
    for i in range(N // chunk):
        lo = i * chunk
        c0, c1 = ingest_write(c0, c1, enc(m[lo:lo + chunk], key, i), lo)
    return Ciphertext(c0, c1)


def encrypt_constant(ks: KeySet, value: int, count: int,
                     key: jax.Array) -> Ciphertext:
    """`count` fresh encryptions of `value` (sentinel and zero pads), in
    storage form.  They go through `encrypt_rows`, past one ingest chunk
    rounded up to whole chunks: a single eager encryption of thousands
    of paper-bfv rows holds several column-sized NTT intermediates at
    once."""
    chunk = INGEST_CHUNK_ROWS
    n = count if count <= chunk else -(-count // chunk) * chunk
    ct = encrypt_rows(ks, jnp.full((n,), value, jnp.int64), key)
    return ct if n == count else Ciphertext(ct.c0[:count], ct.c1[:count])


def _zero_pad_rows(ks: KeySet, cname: str, n_pad: int,
                   salt: int) -> Ciphertext:
    """`n_pad` fresh public-key encryptions of 0 (append-path padding)."""
    key = jax.random.fold_in(column_key(jax.random.PRNGKey(_APPEND_PAD_SEED),
                                        cname), salt)
    return encrypt_constant(ks, 0, n_pad, key)


class Table:
    """Named encrypted columns + row-count bookkeeping + delta-run state.
    Every column block is held in storage form (`store`, int32)."""

    def __init__(self, name: str, columns: Dict[str, Ciphertext],
                 n_rows: int):
        if not columns:
            raise ValueError("table needs at least one column")
        shapes = {c: ct.c0.shape[0] for c, ct in columns.items()}
        n_padded = next(iter(shapes.values()))
        if any(v != n_padded for v in shapes.values()):
            raise ValueError(f"ragged columns: {shapes}")
        if n_padded < 1 or n_padded & (n_padded - 1):
            raise ValueError(f"padded row count {n_padded} not a power of two")
        # n_rows == 0 is legal: an empty table is one all-pad block (the
        # write path starts from `Table.empty` and freshly-compacted
        # delta runs are empty) — the invariant is 0 <= n_rows <= padded
        if not (0 <= n_rows <= n_padded):
            raise ValueError(f"n_rows {n_rows} outside [0, {n_padded}]")
        self.name = name
        self.columns = {c: store(ct) for c, ct in columns.items()}
        self.n_rows = int(n_rows)
        # -- write-path state (all host-side) --------------------------
        self.delta: Optional["Table"] = None     # pending insert run
        self._dead = np.zeros(self.n_rows, bool)  # tombstones, global ids
        self.version = 0                          # bumped per mutation
        self._delta_index_cache: Dict[str, tuple] = {}

    # -- construction ------------------------------------------------------

    @classmethod
    def from_arrays(cls, ks: KeySet, name: str,
                    data: Dict[str, np.ndarray], key: jax.Array, *,
                    fae: bool = False,
                    n_padded: Optional[int] = None) -> "Table":
        """Encrypt host arrays into a padded column-store.

        data: {column: [n_rows] int (bfv) or float (ckks)}.  Under a
        CKKS profile every column is a float column (fixed-point encoded
        at Δ_enc; integer input is fine and stays exact within the
        profile's precision).  Under BFV, float input with fractional
        values is rejected — it would silently truncate; use a ckks
        profile for float columns.  `fae=True` uses perturbation-aware
        encryption (Alg. 3) — note this trades away exact
        Eq/point-lookup semantics by design.  `n_padded` overrides the
        default next-power-of-two target (sharded tables pad every
        shard to one common block size).  Zero-length arrays build an
        empty table (one all-pad block); per-column keys fold in the
        column NAME (`column_key`), so ingest is insertion-order
        independent.
        """
        lengths = {c: len(v) for c, v in data.items()}
        n_rows = next(iter(lengths.values()))
        if any(v != n_rows for v in lengths.values()):
            raise ValueError(f"ragged input columns: {lengths}")
        is_float = ks.params.profile.scheme == "ckks"
        columns = {}
        for cname, arr in data.items():
            arr = np.asarray(arr)
            if (not is_float and np.issubdtype(arr.dtype, np.floating)
                    and not np.array_equal(arr, np.trunc(arr))):
                raise ValueError(
                    f"column {cname!r}: fractional float values under a "
                    f"{ks.params.profile.scheme} profile would truncate — "
                    "use a ckks profile for float columns")
            padded = pad_rows_pow2(
                arr.astype(np.float64 if is_float else np.int64),
                n_target=n_padded)
            columns[cname] = encrypt_rows(ks, jnp.asarray(padded),
                                          column_key(key, cname), fae=fae)
        return cls(name, columns, n_rows)

    @classmethod
    def empty(cls, ks: KeySet, name: str, columns: Iterable[str],
              key: jax.Array) -> "Table":
        """A 0-row table over the named columns (one encrypted all-pad
        slot each) — the write path's starting point: `insert` grows it
        like any other table."""
        return cls.from_arrays(ks, name,
                               {c: np.zeros(0, np.int64) for c in columns},
                               key)

    # -- geometry ----------------------------------------------------------

    @property
    def n_padded(self) -> int:
        """Power-of-two padded row count of the BASE (every base
        column's leading dim; the delta run pads separately)."""
        return next(iter(self.columns.values())).c0.shape[0]

    @property
    def valid(self) -> np.ndarray:
        """[n_padded] bool — True on BASE data rows, False on pad rows
        (delta slots and tombstones are the scan view's concern:
        `slot_valid`)."""
        return np.arange(self.n_padded) < self.n_rows

    @property
    def column_names(self) -> tuple:
        """Names of the encrypted columns."""
        return tuple(self.columns)

    def ciphertext_bytes(self) -> int:
        """Storage footprint of all encrypted columns (base + delta)."""
        total = sum(ct.c0.nbytes + ct.c1.nbytes
                    for ct in self.columns.values())
        if self.delta is not None:
            total += self.delta.ciphertext_bytes()
        return total

    # -- write path --------------------------------------------------------

    @property
    def n_delta(self) -> int:
        """Rows currently pending in the delta run."""
        return 0 if self.delta is None else self.delta.n_rows

    @property
    def n_total(self) -> int:
        """Size of the global row-id space: base rows + delta rows
        (tombstoned rows included — ids are never reused)."""
        return self.n_rows + self.n_delta

    @property
    def has_delta(self) -> bool:
        """True while an uncompacted delta run holds pending inserts."""
        return self.n_delta > 0

    @property
    def alive(self) -> np.ndarray:
        """[n_total] bool — False exactly on tombstoned global ids."""
        return ~self._dead

    @property
    def is_mutated(self) -> bool:
        """True if any mutation is outstanding (delta rows or
        tombstones) — operators without union-read support (joins)
        check this and ask for a compaction first."""
        return self.has_delta or bool(self._dead.any())

    def insert(self, ks: KeySet, data: Dict[str, np.ndarray],
               key: jax.Array) -> np.ndarray:
        """Append new rows to the delta run; returns their global ids.

        One batched encrypt per column for the NEW rows only; growing an
        existing run concatenates ciphertext rows and re-pads to the
        next power of two — base rows are never touched, let alone
        re-encrypted.
        """
        if set(data) != set(self.columns):
            raise ValueError(
                f"insert columns {sorted(data)} != table columns "
                f"{sorted(self.columns)}")
        with obs.span("table.insert", table=self.name) as sp:
            with obs.span("table.encrypt"):     # server-side, new rows only
                new = Table.from_arrays(ks, f"{self.name}.delta", data, key)
            sp.set(rows=new.n_rows)
            start = self.n_total
            if new.n_rows == 0:
                return np.zeros(0, np.int64)
            if self.delta is None:
                self.delta = new
            else:
                self.delta = append_rows(ks, self.delta, new)
            self._dead = np.concatenate(
                [self._dead, np.zeros(new.n_rows, bool)])
            self._invalidate()
            return start + np.arange(new.n_rows, dtype=np.int64)

    def delete(self, rows) -> int:
        """Tombstone the given GLOBAL row ids (host-side mask; the
        ciphertext rows stay in place and every read path excludes
        them).  Returns the number of newly-dead rows."""
        idx = np.asarray(rows, np.int64)
        if idx.size and (idx.min() < 0 or idx.max() >= self.n_total):
            raise IndexError(
                f"row ids outside [0, {self.n_total}): {idx}")
        newly = int((~self._dead[idx]).sum())
        self._dead[idx] = True
        self._invalidate()
        return newly

    def update(self, ks: KeySet, rows, data: Dict[str, np.ndarray],
               key: jax.Array) -> np.ndarray:
        """Replace rows: tombstone `rows`, insert their new versions
        into the delta run (the delta-store update identity).  Returns
        the replacement rows' global ids."""
        self.delete(rows)
        return self.insert(ks, data, key)

    def _invalidate(self) -> None:
        self.version += 1
        self._delta_index_cache.clear()

    # -- scan view (base ∪ delta as one slot space) ------------------------

    @property
    def scan_width(self) -> int:
        """Width of the union scan: base block + delta block slots."""
        return self.n_padded + (0 if self.delta is None
                                else self.delta.n_padded)

    def scan_parts(self, name: str) -> tuple:
        """The named column's stored int32 blocks in union-slot order:
        the base block, then the delta block when a delta run is
        pending.  The fused scan tiles each block in place, never
        concatenating them, and widens each tile inside its eval
        program."""
        if self.delta is None:
            return (self.columns[name],)
        return (self.columns[name], self.delta.columns[name])

    def scan_column(self, name: str) -> Ciphertext:
        """The named column over the UNION slot space — base block then
        delta block, concatenated ciphertext rows (what the fused filter
        launch scans, so base and delta ride ONE raw-eval program), as
        int64."""
        ct = self.columns[name]
        if self.delta is None:
            return widen(ct)
        return widen(concat_ct_rows(ct, self.delta.columns[name]))

    @property
    def slot_global_ids(self) -> np.ndarray:
        """[scan_width] global row id per scan slot (-1 on pad slots).
        Base slot i -> id i; delta slot j -> id n_rows + j."""
        ids = np.full(self.scan_width, -1, np.int64)
        ids[:self.n_rows] = np.arange(self.n_rows)
        if self.delta is not None:
            d = self.delta.n_rows
            ids[self.n_padded:self.n_padded + d] = self.n_rows + np.arange(d)
        return ids

    @property
    def slot_valid(self) -> np.ndarray:
        """[scan_width] bool — True on live data slots: pad slots AND
        tombstoned rows excluded (the mask every filter result is ANDed
        with)."""
        gids = self.slot_global_ids
        ok = gids >= 0
        ok[ok] &= self.alive[gids[ok]]
        return ok

    def delta_index(self, ks: KeySet, column: str):
        """Per-run `SortedIndex` over the CURRENT delta run, built
        lazily and cached until the next mutation.  Index probes answer
        base ∪ delta as base-search + this per-run binary search —
        <= 2·ceil(log2 |delta|) extra compares per Range/Eq.  Returns
        None when there is no pending delta."""
        if not self.has_delta:
            return None
        from repro.db.index import SortedIndex   # circular at module scope
        hit = self._delta_index_cache.get(column)
        if hit is not None and hit[0] == self.version:
            return hit[1]
        idx = SortedIndex.build(ks, self.delta, column)
        self._delta_index_cache[column] = (self.version, idx)
        return idx

    # -- access ------------------------------------------------------------

    def column(self, name: str) -> Ciphertext:
        """The named column's stacked BASE ciphertext rows, as int64 (see
        `scan_column` for the base ∪ delta view)."""
        return widen(self.columns[name])

    def gather(self, name: str, rows: Iterable[int]) -> Ciphertext:
        """Ciphertext rows of `name` at GLOBAL row ids, as int64 — ids
        past `n_rows` resolve into the delta run."""
        idx = np.asarray(rows, dtype=np.int64)
        ct = self.columns[name]
        if self.delta is None or idx.size == 0 or (idx < self.n_rows).all():
            return take_rows(ct, idx)
        dct = self.delta.columns[name]
        bi = np.nonzero(idx < self.n_rows)[0]
        di = np.nonzero(idx >= self.n_rows)[0]
        c0 = jnp.zeros((idx.size,) + ct.c0.shape[1:], jnp.int64)
        c1 = jnp.zeros((idx.size,) + ct.c1.shape[1:], jnp.int64)
        c0 = c0.at[bi].set(ct.c0[idx[bi]])
        c1 = c1.at[bi].set(ct.c1[idx[bi]])
        c0 = c0.at[di].set(dct.c0[idx[di] - self.n_rows])
        c1 = c1.at[di].set(dct.c1[idx[di] - self.n_rows])
        return Ciphertext(c0, c1)

    def decrypt_column(self, ks: KeySet, name: str, *,
                       include_padding: bool = False) -> np.ndarray:
        """Client-side helper (tests / verification only — needs sk).
        Returns ALL rows of the global id space in id order (base rows
        then delta rows; tombstoned rows included — filter with
        `alive`)."""
        if include_padding and self.delta is not None:
            raise ValueError("include_padding only applies to an "
                             "uncompacted-delta-free table")
        vals = np.asarray(E.decrypt(ks, widen(self.columns[name])))
        if include_padding:
            return vals
        vals = vals[:self.n_rows]
        if self.delta is not None:
            vals = np.concatenate(
                [vals, self.delta.decrypt_column(ks, name)])
        return vals

    def __repr__(self) -> str:
        return (f"Table({self.name!r}, rows={self.n_rows}"
                f" (padded {self.n_padded}), cols={list(self.columns)}"
                + (f", delta={self.n_delta}" if self.has_delta else "")
                + (f", dead={int(self._dead.sum())}"
                   if self._dead.any() else "") + ")")


def append_rows(ks: KeySet, base: "Table", new: "Table") -> "Table":
    """Ciphertext-level append: `base`'s valid rows + `new`'s valid
    rows, re-padded to the next power of two with fresh encryptions of
    0.  No row is re-encrypted — existing ciphertexts are sliced and
    concatenated (the same trick as `ShardedTable.from_table`), int32
    parts into an int32 block.  Used to grow a delta run and to fold a
    delta back into the base at compaction."""
    if set(base.columns) != set(new.columns):
        raise ValueError("column mismatch between runs")
    n_total = base.n_rows + new.n_rows
    n_pad = next_pow2(n_total)
    columns = {}
    for cname, ct in base.columns.items():
        nct = new.columns[cname]
        parts = [Ciphertext(ct.c0[:base.n_rows], ct.c1[:base.n_rows]),
                 Ciphertext(nct.c0[:new.n_rows], nct.c1[:new.n_rows])]
        if n_total < n_pad:
            parts.append(_zero_pad_rows(ks, cname, n_pad - n_total,
                                        salt=n_total))
        columns[cname] = concat_ct_rows(*parts)
    return Table(base.name, columns, n_total)
