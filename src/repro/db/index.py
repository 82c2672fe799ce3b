"""HADES sorted index: build once, answer lookups in O(log n) compares.

The index is built server-side with `encrypted_sort` — trapdoor (Alg. 4)
comparisons only, the server never decrypts.  It stores the column's
ciphertext rows in sorted order plus the permutation back to original row
ids.  Lookups then run encrypted *binary search*: each probe is one
HADES compare against a sorted row, so a point lookup or range boundary
costs ceil(log2 n) compares instead of the linear scan's n.

All searches are lane-batched: `search` takes B (value, strictness)
lanes and resolves them together — every binary-search step is ONE
batched Eval over B probes (a range query is 2 lanes; the multi-query
server stacks 2K lanes for K clients).  The per-step compare is jitted
once per lane count, so repeated queries pay only dispatch.

Float (CKKS) columns: every lane can carry its own decode threshold
(`taus`) — the probe Eval returns raw values and the ε-aware three-way
decode happens host-side, so an ε-band Eq and an exact Range ride the
same batched probe launch.  An ε-band point lookup resolves the
boundaries of [v-ε, v+ε] directly: lower lane "first row with
col > v - ε", upper lane "first row with col > v + ε", both expressed
through the widened τ_ε on the SAME trapdoor ciphertext — the client
sends one encrypted v, never ε-shifted plaintexts.

STORAGE.  The sorted copy is kept in the storage form of `db.table`
(`store`: int32 residues, exact as every residue lies in [0, q) with
q < 2^31), whatever built it — the constructor converts, so the base
build, the delta-run indexes, compaction's merged runs and the
per-shard runs of `ShardedIndex` all store int32.  A probe step is ONE
jitted program (`executor.jitted_probe`, `index_probe`) that takes the
copy as an operand, gathers the B probed rows, widens them to int64
and evaluates them: on a TPU an int64 copy is a pair of 32-bit planes,
and a program that takes it splits the whole copy, whatever few rows
it reads; an int32 copy is read in place.  Readers that compute on the
copy (`sorted_run`, the merge networks) get it widened.
"""
from __future__ import annotations

from typing import Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.core import compare as C
from repro.core.ckks import eps_to_tau
from repro.core.encrypt import Ciphertext
from repro.core.keys import KeySet
from repro.db.table import Table, rows_to_mask, store, widen


def _stack_cts(cts) -> Ciphertext:
    return Ciphertext(jnp.stack([ct.c0 for ct in cts]),
                      jnp.stack([ct.c1 for ct in cts]))


def eps_lane_taus(ks: KeySet, eps: Optional[float]) -> Optional[np.ndarray]:
    """The [lower, upper] boundary-lane decode thresholds an ε-band
    predicate resolves to (None = profile default) — one implementation
    for SortedIndex and the sharded fan-out index."""
    if eps is None:
        return None
    tau = eps_to_tau(ks.params, eps)
    return np.asarray([tau, tau], dtype=np.int64)


class SortedIndex:
    """Sorted ciphertext column (stored int32) + permutation, with
    encrypted binary search."""

    def __init__(self, column: str, sorted_ct: Ciphertext, perm: np.ndarray,
                 *, build_compares: int = 0):
        self.column = column
        self.sorted_ct = store(sorted_ct)
        self.perm = np.asarray(perm)
        self.n_rows = int(self.perm.shape[0])
        self.build_compares = build_compares
        self.search_compares = 0               # cumulative probe count
        self.last_probe_counts = np.zeros(0, np.int64)  # per-lane, last call
        self._probe: Optional[Callable] = None  # jitted probe step, lazy

    # -- construction ------------------------------------------------------

    @classmethod
    def build(cls, ks: KeySet, table: Table, column: str, *,
              comparator: Optional[Callable] = None) -> "SortedIndex":
        """Sort the column's valid rows once (server-side, O(n log^2 n)
        trapdoor compares); amortized over every subsequent lookup."""
        col = table.gather(column, np.arange(table.n_rows))
        if comparator is None:
            # the per-KeySet jitted comparator: every network stage, and
            # every later build of the same size, reuses one program
            from repro.db.executor import jitted_comparator
            comparator = jitted_comparator(ks)
        sorted_ct, perm = C.encrypted_sort(ks, col, comparator)
        del col
        return cls(column, sorted_ct, np.asarray(perm),
                   build_compares=C.bitonic_compare_count(table.n_rows))

    def sorted_run(self) -> tuple:
        """The index as an ascending (ciphertext run, row-id array) pair —
        the sort-merge join consumes this directly, so a join between two
        indexed columns pays ZERO extra sort compares (the build is
        already amortized across lookups).  The run is widened to the
        int64 that the merge networks compute in."""
        return widen(self.sorted_ct), self.perm

    # -- search ------------------------------------------------------------

    def _probe_step(self, ks: KeySet) -> Callable:
        """Jitted probe step — gather, widen, raw Eval (jit specializes
        per copy and lane shape) — shared per KeySet so a fresh
        delta-run index of a size seen before reuses its program.  The
        three-way decode happens host-side so each lane applies its
        own τ (profile default or ε-derived)."""
        if self._probe is None:
            from repro.db.executor import jitted_probe
            self._probe = jitted_probe(ks)
        return self._probe

    def _lane_taus(self, ks: KeySet, n_lanes: int,
                   taus: Optional[np.ndarray]) -> np.ndarray:
        if taus is None:
            return np.full(n_lanes, ks.params.tau, dtype=np.int64)
        taus = np.asarray(taus, dtype=np.int64)
        assert taus.shape == (n_lanes,)
        return taus

    def search(self, ks: KeySet, values: Ciphertext, strict: np.ndarray,
               taus: Optional[np.ndarray] = None) -> np.ndarray:
        """Batched boundary search over B lanes.

        values: ciphertexts with leading batch dim B (EncBasic trapdoors).
        strict[i] False -> lower bound: first sorted pos with col >= v_i;
        strict[i] True  -> upper bound: first sorted pos with col >  v_i.
        taus[i] (optional) is lane i's decode threshold: with a widened
        τ_ε, "col >= v" means "col > v - ε" and "col > v" means
        "col > v + ε" — the ε-aware boundary semantics the ε-band
        predicates lower to.  Every iteration is ONE program over the
        B probe lanes: the gather out of the stored copy and the
        batched Eval together.
        """
        strict = np.asarray(strict, bool)
        B = values.c0.shape[0]
        assert strict.shape == (B,)
        taus = self._lane_taus(ks, B, taus)
        step_fn = self._probe_step(ks)
        c0, c1 = self.sorted_ct
        lo = np.zeros(B, np.int64)
        hi = np.full(B, self.n_rows, np.int64)
        probes = np.zeros(B, np.int64)
        with obs.span("index.search", column=self.column, lanes=B,
                      rows=self.n_rows) as sp:
            step = 0
            while np.any(lo < hi):
                active = lo < hi
                # step self time: the probe program's dispatch; decode:
                # the device round trip + the host's bounds update
                with obs.span("index.step", step=step,
                              active=int(active.sum())):
                    mid = (lo + hi) // 2
                    probe = np.where(active, mid, 0)  # fixed shape; dead lanes
                    obs.jit_launch("index.probe", c0, probe, values.c0)
                    obs.count("eval.launches")
                    obs.count("eval.lanes", B)
                    v = step_fn(c0, c1, probe, values.c0, values.c1)
                    with obs.span("index.decode"):
                        v = np.asarray(v)                      # [B] raw
                        c = np.where(np.abs(v) < taus, 0,
                                     np.sign(v))               # per-lane τ
                        probes += active
                        go_left = np.where(strict, c > 0, c >= 0)
                        hi = np.where(active & go_left, mid, hi)
                        lo = np.where(active & ~go_left, mid + 1, lo)
                step += 1
            sp.set(probes=int(probes.sum()))
        obs.count("index.probes", int(probes.sum()))
        self.search_compares += int(probes.sum())
        self.last_probe_counts = probes            # per-lane attribution
        return lo

    def _eps_taus(self, ks: KeySet, eps: Optional[float]) -> Optional[np.ndarray]:
        return eps_lane_taus(ks, eps)

    def search_range(self, ks: KeySet, ct_lo: Ciphertext, ct_hi: Ciphertext,
                     *, eps: Optional[float] = None) -> np.ndarray:
        """Row ids with lo <= value <= hi — 2 lanes, ~2 log2 n compares.
        `eps` makes the bounds ε-inclusive (float columns)."""
        bounds = _stack_cts([ct_lo, ct_hi])
        l, r = self.search(ks, bounds, np.array([False, True]),
                           self._eps_taus(ks, eps))
        return self.perm[l:r]

    def point_lookup(self, ks: KeySet, ct_value: Ciphertext, *,
                     eps: Optional[float] = None) -> np.ndarray:
        """Row ids with value == v (duplicates included) — 2 lanes.
        `eps` widens to the band |value - v| <= ε (float columns)."""
        bounds = _stack_cts([ct_value, ct_value])
        l, r = self.search(ks, bounds, np.array([False, True]),
                           self._eps_taus(ks, eps))
        return self.perm[l:r]

    def mask_range(self, ks: KeySet, ct_lo: Ciphertext, ct_hi: Ciphertext,
                   n_padded: int, *, eps: Optional[float] = None) -> np.ndarray:
        """search_range as a [n_padded] bool row mask (executor plumbing)."""
        return rows_to_mask(self.search_range(ks, ct_lo, ct_hi, eps=eps),
                            n_padded)

    def mask_eq(self, ks: KeySet, ct_value: Ciphertext, n_padded: int, *,
                eps: Optional[float] = None) -> np.ndarray:
        """point_lookup as a [n_padded] bool row mask (executor plumbing)."""
        return rows_to_mask(self.point_lookup(ks, ct_value, eps=eps),
                            n_padded)

    def __repr__(self) -> str:
        return (f"SortedIndex({self.column!r}, rows={self.n_rows}, "
                f"build_compares={self.build_compares})")
