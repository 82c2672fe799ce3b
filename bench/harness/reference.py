"""The plain reference: the same predicates on the plaintext, in numpy.

Semantics copied from `repro.db.smoke` (`_expect` and the row-id
comparisons of `run_smoke`): a read answers the ids of the rows that
satisfy it over each table's loaded rows and every write admitted
before it; a write's answer is the ids it wrote.  Each op's own
arithmetic is in `bench/ops/<op>.py`; answers compare as exact row-id
sets.

`answer` also computes the control: the reference with one of the
configuration's guarantees broken, `bench/controls/<broken>.py`, which
the comparison must refuse.
"""
from __future__ import annotations

from typing import Dict, List, Optional, Sequence

import numpy as np

from harness import spec
from harness.traffic import op_module

# statuses a record can end in (the serving loop's own names)
OK, FAILED, REJECTED, PENDING = "OK", "FAILED", "REJECTED", "PENDING"


def answer(op: str, values: Sequence[int], base: np.ndarray,
           written: Sequence[int], *, broken: Optional[str] = None
           ) -> np.ndarray:
    """Row ids (ascending) that `op` answers over base ∪ `written`.  With
    `broken`, reads see that control's view of the table instead."""
    mod = op_module(op)
    rows = np.concatenate([np.asarray(base, np.int64),
                           np.asarray(written, np.int64)])
    if broken is not None and not mod.WRITES:
        rows = spec.load_module("controls", broken).view(base, written)
    return mod.answer(values, rows)


def compare(records: List[dict], bases: Dict[str, np.ndarray]
            ) -> Dict[str, int]:
    """Count what the run got wrong, over `records` in submission order
    (each: op, values, table, status, row_ids, rid, of), against the
    tables' loaded rows `bases`.

    wrong_answers    answers that differ from the reference
    missing_answers  requests that never came back, or failed
    missing_writes   acknowledged writes that the later read of their
                     key does not return
    """
    written: Dict[str, List[int]] = {t: [] for t in bases}
    wrong = missing = missing_writes = 0
    acked: Dict[int, np.ndarray] = {}     # write rid -> ids it wrote
    for rec in records:
        status = rec["status"]
        if status == REJECTED:
            continue                      # refused: `failed`, never applied
        mod = op_module(rec["op"])
        table = rec["table"]
        want = answer(rec["op"], rec["values"], bases[table],
                      written[table])
        if mod.WRITES:
            mod.apply(rec["values"], written[table])
        of = rec.get("of")
        if status != OK:
            missing += 1
            if of is not None and of in acked:
                missing_writes += 1
            continue
        got = np.sort(np.asarray(rec["row_ids"], np.int64))
        if got.shape != want.shape or not np.array_equal(got, want):
            wrong += 1
        if mod.WRITES:
            acked[rec["rid"]] = want
        elif of is not None and of in acked:
            if not set(acked[of].tolist()) <= set(got.tolist()):
                missing_writes += 1
    return {"wrong_answers": wrong, "missing_answers": missing,
            "missing_writes": missing_writes}
