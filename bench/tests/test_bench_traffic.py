"""The one traffic generator and the plain reference, on tiny tables."""
import collections

import numpy as np
import pytest

from harness import deploy, reference, spec, traffic
from tiny import tiny_config, tiny_mix

BENCH = spec.load_benchmark()
CELLS = [w["name"] for w in BENCH["workloads"]]


def _schedule(cell, seed, seconds=10.0):
    wl = spec.workload(BENCH, cell)
    cfg = tiny_config(spec.config(BENCH, wl["config"]))
    mix = tiny_mix(spec.traffic(wl["traffic"]), rate=20.0)
    data = deploy.table_data(cfg, mix["tables"], seed)
    return mix, data[mix["streams"][0]["table"]], traffic.make_schedule(
        mix, data, seed, seconds)


@pytest.mark.parametrize("cell", CELLS)
def test_same_seed_same_schedule(cell):
    _, _, a = _schedule(cell, 2**31 + 5)
    _, _, b = _schedule(cell, 2**31 + 5)
    assert [(r.op, r.values, r.due) for r in a.requests] == \
        [(r.op, r.values, r.due) for r in b.requests]


@pytest.mark.parametrize("cell", CELLS)
def test_seeds_offer_the_same_work(cell):
    """Every seed: the same ops at the same moments, other keys."""
    _, _, a = _schedule(cell, 11)
    _, _, b = _schedule(cell, 2**33 + 7)
    assert [(r.op, r.due) for r in a.requests] == \
        [(r.op, r.due) for r in b.requests]
    assert [r.values for r in a.requests] != [r.values for r in b.requests]


def test_open_loop_arrivals_span_the_window():
    mix, _, s = _schedule("hg38-point", 3, seconds=10.0)
    dues = [r.due for r in s.requests]
    assert len(dues) == round(mix["streams"][0]["rate_per_s"] * 10.0)
    assert dues == sorted(dues) and dues[0] == 0.0 and dues[-1] < 10.0
    assert dues[-1] > 9.0


def test_shares_are_exact():
    mix, _, s = _schedule("ycsb-d-latest", 4, seconds=20.0)
    n = len(s.requests)
    ins = [r for r in s.requests if r.op == "insert"]
    assert len(ins) == round(0.05 * n)
    assert len({r.values[0] for r in ins}) == len(ins)
    assert [(r.of, r.op, r.values) for r in s.readback] == \
        [(r.rid, "eq", r.values) for r in ins]


def test_latest_reads_known_keys_and_favour_recent_ones():
    _, data, s = _schedule("ycsb-d-latest", 5, seconds=60.0)
    known = set(np.asarray(data["values"]).tolist())
    recent = set(np.asarray(data["values"])[-16:].tolist())
    hits_recent = 0
    for r in s.requests:
        if r.op == "insert":
            known.add(r.values[0])
            recent.add(r.values[0])
        else:
            assert r.values[0] in known
            hits_recent += r.values[0] in recent
    reads = sum(r.op == "eq" for r in s.requests)
    assert hits_recent > reads / 4          # Zipf 0.99 over recency


def test_point_mix_present_and_absent():
    _, data, s = _schedule("hg38-point", 6, seconds=40.0)
    vals = set(np.asarray(data["values"]).tolist())
    present = sum(r.values[0] in vals for r in s.requests)
    assert present == round(0.75 * len(s.requests))


def test_scan_pairs_are_ordered_row_values():
    _, data, s = _schedule("hg38-scan", 7)
    vals = set(np.asarray(data["values"]).tolist())
    for r in s.requests:
        lo, hi = r.values
        assert lo < hi and lo in vals and hi in vals


@pytest.mark.parametrize("cell", CELLS)
def test_warm_groups_hold_exactly_their_batch(cell):
    _, _, s = _schedule(cell, 8)
    assert s.warm
    for group in s.warm:
        b = len(group)
        assert b & (b - 1) == 0 and len({r.stream for r in group}) == 1


def test_reference_answers():
    base = np.asarray([5, 3, 5, 9, 1])
    assert reference.answer("range", (3, 5), base, []).tolist() == [0, 1, 2]
    assert reference.answer("eq", (5,), base, [5]).tolist() == [0, 2, 5]
    assert reference.answer("insert", (7,), base, [5]).tolist() == [6]
    assert reference.answer("eq", (5,), base, [5],
                            broken="stale_reads").tolist() == [0, 2]
    assert reference.answer("eq", (3,), base, [],
                            broken="approximate_values").tolist() == []
    assert reference.answer("eq", (2,), base, [],
                            broken="approximate_values").tolist() == [1]


def _rec(rid, op, values, rows, status="OK", of=None):
    return {"rid": rid, "op": op, "values": values, "table": "t",
            "status": status, "of": of,
            "row_ids": None if rows is None else np.asarray(rows)}


def test_compare_counts_what_is_wrong():
    base = {"t": np.asarray([4, 8, 4])}
    good = [_rec(0, "eq", (4,), [0, 2]), _rec(1, "insert", (6,), [3]),
            _rec(2, "range", (5, 9), [1, 3]),
            _rec(3, "eq", (6,), [3], of=1)]
    assert reference.compare(good, base) == {
        "wrong_answers": 0, "missing_answers": 0, "missing_writes": 0}
    bad = [_rec(0, "eq", (4,), [0]), _rec(1, "insert", (6,), [3]),
           _rec(2, "range", (5, 9), None, status="FAILED"),
           _rec(3, "eq", (6,), [], of=1)]
    assert reference.compare(bad, base) == {
        "wrong_answers": 2, "missing_answers": 1, "missing_writes": 1}
    refused = [_rec(0, "insert", (6,), None, status="REJECTED"),
               _rec(1, "eq", (6,), [], of=0)]
    assert reference.compare(refused, base)["missing_writes"] == 0
