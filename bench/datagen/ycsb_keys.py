"""YCSB's key space as one encrypted, comparable integer column.

YCSB numbers records 0, 1, 2, ... in insert order and, with
`insertorder=hashed`, stores each under a hash of its number.  Here the
hash is a bijection of the integers mod 2**key_bits (an odd multiplier
after a salt drawn from the seed), so every key is distinct and lies in
the compare path's headroom.  Record i of the initial load holds key
`key_of(i)`; the j-th insert of a run takes `key_of(recordcount + j)`.
"""
import numpy as np

_MULT = 0x9E3779B97F4A7C15


def key_of(keynums, salt: int, key_bits: int) -> np.ndarray:
    """Keys of the record numbers `keynums` (distinct for distinct
    numbers below 2**key_bits)."""
    mod = 1 << key_bits
    k = (np.asarray(keynums, dtype=object) + salt) * (_MULT % mod) % mod
    return np.asarray(k, dtype=np.int64)


def make(spec: dict, seed: int) -> dict:
    """The `recordcount` keys of the initial load, and the keys of the
    next records in insert order."""
    salt = int(np.random.default_rng(seed).integers(1 << 62))
    bits = int(spec["key_bits"])
    n = int(spec["recordcount"])
    return {"values": key_of(np.arange(n), salt, bits),
            "domain": (0, 1 << bits),
            "next_values": lambda j0, count: key_of(
                np.arange(n + j0, n + j0 + count), salt, bits)}
