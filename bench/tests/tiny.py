"""Tiny copies of the benchmark's configurations and mixes, at the
`test-bfv` profile, for CPU tests of the harness."""
import copy

TINY_ROWS = {"hg38": 300, "hg38_prefix": 64, "usertable": 128}


def tiny_config(cfg: dict) -> dict:
    """`cfg` at test-bfv widths with a few hundred rows."""
    c = copy.deepcopy(cfg)
    c.update(profile="test-bfv", n=256, num_towers=1, modulus_bits=31,
             gadget_log_base=6, plaintext_modulus=257)
    for t in c["tables"]:
        rows = TINY_ROWS[t["name"]]
        if "recordcount" in t:
            t["recordcount"] = rows
            t["key_bits"] = 13
        else:
            t["rows"] = rows
        if "t" in t:
            t["t"] = 257
    return c


def tiny_mix(mix: dict, rate: float = 40.0) -> dict:
    """`mix` with a rate the CPU keeps up with at test-bfv."""
    m = copy.deepcopy(mix)
    for st in m["streams"]:
        if "rate_per_s" in st:
            st["rate_per_s"] = rate
        else:
            st["rounds"] = 3000
    return m
