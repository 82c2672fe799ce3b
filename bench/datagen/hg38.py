"""The hg38 column of Hades §6: genomic coordinates, a mixture over the
lengths of the 22 autosomes, reduced mod the BFV plaintext modulus.

A copy of the repository's seeded stand-in (`repro.data.datasets._hg38`
and `load_dataset(..., scheme="bfv")`), seeded here by the run's seed.
"""
import numpy as np

CHROM_LENS = np.array([248956422, 242193529, 198295559, 190214555,
                       181538259, 170805979, 159345973, 145138636,
                       138394717, 133797422, 135086622, 133275309,
                       114364328, 107043718, 101991189, 90338345,
                       83257441, 80373285, 58617616, 64444167,
                       46709983, 50818468], dtype=np.float64)


def make(spec: dict, seed: int) -> dict:
    """`spec["rows"]` values in [0, spec["t"]) from `seed`."""
    rng = np.random.default_rng(seed)
    probs = CHROM_LENS / CHROM_LENS.sum()
    chrom = rng.choice(len(CHROM_LENS), size=int(spec["rows"]), p=probs)
    raw = rng.uniform(0, CHROM_LENS[chrom])
    values = raw.astype(np.int64) % int(spec["t"])
    return {"values": values, "domain": (0, int(spec["t"]))}
