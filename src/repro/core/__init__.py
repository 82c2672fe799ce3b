"""HADES core: RLWE-based homomorphic symbol comparison (the paper's contribution).

Everything in this package operates on int64 coefficient arrays in RNS
(residue-number-system) representation; 64-bit mode is required for exact
modular arithmetic.

Importing it also fixes where compiled programs persist, once for every
entry point (CLIs, benchmarks, chip_smoke.py all import this package):
`JAX_COMPILATION_CACHE_DIR` when set (JAX reads it itself), otherwise
`.jax_cache/` at the root of the checkout — a fixed, git-ignored path,
so a later run of the same code finds its programs again.
"""
import os
import pathlib

import jax

# Exact mod-q arithmetic needs 64-bit integers. Model code pins its own
# dtypes explicitly, so enabling x64 here is safe for the whole package.
jax.config.update("jax_enable_x64", True)

if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
    jax.config.update(
        "jax_compilation_cache_dir",
        str(pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"))

# NOTE: functions named like their submodule (encrypt.encrypt,
# compare.compare) are deliberately NOT re-exported — rebinding them here
# would shadow the submodules for `import repro.core.encrypt` users.
from repro.core.params import HadesParams, Profile, make_params  # noqa: E402,F401
from repro.core.keys import KeySet, keygen  # noqa: E402,F401
from repro.core.encrypt import (  # noqa: E402,F401
    Ciphertext,
    encrypt_fae,
    decrypt,
    decrypt_raw,
)
from repro.core.compare import (  # noqa: E402,F401
    compare_many,
    compare_fae,
    range_query,
    encrypted_sort,
    encrypted_topk,
)
