"""The phases of `chip_smoke.py`, run here on the CPU at test-bfv over a
few hundred hg38 rows (the script runs them on a TPU at paper-bfv over
all 34,423), plus the contract around them: wrong answers raise, the
script refuses a host without a TPU, and compiled programs persist where
the compile-cache rule says."""
import os
import pathlib
import subprocess
import sys
import time

import jax
import numpy as np
import pytest

from repro.db import smoke

REPO = pathlib.Path(__file__).resolve().parents[1]


def test_one_device_phases_answer_exactly():
    lines = []
    record = smoke.run_smoke("test-bfv", "gadget", rows=300, index_rows=96,
                             batch=2, log=lines.append)
    assert set(record) == {
        "keygen", "ingest", "index_build", "range_scans_cold",
        "point_lookups_cold", "range_scans_warm", "point_lookups_warm",
        "topk", "insert_then_read"}
    # warm rounds reuse every program the cold rounds compiled
    assert record["range_scans_warm"]["programs"] == 0
    assert record["point_lookups_warm"]["programs"] == 0
    assert any(line.startswith("tables: hg38 300 rows") for line in lines)
    total = smoke.summary(record)
    assert total["programs"] > 0 and total["compile_s"] > 0


def test_sharded_phases_answer_exactly():
    n = len(jax.devices())
    record = smoke.run_sharded_smoke("test-bfv", "gadget", rows=200,
                                     shards=2 * n, devices=n, batch=2,
                                     log=lambda _: None)
    assert set(record) == {"keygen", "ingest", "index_build", "indexed",
                           "range_scans"}


def test_sharded_smoke_refuses_a_mesh_it_cannot_build():
    with pytest.raises(AssertionError, match="distinct"):
        smoke.run_sharded_smoke("test-bfv", "gadget", rows=64, shards=4,
                                devices=len(jax.devices()) + 1,
                                log=lambda _: None)


def test_answer_checks_raise_on_any_difference():
    smoke._expect("same", [1, 2, 3], np.asarray([1, 2, 3]))
    for got in ([1, 2], [1, 3, 2], [1, 2, 4]):
        with pytest.raises(AssertionError, match="differs"):
            smoke._expect("case", got, [1, 2, 3])
    vals = np.asarray([5, 9, 9, 1, 7, 7])
    smoke._check_topk("ties interchangeable", vals, 0, 10, [2, 1, 5])
    for bad in ([4, 1, 2],       # order
                [1, 1, 4],       # a row twice
                [1, 2, 0]):      # not the largest
        with pytest.raises(AssertionError):
            smoke._check_topk("bad", vals, 0, 10, bad)
    with pytest.raises(AssertionError):        # outside the band
        smoke._check_topk("band", vals, 0, 8, [4, 0, 3])


def test_script_refuses_without_a_tpu(tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    run = subprocess.run([sys.executable, str(REPO / "chip_smoke.py")],
                         env=env, capture_output=True, text=True,
                         timeout=300)
    assert run.returncode != 0
    assert '"ok"' not in run.stdout
    # alone in a directory, without the rest of the repository
    (tmp_path / "chip_smoke.py").write_text(
        (REPO / "chip_smoke.py").read_text())
    run = subprocess.run([sys.executable, "chip_smoke.py"], cwd=tmp_path,
                         env=env, capture_output=True, text=True,
                         timeout=300)
    assert run.returncode != 0
    assert '"ok"' not in run.stdout


@pytest.mark.parametrize("env_dir", [None, "given"])
def test_compile_cache_directory_rule(tmp_path, env_dir):
    """JAX_COMPILATION_CACHE_DIR wins when set; otherwise programs land
    in the checkout's git-ignored `.jax_cache/`."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=str(REPO / "src"),
               JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS="0")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    want = REPO / ".jax_cache"
    if env_dir:
        want = tmp_path / env_dir
        env["JAX_COMPILATION_CACHE_DIR"] = str(want)
    before = set(want.glob("*")) if want.exists() else set()
    fresh = time.time_ns() % 1_000_003        # a program not cached yet
    code = ("import repro.core, jax, jax.numpy as jnp; "
            f"jax.jit(lambda x: x * {fresh} + 1)"
            "(jnp.arange(7)).block_until_ready(); "
            "print(jax.config.jax_compilation_cache_dir)")
    run = subprocess.run([sys.executable, "-c", code], env=env, cwd=tmp_path,
                         capture_output=True, text=True, timeout=300)
    assert run.returncode == 0, run.stderr
    assert pathlib.Path(run.stdout.strip()) == want
    assert set(want.glob("*")) - before          # the program landed there
    if env_dir:
        assert list(tmp_path.iterdir()) == [want]  # and nowhere else here
    ignored = (REPO / ".gitignore").read_text().split()
    assert ".jax_cache/" in ignored
