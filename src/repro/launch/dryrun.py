import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

# NOTE: the two lines above MUST run before any jax import — jax locks the
# device count at first init.  Everything below is ordinary.

import argparse      # noqa: E402
import dataclasses   # noqa: E402
import json          # noqa: E402
import time          # noqa: E402
import traceback     # noqa: E402
from typing import Any, Dict, Optional, Tuple  # noqa: E402

import jax                                     # noqa: E402
import jax.numpy as jnp                        # noqa: E402
from jax.sharding import NamedSharding, PartitionSpec as P  # noqa: E402

from repro import configs                      # noqa: E402
from repro.launch import roofline as RL        # noqa: E402
from repro.launch.mesh import make_production_mesh  # noqa: E402
from repro.launch.specs import (               # noqa: E402
    SHAPES, cell_supported, input_specs)
from repro.models import serve as SV           # noqa: E402
from repro.models import transformer as T      # noqa: E402
from repro.models.config import ModelConfig    # noqa: E402
from repro.parallel import sharding as SH      # noqa: E402
from repro.train import train_lib as TL        # noqa: E402
from repro.train.optimizer import AdamState    # noqa: E402

"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

For each cell this prints/records:
  * memory_analysis()   — proves the partitioned program fits HBM
  * cost_analysis()     — per-device FLOPs / bytes for §Roofline
  * collective bytes    — parsed from the post-SPMD HLO text

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch smollm-360m \
      --shape train_4k [--multi-pod] [--all] [--out artifacts/dryrun]
"""

PyTree = Any


# sanitize-move toggle: conservative (drop-to-replicated) by default —
# measured better (minicpm3 train: 13.3s -> 0.7s collectives, the moved
# embed layout forced per-step activation regathers); run_cell retries
# WITH moves if the conservative layout fails to compile.
_ALLOW_MOVE = {"v": False}


def _state_spec_tree(state_specs: TL.TrainState) -> TL.TrainState:
    p_spec = SH.param_specs(state_specs.params)
    return TL.TrainState(
        params=p_spec,
        opt=AdamState(step=P(), mu=p_spec, nu=p_spec),
        compressor=None)


def build_cell(cfg: ModelConfig, shape: str, mesh,
               tcfg: Optional[TL.TrainConfig] = None):
    """-> (step_fn, args_specs, in_shardings, out_shardings).

    Chooses the cell's layout first (§Perf iteration A2): small models
    replicate params and spread the batch over ALL axes (pure DP) —
    callers must trace/lower while this layout is set.
    """
    from repro.parallel.constrain import set_batch_axes
    meta = SHAPES[shape]
    axes, replicate = SH.choose_layout(mesh, cfg.param_count(),
                                       meta["global_batch"])
    set_batch_axes(axes if replicate else None)
    param_specs_fn = (SH.replicated_param_specs if replicate
                      else SH.param_specs)

    spec = input_specs(cfg, shape, tcfg)
    kind = spec["kind"]
    args = spec["args"]
    named = lambda sp, shapes: SH.to_shardings(
        mesh, SH.sanitize_specs(mesh, sp, shapes,
                                allow_move=_ALLOW_MOVE["v"]))

    if kind == "train":
        tcfg = tcfg or TL.TrainConfig()
        step = TL.make_train_step(cfg, tcfg)
        state_specs, batch_specs = args
        p_spec = param_specs_fn(state_specs.params)
        st_spec = TL.TrainState(
            params=p_spec, opt=AdamState(step=P(), mu=p_spec, nu=p_spec),
            compressor=None)
        st_sh = named(st_spec, state_specs)
        in_sh = (st_sh, named(SH.data_specs(mesh, batch_specs), batch_specs))
        out_sh = (st_sh,
                  SH.to_shardings(mesh, {"loss": P(), "lr": P(),
                                         "grad_norm": P()}))
        fn = step
    elif kind == "prefill":
        params_specs, batch_specs = args
        b = SH.batch_axes(mesh)
        gb, seq = batch_specs["tokens"].shape
        cache_shapes = jax.eval_shape(lambda: SV.init_cache(cfg, gb, seq))
        logits_shape = jax.ShapeDtypeStruct((gb, cfg.vocab_size), cfg.dtype)
        in_sh = (named(param_specs_fn(params_specs), params_specs),
                 named(SH.data_specs(mesh, batch_specs), batch_specs))
        out_sh = (named(P(b, "model"), logits_shape),
                  named(SH.cache_specs(mesh, cache_shapes), cache_shapes))
        fn = lambda params, batch: SV.prefill(cfg, params, batch)
    else:  # decode
        params_specs, cache_specs_, token_spec = args
        b = SH.batch_axes(mesh)
        gb = token_spec.shape[0]
        logits_shape = jax.ShapeDtypeStruct((gb, cfg.vocab_size), cfg.dtype)
        cache_sh = named(SH.cache_specs(mesh, cache_specs_), cache_specs_)
        in_sh = (named(param_specs_fn(params_specs), params_specs),
                 cache_sh,
                 named(P(b), token_spec))
        out_sh = (named(P(b, "model"), logits_shape), cache_sh)
        fn = lambda params, cache, token: SV.decode_step(
            cfg, params, cache, token)
    return fn, args, in_sh, out_sh


def _depth_variant(cfg: ModelConfig, groups: int) -> ModelConfig:
    """Depth-scaled UNROLLED variant for exact cost measurement.

    XLA's cost_analysis counts while-loop bodies ONCE (verified 8x
    undercount on a scan), so the scanned full-depth compile cannot give
    roofline FLOPs.  Costs are linear in depth: measure unrolled G=1 and
    G=2, extrapolate  total(G) = f1 + (G-1) * (f2 - f1).
    """
    repl = dict(num_layers=groups * cfg.group_size, scan_layers=False)
    if cfg.is_encoder_decoder:
        repl["encoder_layers"] = groups
    return dataclasses.replace(cfg, **repl)


def _compile_cell(cfg: ModelConfig, shape: str, mesh, tcfg=None):
    fn, args, in_sh, out_sh = build_cell(cfg, shape, mesh, tcfg)
    lowered = jax.jit(fn, in_shardings=in_sh,
                      out_shardings=out_sh).lower(*args)
    return lowered.compile()


def _extrapolated_cost(cfg: ModelConfig, shape: str, mesh) -> Dict:
    """(flops, bytes, per-op collective bytes) at full depth, per device."""
    G = cfg.num_groups
    c1 = _compile_cell(_depth_variant(cfg, 1), shape, mesh)
    f1 = c1.cost_analysis()
    h1 = RL.collective_bytes(c1.as_text())
    if G == 1:
        f2, h2 = f1, h1
    else:
        c2 = _compile_cell(_depth_variant(cfg, 2), shape, mesh)
        f2 = c2.cost_analysis()
        h2 = RL.collective_bytes(c2.as_text())

    def lin(a, b):
        return a + (G - 1) * (b - a)

    flops = lin(float(f1.get("flops", 0.0)), float(f2.get("flops", 0.0)))
    byts = lin(float(f1.get("bytes accessed", 0.0)),
               float(f2.get("bytes accessed", 0.0)))
    ops = set(h1) | set(h2)
    coll = {op: int(lin(h1.get(op, 0), h2.get(op, 0))) for op in ops}
    return {"flops": flops, "bytes accessed": byts, "collectives": coll}


def _slstm_correction(cfg: ModelConfig, shape: str, mesh) -> float:
    """sLSTM's hidden-to-hidden recurrence is a genuine while loop over S
    (cannot unroll 32k steps); add its per-step matmul FLOPs analytically."""
    n_slstm = sum(k == "slstm" for k in cfg.pattern) * cfg.num_groups
    if not n_slstm:
        return 0.0
    meta = SHAPES[shape]
    S = meta["seq_len"] if meta["kind"] != "decode" else 1
    if S <= 1:
        return 0.0
    gb = meta["global_batch"]
    chips_batch = mesh.shape.get("data", 1) * mesh.shape.get("pod", 1)
    b_dev = max(1, gb // chips_batch)
    w = cfg.d_model
    hd = w // cfg.num_heads
    per_step = b_dev * cfg.num_heads * hd * 4 * hd * 2
    mult = 3.0 if meta["kind"] == "train" else 1.0
    return n_slstm * (S - 1) * per_step * mult


def _auto_microbatches(cfg: ModelConfig, shape: str, mesh) -> int:
    """Grad-accumulation factor so the scan residual (x carried per group,
    bf16) stays under ~2 GiB/device in the memory-fit compile.  Respects
    the cell's chosen layout (small models spread batch over model too,
    so their per-device batch is already tiny)."""
    import math
    meta = SHAPES[shape]
    if meta["kind"] != "train":
        return 1
    axes, _ = SH.choose_layout(mesh, cfg.param_count(),
                               meta["global_batch"])
    chips_batch = math.prod(mesh.shape[a] for a in axes)
    b_dev = max(1, meta["global_batch"] // chips_batch)
    carry = cfg.num_groups * b_dev * meta["seq_len"] * cfg.d_model * 2
    budget = 2 * 2**30
    mb = 1
    while carry / mb > budget and mb < b_dev:
        mb *= 2
    return min(mb, b_dev)


# ---------------------------------------------------------------------------
# the paper's own workload as a dry-run cell: batched HADES comparisons
# sharded over the mesh (DESIGN.md §2.1 — the compare plane scales on the
# batch axis; each ciphertext's ring stays chip-local).
# ---------------------------------------------------------------------------

HADES_SHAPES = {"cmp_64k": 65536, "cmp_256k": 262144, "cmp_1m": 1048576,
                # §Perf iteration C: int32 at-rest ciphertexts (residues are
                # < 2^31; widen to int64 in-register) — halves HBM traffic
                "cmp_256k_c32": 262144}


def run_hades_cell(shape: str, multi_pod: bool) -> Dict:
    import jax.numpy as jnp
    from repro.core import compare as HC
    from repro.core import ring as HR
    from repro.core.encrypt import Ciphertext
    from repro.core.keys import KeySet
    from repro.core.params import make_params

    mesh_name = "2x16x16" if multi_pod else "16x16"
    mesh = make_production_mesh(multi_pod=multi_pod)
    chips = mesh.devices.size
    params = make_params("paper-bfv", mode="gadget")
    ring = HR.make_ring(params)
    K, n = params.num_towers, params.n
    E = K * params.gadget_digits_per_tower
    B = HADES_SHAPES[shape]
    compact = shape.endswith("_c32")
    b_axes = SH.batch_axes(mesh)

    def fn(cek_rev, a0, a1, b0, b1):
        if compact:
            a0, a1, b0, b1 = (t.astype(jnp.int64) for t in (a0, a1, b0, b1))
        ks = KeySet(params=params, ring=ring, sk=None, pk0=None, pk1=None,
                    cek=None, cek_gadget=None, cek_gadget_ntt=None,
                    cek_rev=cek_rev)
        return HC.compare(ks, Ciphertext(a0, a1), Ciphertext(b0, b1))

    ct_dt = jnp.int32 if compact else jnp.int64
    ct_sds = jax.ShapeDtypeStruct((B, K, n), ct_dt)
    cek_sds = jax.ShapeDtypeStruct((E, K, n), jnp.int64)
    ct_sh = NamedSharding(mesh, P(b_axes, None, None))
    rep = NamedSharding(mesh, P())
    t0 = time.time()
    with mesh:
        lowered = jax.jit(
            fn, in_shardings=(rep, ct_sh, ct_sh, ct_sh, ct_sh),
            out_shardings=NamedSharding(mesh, P(b_axes))).lower(
                cek_sds, ct_sds, ct_sds, ct_sds, ct_sds)
        compiled = lowered.compile()
        mem = compiled.memory_analysis()
        cost = compiled.cost_analysis()
        coll = RL.collective_bytes(compiled.as_text())
    # "useful" op count: the coefficient-0 inner product, E*K*n MACs
    # (2 int-ops each) per comparison, per device.
    b_dev = B / (mesh.shape.get("pod", 1) * mesh.shape["data"])
    useful = b_dev * K * E * n * 2
    flops = float(cost.get("flops", 0.0)) + float(
        cost.get("transcendentals", 0.0))
    # fused-kernel HBM floor: 4 ct components in + CEK + residues out
    # (the Pallas cmp_eval kernel keeps the whole pipeline VMEM-resident)
    ct_bytes = 4 if compact else 8
    floor = (b_dev * 4 * K * n * ct_bytes + E * K * n * 8 + b_dev * K * 8)
    terms = {
        "compute_s": flops / 197e12,
        "memory_s": floor / chips_hbm(),
        "memory_upper_s": float(cost.get("bytes accessed", 0.0))
        / chips_hbm(),
        "collective_s": sum(coll.values()) / 50e9,
    }
    dominant = max(
        {k: terms[k] for k in ("compute_s", "memory_s", "collective_s")},
        key=lambda k: terms[k]).replace("_s", "")
    return {
        "arch": "hades-cmp", "shape": shape, "mesh": mesh_name,
        "status": "ok", "chips": chips, "microbatches": 1,
        "cost_compile_s": 0.0,
        "memfit_compile_s": round(time.time() - t0, 2),
        "memory": {
            "argument_bytes": mem.argument_size_in_bytes,
            "output_bytes": mem.output_size_in_bytes,
            "temp_bytes": mem.temp_size_in_bytes,
            "alias_bytes": mem.alias_size_in_bytes,
            "peak_per_device_gib": round(
                (mem.argument_size_in_bytes + mem.temp_size_in_bytes)
                / 2**30, 3),
        },
        "cost": {"flops": flops,
                 "bytes_accessed": float(cost.get("bytes accessed", 0.0))},
        "collectives": coll,
        "roofline": {
            **{k: terms[k] for k in terms},
            "dominant": dominant,
            "model_flops_per_dev": useful,
            "useful_ratio": round(useful / max(flops, 1.0), 4),
            "roofline_fraction": round(
                (useful / 197e12) / max(terms.values()), 6),
            "step_time_s": max(terms.values()),
        },
    }


def chips_hbm() -> float:
    return 819e9


def run_cell(arch: str, shape: str, multi_pod: bool,
             save_hlo: bool = False) -> Dict:
    if arch == "hades-cmp":
        return run_hades_cell(shape, multi_pod)
    cfg = configs.get_config(arch)
    ok, why = cell_supported(cfg, shape)
    mesh_name = "2x16x16" if multi_pod else "16x16"
    if not ok:
        return {"arch": arch, "shape": shape, "mesh": mesh_name,
                "status": "skip", "reason": why}
    meta = SHAPES[shape]
    mesh = make_production_mesh(multi_pod=multi_pod)
    chips = mesh.devices.size
    t0 = time.time()
    from repro.parallel.constrain import set_batch_axes
    try:
        for attempt in range(2):
            try:
                with mesh:
                    # (1) memory-fit compile: full depth, scanned, auto mb
                    mb = _auto_microbatches(cfg, shape, mesh)
                    tcfg = TL.TrainConfig(microbatches=mb)
                    compiled = _compile_cell(cfg, shape, mesh, tcfg)
                    t_compile = time.time() - t0
                    mem = compiled.memory_analysis()
                    # (2) cost compiles: unrolled G=1/G=2, extrapolated
                    cost = _extrapolated_cost(cfg, shape, mesh)
                    cost["flops"] += _slstm_correction(cfg, shape, mesh)
                    t_lower = time.time() - t0 - t_compile
                break
            except Exception:
                if attempt == 1:
                    raise
                # retry with sanitize-moves enabled (some cells need the
                # vocab->d_model / batch->seq moved layouts to shard)
                _ALLOW_MOVE["v"] = True
    finally:
        set_batch_axes(None)
        _ALLOW_MOVE["v"] = False
    data_shards = mesh.shape.get("pod", 1) * mesh.shape.get("data", 1)
    terms = RL.make_terms(cfg, arch, shape, mesh_name, chips, meta["kind"],
                          meta["seq_len"], meta["global_batch"], cost,
                          hlo_text=None, data_shards=data_shards)
    terms.coll_by_op = cost["collectives"]
    terms.coll_bytes_per_dev = float(sum(cost["collectives"].values()))
    terms.__post_init__()
    rec = {
        "arch": arch, "shape": shape, "mesh": mesh_name, "status": "ok",
        "chips": chips,
        "microbatches": mb,
        "cost_compile_s": round(t_lower, 2),
        "memfit_compile_s": round(t_compile, 2),
        "memory": {
            "argument_bytes": mem.argument_size_in_bytes,
            "output_bytes": mem.output_size_in_bytes,
            "temp_bytes": mem.temp_size_in_bytes,
            "alias_bytes": mem.alias_size_in_bytes,
            "peak_per_device_gib": round(
                (mem.argument_size_in_bytes + mem.temp_size_in_bytes)
                / 2**30, 3),
        },
        "cost": {"flops": float(cost.get("flops", 0.0)),
                 "bytes_accessed": float(cost.get("bytes accessed", 0.0))},
        "collectives": terms.coll_by_op,
        "roofline": {
            "compute_s": terms.compute_s,
            "memory_s": terms.memory_s,
            "memory_upper_s": terms.memory_upper_s,
            "collective_s": terms.collective_s,
            "dominant": terms.dominant,
            "model_flops_per_dev": terms.model_flops_per_dev,
            "useful_ratio": round(terms.useful_ratio, 4),
            "roofline_fraction": round(terms.roofline_fraction, 6),
            "step_time_s": terms.step_time_s,
        },
    }
    if save_hlo:
        rec["hlo_len"] = len(hlo_text)
    return rec


def all_cells():
    for arch in configs.ARCH_IDS:
        for shape in SHAPES:
            yield arch, shape


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None,
                    choices=list(SHAPES) + list(HADES_SHAPES))
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--all", action="store_true",
                    help="run every (arch x shape) cell")
    ap.add_argument("--out", default="artifacts/dryrun")
    args = ap.parse_args()

    os.makedirs(args.out, exist_ok=True)
    cells = (list(all_cells()) if args.all
             else [(args.arch, args.shape)])
    meshes = [False, True] if args.both_meshes else [args.multi_pod]

    failures = 0
    for arch, shape in cells:
        for mp in meshes:
            tag = f"{configs.canon(arch)}_{shape}_{'2x16x16' if mp else '16x16'}"
            try:
                rec = run_cell(arch, shape, mp)
            except Exception as e:  # a failing cell is a bug in the system
                failures += 1
                rec = {"arch": arch, "shape": shape,
                       "mesh": "2x16x16" if mp else "16x16",
                       "status": "error", "error": repr(e),
                       "trace": traceback.format_exc()[-2000:]}
            with open(os.path.join(args.out, tag + ".json"), "w") as f:
                json.dump(rec, f, indent=1)
            if rec["status"] == "ok":
                r = rec["roofline"]
                print(f"[ok]   {tag:55s} mem/dev={rec['memory']['peak_per_device_gib']:7.2f}GiB "
                      f"compute={r['compute_s']:.3e}s memory={r['memory_s']:.3e}s "
                      f"coll={r['collective_s']:.3e}s dom={r['dominant']}")
            elif rec["status"] == "skip":
                print(f"[skip] {tag:55s} {rec['reason'][:60]}")
            else:
                print(f"[FAIL] {tag:55s} {rec['error'][:120]}")
    if failures:
        raise SystemExit(f"{failures} dry-run cells failed")


if __name__ == "__main__":
    main()
