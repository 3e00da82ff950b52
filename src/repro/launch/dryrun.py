"""Multi-pod dry-run: lower + compile every (architecture × shape × mesh).

For each cell this lowers the real step function (train_step / prefill_step /
serve_step) against ShapeDtypeStruct inputs on the production mesh, compiles
it, and records:

  * memory_analysis()      — per-device bytes: proves the cell fits,
  * cost_analysis()        — XLA's own counters (kept for reference),
  * custom HLO analysis    — trip-count-aware FLOPs / traffic / collective
                             bytes per chip (launch/hlo_analysis.py),

writing one JSON per cell into --out (incremental: finished cells are skipped
on rerun with --skip-existing).

Usage:
  python -m repro.launch.dryrun --arch llama3-8b --shape train_4k
  python -m repro.launch.dryrun --all --skip-existing --out results/dryrun
  python -m repro.launch.dryrun --all --multipod
"""

import argparse
import gc
import json
import os
import time
import traceback
from pathlib import Path
from typing import Optional

import jax
import jax.numpy as jnp

from repro.configs.base import SHAPES, shape_applicable
from repro.configs.registry import ARCH_IDS, get_config
from repro.configs.specs import cross_len, decoder_len, input_specs
from repro.dist.sharding import (batch_shardings, cache_shardings,
                                 mesh_context, params_shardings)
from repro.launch.hlo_analysis import analyze_hlo
from repro.launch.mesh import make_production_mesh
from repro.models.model import Model
from repro.optim.adamw import AdamWConfig
from repro.train.step import (abstract_train_state, make_prefill_step,
                              make_serve_step, make_train_step,
                              microbatch_plan, train_state_shardings)


def memory_report(compiled) -> dict:
    ma = compiled.memory_analysis()
    out = {}
    for attr in ("argument_size_in_bytes", "output_size_in_bytes",
                 "temp_size_in_bytes", "alias_size_in_bytes",
                 "generated_code_size_in_bytes"):
        out[attr] = int(getattr(ma, attr, 0) or 0)
    out["peak_bytes_per_device"] = (out["argument_size_in_bytes"]
                                    + out["output_size_in_bytes"]
                                    + out["temp_size_in_bytes"]
                                    - out["alias_size_in_bytes"])
    return out


def lower_cell(arch: str, shape_name: str, multi_pod: bool,
               moe_strategy: str = "einsum",
               mb_tokens: Optional[int] = None):
    """Build + lower + compile one cell; returns (record, compiled)."""
    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    runnable, reason = shape_applicable(cfg, shape)
    if not runnable:
        return {"arch": arch, "shape": shape_name,
                "mesh": "2x16x16" if multi_pod else "16x16",
                "status": "skipped", "reason": reason}, None

    mesh = make_production_mesh(multi_pod=multi_pod)
    n_chips = mesh.size
    dec_positions = shape.seq_len + 8 if cfg.is_encdec else 0
    model = Model(cfg, moe_strategy=moe_strategy,
                  max_decoder_positions=dec_positions)
    specs = input_specs(cfg, shape, model)
    t0 = time.time()

    with mesh_context(mesh) as ctx:
        if shape.kind == "train":
            opt_cfg = AdamWConfig(moment_dtype=cfg.moment_dtype)
            if mb_tokens is None:
                # 398B: half the default activation budget (hillclimb B)
                mb_tokens = 4096 if cfg.name.startswith("jamba") else 8192
            n_mb = microbatch_plan(shape.global_batch, ctx.dp,
                                   tokens_per_seq=decoder_len(cfg, shape),
                                   target_tokens_per_replica=mb_tokens)
            step = make_train_step(model, opt_cfg, num_microbatches=n_mb,
                                   accum_dtype=cfg.moment_dtype)
            astate = abstract_train_state(model, opt_cfg)
            sshard = train_state_shardings(cfg, model, opt_cfg, mesh)
            bshard = batch_shardings(mesh, specs)
            lowered = jax.jit(step, in_shardings=(sshard, bshard),
                              donate_argnums=0).lower(astate, specs)
            extra = {"num_microbatches": n_mb}
        elif shape.kind == "prefill":
            aparams = model.abstract_params()
            pshard = params_shardings(cfg, aparams, mesh)
            bshard = batch_shardings(mesh, specs)
            stepf = make_prefill_step(model)
            lowered = jax.jit(stepf, in_shardings=(pshard, bshard)).lower(
                aparams, specs)
            extra = {}
        else:  # decode
            aparams = model.abstract_params()
            pshard = params_shardings(cfg, aparams, mesh)
            cshard = cache_shardings(cfg, mesh, specs["cache"],
                                     shape.global_batch)
            tshard = batch_shardings(
                mesh, {"t": specs["tokens"]})["t"]
            stepf = make_serve_step(model)
            lowered = jax.jit(
                stepf, in_shardings=(pshard, tshard, cshard, tshard),
                donate_argnums=2,
            ).lower(aparams, specs["tokens"], specs["cache"],
                    specs["lengths"])
            extra = {}

        t_lower = time.time() - t0
        compiled = lowered.compile()
        t_compile = time.time() - t0 - t_lower

    hlo = compiled.as_text()
    custom = analyze_hlo(hlo)
    ca = compiled.cost_analysis() or {}
    # persist compressed HLO so the analyzer can be iterated w/o recompiles
    try:
        import zstandard as zstd
        hdir = Path("results/hlo")
        hdir.mkdir(parents=True, exist_ok=True)
        tag = (f"{arch}__{shape_name}__"
               f"{'mp' if multi_pod else 'sp'}.hlo.zst")
        (hdir / tag).write_bytes(
            zstd.ZstdCompressor(level=6).compress(hlo.encode()))
    except Exception:
        pass
    record = {
        "arch": arch, "shape": shape_name,
        "mesh": "2x16x16" if multi_pod else "16x16",
        "chips": n_chips,
        "status": "ok",
        "kind": shape.kind,
        "seq_len": shape.seq_len,
        "global_batch": shape.global_batch,
        "params": cfg.param_count(),
        "params_active": cfg.param_count(active_only=True),
        "lower_s": round(t_lower, 1),
        "compile_s": round(t_compile, 1),
        "memory": memory_report(compiled),
        "xla_cost": {k: float(ca[k]) for k in ("flops", "bytes accessed")
                     if k in ca},
        "hlo": custom,
        "hlo_chars": len(hlo),
        **extra,
    }
    return record, compiled


def main() -> None:
    # 512 host devices stand in for the production mesh; set before the
    # first JAX call initializes the CPU backend
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--multipod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--out", default="results/dryrun")
    ap.add_argument("--skip-existing", action="store_true")
    ap.add_argument("--moe-strategy", default="einsum")
    args = ap.parse_args()

    out = Path(args.out)
    out.mkdir(parents=True, exist_ok=True)

    archs = ARCH_IDS if (args.all or not args.arch) else [args.arch]
    shapes = list(SHAPES) if (args.all or not args.shape) else [args.shape]
    meshes = [False, True] if (args.both_meshes or args.all) else \
        [args.multipod]

    failures = []
    for multi_pod in meshes:
        for arch in archs:
            for shape_name in shapes:
                tag = f"{arch}__{shape_name}__{'mp' if multi_pod else 'sp'}"
                path = out / f"{tag}.json"
                if args.skip_existing and path.exists():
                    print(f"[dryrun] {tag}: exists, skipping")
                    continue
                print(f"[dryrun] {tag}: lowering...", flush=True)
                t0 = time.time()
                try:
                    record, compiled = lower_cell(
                        arch, shape_name, multi_pod,
                        moe_strategy=args.moe_strategy)
                    if compiled is not None:
                        ma = record["memory"]
                        print(f"[dryrun] {tag}: OK "
                              f"({time.time()-t0:.0f}s, "
                              f"{ma['peak_bytes_per_device']/2**30:.2f} "
                              f"GiB/dev)", flush=True)
                        del compiled
                    else:
                        print(f"[dryrun] {tag}: SKIP ({record['reason']})",
                              flush=True)
                except Exception as e:  # noqa
                    record = {"arch": arch, "shape": shape_name,
                              "mesh": "2x16x16" if multi_pod else "16x16",
                              "status": "error", "error": str(e)[:2000],
                              "traceback": traceback.format_exc()[-4000:]}
                    failures.append(tag)
                    print(f"[dryrun] {tag}: ERROR {str(e)[:200]}", flush=True)
                path.write_text(json.dumps(record, indent=1))
                gc.collect()

    print(f"[dryrun] done; {len(failures)} failures: {failures}")
    if failures:
        raise SystemExit(1)


if __name__ == "__main__":
    main()
