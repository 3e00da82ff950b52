"""Serving launcher: seeded requests through ``ContinuousEngine``.

    PYTHONPATH=src python -m repro.launch.serve --arch minitron-4b \
        --requests 8 --max-new 32 --max-batch 8 --max-seq 1024 \
        --prompt-len 900 [--smoke]

The full published config by default (random weights from ``--seed``);
``--smoke`` swaps in the reduced same-family config.  Admission is the
Kvik ``cap`` adaptor and prefill is ``by_blocks`` chunked, interleaved with
the decode ticks.  :func:`serve` is the in-process entry point that
``chip_smoke.py`` drives.
"""

from __future__ import annotations

import argparse
import dataclasses
import time
from typing import List, Optional

import jax
import numpy as np

from repro.configs.base import ModelConfig
from repro.configs.registry import ARCH_IDS, get_config, get_smoke_config
from repro.launch.compile_cache import enable_compile_cache
from repro.models.model import Model
from repro.serve.engine import ContinuousEngine, EngineConfig, Request


def make_requests(vocab_size: int, n: int, *, max_new: int, prompt_len: int,
                  seed: int) -> List[Request]:
    """``n`` seeded requests with prompts of 16..``prompt_len`` tokens; the
    first is the longest and the second the shortest, so both ends of the
    range are always served."""
    rng = np.random.default_rng(seed)
    lo = min(16, prompt_len)
    lengths = rng.integers(lo, prompt_len + 1, n)
    lengths[:2] = [prompt_len, lo][:n]
    return [Request(rid=i, prompt=rng.integers(3, vocab_size, int(L),
                                               dtype=np.int32),
                    max_new=max_new)
            for i, L in enumerate(lengths)]


@dataclasses.dataclass
class ServeRun:
    model: Model
    params: object
    engine: ContinuousEngine
    served: List[Request]
    init_s: float          # weights made on the device (compile included)
    wall_s: float          # submit → drained (compiles included)


def serve(cfg: ModelConfig, requests: List[Request], *, max_batch: int,
          max_seq: int, seed: int = 0, eos_id: int = 2,
          prefill_block_budget: Optional[int] = None) -> ServeRun:
    """Make ``cfg``'s weights from ``seed`` on the device, submit every
    request to a fresh ``ContinuousEngine`` and step it until drained."""
    model = Model(cfg)
    t0 = time.perf_counter()
    params = jax.jit(model.init)(jax.random.PRNGKey(seed))
    jax.block_until_ready(params)
    init_s = time.perf_counter() - t0
    engine = ContinuousEngine(model, params, EngineConfig(
        max_batch=max_batch, max_seq=max_seq, eos_id=eos_id,
        prefill_block_budget=prefill_block_budget))
    t0 = time.perf_counter()
    for r in requests:
        engine.submit(r)
    served: List[Request] = []
    while engine.pending:
        served += engine.step()
    return ServeRun(model, params, engine, served, init_s,
                    time.perf_counter() - t0)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="minitron-4b", choices=ARCH_IDS)
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--max-new", type=int, default=32)
    ap.add_argument("--max-batch", type=int, default=8)
    ap.add_argument("--max-seq", type=int, default=1024)
    ap.add_argument("--prompt-len", type=int, default=900,
                    help="longest prompt; the others are 16..this long")
    ap.add_argument("--eos-id", type=int, default=2)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--smoke", action="store_true",
                    help="the reduced same-family config")
    args = ap.parse_args()

    enable_compile_cache()
    cfg = get_smoke_config(args.arch) if args.smoke else get_config(args.arch)
    if cfg.is_encdec or cfg.family == "vlm":
        raise SystemExit(f"{args.arch}: use a text-only arch for this demo "
                         f"(modality stubs need explicit inputs)")
    print(f"[launch.serve] {cfg.name}: {cfg.param_count()/1e6:.1f}M params "
          f"on {jax.devices()[0].device_kind}")
    reqs = make_requests(cfg.vocab_size, args.requests, max_new=args.max_new,
                         prompt_len=args.prompt_len, seed=args.seed)
    run = serve(cfg, reqs, max_batch=args.max_batch, max_seq=args.max_seq,
                seed=args.seed, eos_id=args.eos_id)
    for r in sorted(run.served, key=lambda r: r.rid):
        print(f"[launch.serve] req {r.rid}: prompt {len(r.prompt)}, "
              f"{len(r.result)} tokens, eos={r.stats.all_finished}")
    tokens = sum(len(r.result) for r in run.served)
    print(f"[launch.serve] served {len(run.served)}/{args.requests} "
          f"requests, {tokens} tokens in {run.wall_s:.3f}s; "
          f"telemetry {run.engine.telemetry.snapshot()}")


if __name__ == "__main__":
    main()
