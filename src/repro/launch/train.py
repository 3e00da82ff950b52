"""Sharded training launcher.

    PYTHONPATH=src python -m repro.launch.train --arch llama3-8b \
        --steps 100 --global-batch 16 --seq-len 512 [--smoke]

The full published config by default; ``--smoke`` swaps in the reduced
same-family config.  On 256 or 512 devices the launcher builds the
production mesh, on 4 or more a 2x2 host mesh; the state is made sharded
on that mesh and the fault-tolerant Trainer loop runs.
"""

import argparse
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh

from repro.configs.base import ModelConfig
from repro.configs.registry import ARCH_IDS, get_config, get_smoke_config
from repro.data.pipeline import DataConfig
from repro.dist.sharding import batch_shardings, mesh_context
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_host_mesh, make_production_mesh
from repro.models.model import Model
from repro.optim.adamw import AdamWConfig
from repro.train.loop import LoopConfig, Trainer
from repro.train.step import microbatch_plan


def make_trainer(cfg: ModelConfig, mesh: Optional[Mesh], *, steps: int,
                 global_batch: int, seq_len: int, ckpt_dir: str,
                 lr: float = 3e-4) -> Trainer:
    """The Trainer for ``cfg`` on ``mesh`` (None: one device), with the
    synthetic data pipeline and the batch sharded over the data axes.  Run
    it inside ``mesh_context(mesh)``."""
    model = Model(cfg, max_decoder_positions=seq_len + 8)
    opt_cfg = AdamWConfig(lr_peak=lr, warmup_steps=max(5, steps // 20),
                          decay_steps=steps, moment_dtype=cfg.moment_dtype)
    data_cfg = DataConfig(vocab_size=cfg.vocab_size, seq_len=seq_len,
                          global_batch=global_batch, seed=0)
    dp = mesh.shape.get("data", 1) * mesh.shape.get("pod", 1) if mesh else 1
    loop_cfg = LoopConfig(
        total_steps=steps, ckpt_every=max(10, steps // 4), ckpt_dir=ckpt_dir,
        log_every=max(1, steps // 10),
        num_microbatches=microbatch_plan(global_batch, dp,
                                         tokens_per_seq=seq_len),
        num_replicas=dp)
    bsh = None
    if mesh is not None:
        rows = jax.ShapeDtypeStruct((global_batch, seq_len), jnp.int32)
        bsh = batch_shardings(mesh, {"tokens": rows, "labels": rows})
    return Trainer(model, opt_cfg, data_cfg, loop_cfg, batch_shardings=bsh)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="minitron-4b", choices=ARCH_IDS)
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--global-batch", type=int, default=8)
    ap.add_argument("--seq-len", type=int, default=256)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--smoke", action="store_true",
                    help="the reduced same-family config")
    ap.add_argument("--multipod", action="store_true")
    args = ap.parse_args()

    enable_compile_cache()
    n_dev = jax.device_count()
    cfg = get_smoke_config(args.arch) if args.smoke else get_config(args.arch)
    print(f"[launch.train] {cfg.name} ({cfg.param_count()/1e6:.1f}M params) "
          f"on {n_dev} device(s)")

    mesh = None
    if n_dev >= 256:
        mesh = make_production_mesh(multi_pod=args.multipod)
    elif n_dev >= 4:
        mesh = make_host_mesh(2, 2)
    trainer = make_trainer(cfg, mesh, steps=args.steps,
                           global_batch=args.global_batch,
                           seq_len=args.seq_len, lr=args.lr,
                           ckpt_dir=args.ckpt_dir or f"checkpoints/{cfg.name}")
    trainer.install_signal_handlers()
    if mesh is not None:
        with mesh_context(mesh):
            trainer.run()
    else:
        trainer.run()


if __name__ == "__main__":
    main()
