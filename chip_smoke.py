"""Bring-up smoke test on TPU: the system's main paths at published widths.

    python chip_smoke.py               # one chip: serve phase + kernel phase
    python chip_smoke.py --four-chips  # four chips: Trainer, 2x2 vs 1x4 mesh

One process, no children.  It refuses to run anywhere but a TPU (there is
no CPU fallback) and outside a checkout of this repository.

* Serve phase: minitron-4b at its published widths (32 layers, d_model
  3072, GQA 24/8, d_ff 9216, vocab 256000, bf16; random weights from
  ``--seed``) behind ``ContinuousEngine`` — Kvik ``cap`` admission,
  ``by_blocks`` chunked prefill with a two-block budget so that long prompts
  are preempted and resumed.  Eight seeded requests, prompts of 16..900
  tokens, 32 new tokens each, drained; checks every request's length, that
  every cache page is free again, the engine's counters, and the chunked
  prefill's last-position logits against a one-shot ``Model.prefill``.
* Kernel phase: the model-path Pallas kernels, compiled, at the widths of
  the models that use them, against their oracles — ``moe_dispatch_sort``
  (deepseek-v2-lite: T=2048, K=6, D=2048, E=64) exactly; ``mamba_assoc_scan``
  (jamba: c=256, Di=16384, N=16) and ``mlstm_carry_scan`` (xlstm-1.3b: 8
  chunks, 4 heads, dh=1024) within f32 tolerance.
* ``--four-chips``: xlstm-1.3b at published widths (48 blocks, d 2048) in
  the Trainer, global batch 8 x 512 tokens, 3 steps on a 2x2 (data, model)
  mesh and on a 1x4 mesh; the losses must agree and every device must hold
  its share of the sharded state.  The trainers start from fresh
  directories under ``.chip_smoke/``, so no saved state is restored.

Compile time, wall time, tokens and peak device memory are printed on the
way; the last line of standard output is one JSON object naming the device.
These are bring-up numbers, not benchmark metrics.
"""

from __future__ import annotations

import argparse
import json
import shutil
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent

# the chunked prefill's logits against a one-shot prefill of the same
# prompt, in bf16 through 32 layers: max |Δ| over the vocabulary must stay
# within this fraction of the largest reference logit
PREFILL_LOGITS_RTOL = 5e-2
SCAN_TOL = 1e-5             # f32 scans against their oracles (rtol = atol)
LOSS_RTOL = 5e-3            # 2x2 vs 1x4 mesh losses

SERVE_ARCH, TRAIN_ARCH = "minitron-4b", "xlstm-1.3b"
MOE_WIDTHS = dict(T=2048, K=6, D=2048, E=64)          # deepseek-v2-lite
MAMBA_WIDTHS = dict(B=1, c=256, Di=16384, N=16)       # jamba-1.5
MLSTM_WIDTHS = dict(nc=8, B=1, H=4, dh=1024)          # xlstm-1.3b


def log(msg: str) -> None:
    print(f"[chip_smoke] {msg}", flush=True)


def check(ok: bool, msg: str) -> None:
    if not ok:
        raise AssertionError(msg)


def tpu_device():
    import jax
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        sys.exit(f"chip_smoke: needs a TPU, found platform {dev.platform!r} "
                 f"({dev.device_kind}); there is no CPU fallback")
    return dev


# ---------------------------------------------------------------------------
# serve phase
# ---------------------------------------------------------------------------

def serve_phase(seed: int, clock) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.configs.registry import get_config
    from repro.launch.serve import make_requests, serve

    cfg = get_config(SERVE_ARCH)
    max_batch, max_seq, max_new, eos = 8, 1024, 32, 2
    reqs = make_requests(cfg.vocab_size, 8, max_new=max_new, prompt_len=900,
                         seed=seed)
    c0 = clock.seconds
    run = serve(cfg, reqs, max_batch=max_batch, max_seq=max_seq, seed=seed,
                eos_id=eos, prefill_block_budget=2)
    eng, tel = run.engine, run.engine.telemetry
    tokens = sum(len(r.result) for r in run.served)
    log(f"serve: {cfg.name} ({cfg.param_count() / 1e9:.2f}B params), "
        f"max_batch {max_batch}, max_seq {max_seq}, prompts "
        f"{sorted(len(r.prompt) for r in reqs)}")
    log(f"serve: weights made in {run.init_s:.3f}s; drained in "
        f"{run.wall_s:.3f}s (compiles included), {tokens} tokens served, "
        f"compile {clock.seconds - c0:.3f}s")
    log(f"serve: telemetry {tel.snapshot()}")

    check(sorted(r.rid for r in run.served) == [r.rid for r in reqs],
          "not every request was served exactly once")
    for r in run.served:
        n = len(r.result)
        check(n == r.max_new or (0 < n <= r.max_new and r.result[-1] == eos),
              f"request {r.rid}: {n} tokens, neither max_new={r.max_new} "
              f"nor ended at EOS")
    check(all(s is None for s in eng.slots), "a decode lane is still taken")
    check(len(eng.pages.free) == eng.pages.num_pages,
          f"{eng.pages.num_pages - len(eng.pages.free)} cache pages leaked")
    check(tel.ticks > 0 and tel.prefill_blocks > 0,
          "telemetry counted no decode ticks or no prefill blocks")
    check(tel.prefill_preemptions > 0, "no by_blocks prefill was preempted")

    # the engine's chunked prefill vs one shot over the same prompt
    one_shot = jax.jit(run.model.prefill)
    for r in (reqs[0], reqs[1]):                    # longest, shortest
        L = len(r.prompt)
        toks = np.zeros((1, -(-L // 32) * 32), np.int32)
        toks[0, :L] = r.prompt
        got, _, pst = eng.prefiller.run(
            run.params, jnp.asarray(toks), run.model.init_cache(1, max_seq),
            row_lengths=[L])
        want, _ = one_shot(run.params, {"tokens": jnp.asarray(r.prompt)[None]})
        got = np.asarray(got[0, :cfg.vocab_size], np.float32)
        want = np.asarray(want[0, :cfg.vocab_size], np.float32)
        err, scale = float(np.abs(got - want).max()), float(np.abs(want).max())
        log(f"serve: prompt {L}: chunked prefill ({pst.blocks} blocks) vs "
            f"one-shot logits: max |diff| {err:.6g}, max |logit| "
            f"{scale:.6g}, argmax {int(got.argmax())} vs "
            f"{int(want.argmax())}")
        check(np.isfinite(got).all() and err <= PREFILL_LOGITS_RTOL * scale,
              f"prompt {L}: chunked prefill logits differ by {err} "
              f"(limit {PREFILL_LOGITS_RTOL} x {scale})")


# ---------------------------------------------------------------------------
# kernel phase
# ---------------------------------------------------------------------------

def kernel_phase(seed: int, clock) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.kernels import resolve_interpret
    from repro.kernels.radix_sort import moe_dispatch_sort
    from repro.kernels.ref import stable_argsort_reference
    from repro.kernels.ssm_scan import (mamba_assoc_scan,
                                        mamba_assoc_scan_ref,
                                        mlstm_carry_scan,
                                        mlstm_carry_scan_ref)

    check(resolve_interpret(None) is False,
          "kernels would run in the interpreter on this device")
    ks = jax.random.split(jax.random.PRNGKey(seed), 10)

    def timed(name, fn, *args):
        c0, t0 = clock.seconds, time.perf_counter()
        out = jax.block_until_ready(fn(*args))
        log(f"kernel {name}: first call {time.perf_counter() - t0:.3f}s "
            f"(compile {clock.seconds - c0:.3f}s)")
        return out

    # MoE dispatch at deepseek-v2-lite widths: exact
    T, K, D, E = MOE_WIDTHS.values()
    x = jax.random.normal(ks[0], (T, D), jnp.bfloat16)
    experts = jax.random.randint(ks[1], (T, K), 0, E, jnp.int32)
    probs = jax.random.uniform(ks[2], (T, K), jnp.float32)
    got = timed("moe_dispatch_sort", jax.jit(
        lambda x, e, p: moe_dispatch_sort(x, e, p, num_experts=E)),
        x, experts, probs)
    flat = experts.reshape(-1)
    order = stable_argsort_reference(flat)
    want = (x[order // K], flat[order], order // K, probs.reshape(-1)[order])
    for name, g, w in zip(("rows", "experts", "tokens", "probs"), got, want):
        check(np.array_equal(np.asarray(g), np.asarray(w)),
              f"moe_dispatch_sort {name} differ from the stable argsort")

    # Mamba selective scan at jamba widths
    B, c, Di, N = MAMBA_WIDTHS.values()
    dA = jnp.exp(-jax.nn.softplus(jax.random.normal(ks[3], (B, c, Di, N))))
    dBx = 0.1 * jax.random.normal(ks[4], (B, c, Di, N))
    h0 = jax.random.normal(ks[5], (B, Di, N))
    got = timed("mamba_assoc_scan", mamba_assoc_scan, dA, dBx, h0)
    want = jax.jit(mamba_assoc_scan_ref)(dA, dBx, h0)
    err = float(jnp.abs(got - want).max())
    log(f"kernel mamba_assoc_scan: max |diff| {err:.3g}")
    check(bool(jnp.allclose(got, want, rtol=SCAN_TOL, atol=SCAN_TOL)),
          f"mamba_assoc_scan differs from its oracle by {err}")

    # mLSTM chunk-carry scan at xlstm-1.3b widths
    nc, B, H, dh = MLSTM_WIDTHS.values()
    la = -jax.nn.softplus(jax.random.normal(ks[6], (nc, B, H)))
    mS = jax.random.normal(ks[7], (nc, B, H))
    Chat = jax.random.normal(ks[8], (nc, B, H, dh, dh)) / dh
    nhat = jax.random.normal(ks[9], (nc, B, H, dh))
    carry0 = (jnp.zeros((B, H)), jnp.zeros((B, H, dh, dh)),
              jnp.zeros((B, H, dh)))
    got = timed("mlstm_carry_scan", mlstm_carry_scan, la, mS, Chat, nhat,
                carry0)
    want = jax.jit(mlstm_carry_scan_ref)(la, mS, Chat, nhat, carry0)
    for name, g, w in zip(("la", "m", "C", "n"), got, want):
        err = float(jnp.abs(g - w).max())
        log(f"kernel mlstm_carry_scan {name}: max |diff| {err:.3g}")
        check(bool(jnp.allclose(g, w, rtol=SCAN_TOL, atol=SCAN_TOL)),
              f"mlstm_carry_scan {name} differs from its oracle by {err}")


# ---------------------------------------------------------------------------
# four-chip phase
# ---------------------------------------------------------------------------

def _device_bytes(tree, mesh) -> dict:
    """Bytes of ``tree`` each mesh device holds; every leaf must have one
    shard on each device of the mesh."""
    import jax
    devs = set(mesh.devices.flat)
    held = {d: 0 for d in devs}
    for leaf in jax.tree.leaves(tree):
        shards = leaf.addressable_shards
        check({s.device for s in shards} == devs and len(shards) == len(devs),
              f"a {leaf.shape} leaf is not spread over the mesh: "
              f"{[s.device.id for s in shards]}")
        for s in shards:
            held[s.device] += s.data.nbytes
    return held


def _check_shares(state, mesh, name: str) -> None:
    """Every device holds the same bytes of the state, and 1/model of every
    leaf the rule table shards over 'model'."""
    import jax
    held = _device_bytes(state, mesh)
    total = sum(l.nbytes for l in jax.tree.leaves(state))
    check(max(held.values()) <= 1.01 * min(held.values()),
          f"{name}: devices hold unequal shares {sorted(held.values())}")
    model_leaves = [l for l in jax.tree.leaves(state.params)
                    if "model" in jax.tree.leaves(tuple(l.sharding.spec))]
    m_held = _device_bytes(model_leaves, mesh)
    m_total = sum(l.nbytes for l in model_leaves)
    share = max(m_held.values()) / m_total
    check(abs(share - 1 / mesh.shape["model"]) < 1e-6,
          f"{name}: a device holds {share:.4f} of the model-sharded params, "
          f"not 1/{mesh.shape['model']}")
    log(f"train {name}: each device holds {max(held.values()) / total:.4f} "
        f"of the state ({max(held.values())} of {total} bytes) and "
        f"{share:.4f} of the {len(model_leaves)} model-sharded params")


def four_chip_phase(steps: int, clock) -> list:
    import jax

    from repro.configs.registry import get_config
    from repro.data.pipeline import host_batch_to_device
    from repro.dist.sharding import mesh_context
    from repro.launch.mesh import make_host_mesh
    from repro.launch.train import make_trainer

    check(len(jax.devices()) == 4, f"--four-chips needs 4 devices, found "
          f"{len(jax.devices())}")
    cfg = get_config(TRAIN_ARCH)
    losses = {}
    for name, (data, model) in (("2x2", (2, 2)), ("1x4", (1, 4))):
        mesh = make_host_mesh(data, model)
        ckpt = ROOT / ".chip_smoke" / f"train-{name}"
        shutil.rmtree(ckpt, ignore_errors=True)
        trainer = make_trainer(cfg, mesh, steps=steps, global_batch=8,
                               seq_len=512, ckpt_dir=str(ckpt))
        c0, t0 = clock.seconds, time.perf_counter()
        with mesh_context(mesh):
            state = trainer.init_or_restore()
            check(trainer.start_step == 0, f"{name}: restored saved state")
            _check_shares(state, mesh, name)
            losses[name] = []
            for _ in range(steps):
                batch = host_batch_to_device(trainer.pipeline.next_batch(),
                                             trainer.batch_shardings)
                state, metrics = trainer.step_fn(state, batch)
                losses[name].append(float(metrics["loss"]))
            _check_shares(state, mesh, name)
        log(f"train {name}: {cfg.name} losses {losses[name]} in "
            f"{time.perf_counter() - t0:.3f}s (compile "
            f"{clock.seconds - c0:.3f}s)")
        del state, trainer
        shutil.rmtree(ckpt, ignore_errors=True)
    for a, b in zip(losses["2x2"], losses["1x4"]):
        check(abs(a - b) <= LOSS_RTOL * abs(b),
              f"2x2 vs 1x4 losses disagree: {losses}")
    return losses


# ---------------------------------------------------------------------------

def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="only the four-chip Trainer mesh comparison")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    if not (ROOT / "src" / "repro").is_dir():
        sys.exit(f"chip_smoke: no repro package under {ROOT / 'src'}; run "
                 f"it from a checkout of the repository")
    sys.path.insert(0, str(ROOT / "src"))
    dev = tpu_device()

    import jax
    from repro.launch.compile_cache import CompileClock, enable_compile_cache

    cache_dir = enable_compile_cache()
    clock = CompileClock()
    t0 = time.perf_counter()
    log(f"device {dev.device_kind} x{len(jax.devices())}, jax "
        f"{jax.__version__}, compile cache {cache_dir}")
    if args.four_chips:
        four_chip_phase(3, clock)
    else:
        serve_phase(args.seed, clock)
        stats = dev.memory_stats() or {}
        log(f"serve: peak device memory {stats.get('peak_bytes_in_use')} "
            f"bytes of {stats.get('bytes_limit')}")
        kernel_phase(args.seed, clock)
    log(f"total {time.perf_counter() - t0:.3f}s, compile {clock.seconds:.3f}s "
        f"over {clock.compiles} programs, {clock.cache_hits} persistent-cache "
        f"hits")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}), flush=True)


if __name__ == "__main__":
    main()
