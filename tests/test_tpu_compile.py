"""Compiles for a described TPU v5e — the chip's own compiler, no chip.

The model-path Pallas kernels at the widths of the models that use them,
compiled (not interpreted): a refusal here (an unsupported cast, a
zero-size or misaligned vector, too much VMEM) is what a chip run would
hit first.  The full-width minitron-4b decode tick of ``ContinuousEngine``
must fit one chip's 16 GB and write its K/V cache in place.  Nothing runs:
these tests say nothing about results or times.

The topology is described inside a module fixture, never at import: only
one process at a time may load the TPU library, and pytest-xdist workers
import every test file.
"""

import functools
import re

import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

from repro.configs.registry import get_config
from repro.kernels.radix_sort import moe_dispatch_sort
from repro.kernels.ssm_scan import mamba_assoc_scan, mlstm_carry_scan
from repro.models.model import Model
from repro.serve.early_exit import make_decode_tick

GiB = 1 << 30
DECODE_BATCH, DECODE_SEQ = 8, 1024     # chip_smoke.py's serving cache


@pytest.fixture(scope="module")
def one_chip():
    import os
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "no TPU lib"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a persistent cache entry compiled for a described chip cannot be read
    # back without one: keep these compiles out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


def _on(sharding, *shapes, dtype=jnp.float32):
    return [jax.ShapeDtypeStruct(s, dtype, sharding=sharding) for s in shapes]


def _compile(fn, *args):
    return jax.jit(fn).lower(*args).compile()


def _kernel_args(name, dev):
    if name == "moe_dispatch_sort":        # deepseek-v2-lite
        T, K, D, E = 2048, 6, 2048, 64
        fn = functools.partial(moe_dispatch_sort, num_experts=E,
                               interpret=False, jit=False)
        return fn, (*_on(dev, (T, D), dtype=jnp.bfloat16),
                    *_on(dev, (T, K), dtype=jnp.int32), *_on(dev, (T, K)))
    if name == "mamba_assoc_scan":         # jamba-1.5
        B, c, Di, N = 1, 256, 16384, 16
        fn = functools.partial(mamba_assoc_scan, interpret=False)
        return fn, _on(dev, (B, c, Di, N), (B, c, Di, N), (B, Di, N))
    nc, B, H, dh = 8, 1, 4, 1024           # xlstm-1.3b mLSTM carry

    def fn(la, mS, C, n, m0, C0, n0):
        return mlstm_carry_scan(la, mS, C, n, (m0, C0, n0), interpret=False)
    return fn, _on(dev, (nc, B, H), (nc, B, H), (nc, B, H, dh, dh),
                   (nc, B, H, dh), (B, H), (B, H, dh, dh), (B, H, dh))


@pytest.mark.parametrize("name", ["moe_dispatch_sort", "mamba_assoc_scan",
                                  "mlstm_carry_scan"])
def test_kernel_compiles_for_v5e(one_chip, name):
    fn, args = _kernel_args(name, one_chip)
    compiled = _compile(fn, *args)
    assert "tpu_custom_call" in compiled.as_text()


def _compile_minitron_tick(one_chip, batch, max_seq):
    model = Model(get_config("minitron-4b"))
    tick = make_decode_tick(model, eos_id=2)

    def place(tree):
        return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=one_chip), tree)

    params = place(model.abstract_params())
    cache = place(model.abstract_cache(batch, max_seq))
    lanes = _on(one_chip, (batch,), dtype=jnp.int32)[0]
    done = _on(one_chip, (batch,), dtype=jnp.bool_)[0]
    return jax.jit(
        lambda p, t, c, l, f, r: tick(p, t, c, l, f, r, 8),
        donate_argnums=2).lower(params, lanes, cache, lanes, done,
                                lanes).compile()


def test_minitron_decode_tick_fits_v5e(one_chip):
    compiled = _compile_minitron_tick(one_chip, DECODE_BATCH, DECODE_SEQ)
    ma = compiled.memory_analysis()
    need = ma.argument_size_in_bytes + ma.temp_size_in_bytes
    assert need < 15 * GiB, f"decode tick needs {need / GiB:.2f} GiB"


# ops that only name or pass on a buffer, and write nothing
_PLUMBING = {"parameter", "get-tuple-element", "tuple", "while", "bitcast"}


def _whole_buffer_writes(hlo, shape):
    """(name, opcode, op_name) of every op outside a fusion's body whose
    output holds an array of ``shape``."""
    fused = set(re.findall(r"fusion\(.*?calls=(%?[\w.\-]+)", hlo))
    comp, found = None, []
    for line in hlo.splitlines():
        head = re.match(r"(?:ENTRY )?(%?[\w.\-]+) ", line)
        if head and line.rstrip().endswith("{"):
            comp = head.group(1)
            continue
        op = re.match(r"\s*(?:ROOT )?(%?[\w.\-]+) = (.*?) ([\w\-]+)\(",
                      line)
        if comp in fused or not op or op.group(3) in _PLUMBING:
            continue
        if shape in op.group(2):
            meta = re.search(r'op_name="([^"]*)"', line)
            found.append((op.group(1), op.group(3),
                          meta.group(1) if meta else ""))
    return found


def test_minitron_decode_tick_writes_kv_in_place_v5e(one_chip):
    """At the chat cell's cache (8 lanes x 2048), no op of the tick writes
    a whole stacked K or V cache but the two in-place scatters of the new
    rows: no copy, no buffer allocated for the layer scan's output, no
    rewrite of every position."""
    batch, max_seq = 8, 2048
    hlo = _compile_minitron_tick(one_chip, batch, max_seq).as_text()
    found = _whole_buffer_writes(hlo, f"bf16[32,{batch},{max_seq},8,128]")
    others = [f for f in found
              if f[1] != "fusion" or "attn.kv_write/scatter" not in f[2]]
    assert not others, others
    assert len(found) == 2, found
