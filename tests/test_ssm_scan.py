"""Chunked SSM scans (kernels/ssm_scan.py + the models/ssm.py switch).

The contract under test: for any monoid, ``tree_scan``/``batched_scan``
equal ``jax.lax.associative_scan`` seeded with ``carry0`` — in ONE launch —
and flipping ``scan_impl="lax" → "pallas"`` on a model changes launch
structure, never tokens.  With real ``hypothesis`` the properties run as
``@given`` tests; under the conftest stub they degrade to a seeded sweep
(the tests/test_tile_scan.py pattern), so tier-1 keeps the coverage.
"""

import dataclasses
import random

import hypothesis
import pytest

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.launch_trace import trace_launches
from repro.kernels.ssm_scan import (AFFINE_UNITS, LOGSPACE_UNITS,
                                    affine_combine, logspace_affine_combine,
                                    mamba_assoc_scan, mamba_assoc_scan_ref,
                                    mamba_seq_scan_ref, mlstm_carry_scan,
                                    mlstm_carry_scan_ref)
from repro.kernels.tile_scan import batched_scan, tree_scan

HAVE_HYPOTHESIS = hasattr(hypothesis, "__version__")

EOS = 2


def _fp32(cfg):
    return dataclasses.replace(cfg, param_dtype="float32",
                               compute_dtype="float32")


def _affine_inputs(seed, B, L, Di, N, dtype=jnp.float32):
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(seed), 3)
    dA = jnp.exp(-jax.nn.softplus(
        jax.random.normal(k1, (B, L, Di, N)))).astype(dtype)
    dBx = (0.1 * jax.random.normal(k2, (B, L, Di, N))).astype(dtype)
    h0 = jax.random.normal(k3, (B, Di, N)).astype(dtype)
    return dA, dBx, h0


# ---------------------------------------------------------------------------
# check bodies (shared between the hypothesis and the seeded paths)
# ---------------------------------------------------------------------------

def check_mamba_equiv(seed, L, block, dtype=jnp.float32, atol=1e-5):
    dA, dBx, h0 = _affine_inputs(seed, 2, L, 4, 4, dtype)
    got = mamba_assoc_scan(dA, dBx, h0, block=block, fblock=64)
    want = mamba_assoc_scan_ref(dA.astype(jnp.float32),
                                dBx.astype(jnp.float32),
                                h0.astype(jnp.float32))
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want), atol=atol, rtol=atol)
    seq = mamba_seq_scan_ref(dA.astype(jnp.float32),
                             dBx.astype(jnp.float32),
                             h0.astype(jnp.float32))
    np.testing.assert_allclose(np.asarray(want), np.asarray(seq),
                               atol=atol, rtol=atol)


def check_logspace_equiv(la, mS, seed=0, block=4):
    """Exclusive mlstm carry scan vs the sequential-fold oracle.  ``la``
    and ``mS`` come from the caller (the adversarial axis — gate log-sums
    of arbitrary magnitude); C/n are well-scaled randoms."""
    la = jnp.asarray(la, jnp.float32).reshape(-1, 1, 1)
    mS = jnp.asarray(mS, jnp.float32).reshape(-1, 1, 1)
    nc, B, H, dh = la.shape[0], 1, 1, 4
    k1, k2, k3, k4 = jax.random.split(jax.random.PRNGKey(seed), 4)
    Chat = jax.random.normal(k1, (nc, B, H, dh, dh))
    nhat = jax.random.normal(k2, (nc, B, H, dh))
    carry0 = (jax.random.normal(k3, (B, H)),
              jax.random.normal(k4, (B, H, dh, dh)),
              jnp.zeros((B, H, dh)))
    got = mlstm_carry_scan(la, mS, Chat, nhat, carry0, block=block)
    want = mlstm_carry_scan_ref(la, mS, Chat, nhat, carry0)
    for g, w in zip(got, want):
        g, w = np.asarray(g), np.asarray(w)
        assert np.all(np.isfinite(g)), "stabilized scan went non-finite"
        # m entries are log-scale and can be huge; compare with rtol on
        # the magnitude so ±1e30-ish log-zeros still match exactly.
        np.testing.assert_allclose(g, w, rtol=1e-5, atol=1e-5)


# ---------------------------------------------------------------------------
# kernel-level: equivalence, padding, carries, dtypes, launch count
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("L", [1, 2, 16, 63, 64, 65, 300, 1024])
def test_mamba_matches_assoc_scan(L):
    # block=16 forces cross-chunk carries from L=17 up; non-pow2 lengths
    # exercise the identity-padding path.
    check_mamba_equiv(L, L, block=16)


@pytest.mark.parametrize("dtype,atol", [(jnp.float32, 1e-5),
                                        (jnp.bfloat16, 5e-2)])
def test_mamba_dtypes(dtype, atol):
    check_mamba_equiv(7, 130, block=32, dtype=dtype, atol=atol)


def test_int_sum_monoid():
    """batched_scan is monoid-generic: int32 cumsum as a 1-leaf tree."""
    rng = np.random.default_rng(0)
    vals = rng.integers(-50, 50, (2, 257, 3)).astype(np.int32)
    (out,) = batched_scan((jnp.asarray(vals),),
                          combine=lambda a, b: (a[0] + b[0],),
                          units=(0,), block=32, kind="ssm_scan")
    np.testing.assert_array_equal(np.asarray(out),
                                  np.cumsum(vals, axis=1, dtype=np.int32))


def test_exclusive_and_carry0():
    rng = np.random.default_rng(1)
    vals = jnp.asarray(rng.integers(0, 9, (1, 77, 2)).astype(np.int32))
    c0 = jnp.asarray([[100, 200]], jnp.int32)
    (out,) = batched_scan((vals,), combine=lambda a, b: (a[0] + b[0],),
                          units=(0,), carry0=(c0,), inclusive=False,
                          block=16, kind="ssm_scan")
    ref = np.cumsum(np.asarray(vals), axis=1) - np.asarray(vals) \
        + np.asarray(c0)[:, None]
    np.testing.assert_array_equal(np.asarray(out), ref)


@pytest.mark.parametrize("L", [1, 5, 64, 257, 1000])
def test_single_launch_any_length(L):
    dA, dBx, h0 = _affine_inputs(L, 1, L, 2, 2)
    with trace_launches() as tr:
        batched_scan((dA, dBx), combine=affine_combine, units=AFFINE_UNITS,
                     carry0=(jnp.ones_like(h0), h0), kind="ssm_scan",
                     block=64)
    assert [r.kind for r in tr] == ["ssm_scan"]


def test_tree_scan_single_launch():
    la = jnp.zeros((3, 20, 1, 1))
    with trace_launches() as tr:
        tree_scan((la, la - 5.0, jnp.ones((3, 20, 16, 2)),
                   jnp.ones((3, 20, 1, 2))),
                  combine=logspace_affine_combine, units=LOGSPACE_UNITS,
                  inclusive=False, block=8, rblock=8, kind="ssm_scan")
    assert [r.kind for r in tr] == ["ssm_scan"]


def test_logspace_monoid_extreme_magnitudes():
    """Gate log-sums at ±1e3 (raw exp would overflow at ~88): the max-
    rebased combine must stay finite and still match the fold oracle."""
    check_logspace_equiv([1e3, -1e3, 500.0, 0.0, -700.0, 300.0, 88.0],
                         [-1e3, 1e3, -500.0, 700.0, 0.0, -88.0, 2.0])


# ---------------------------------------------------------------------------
# model-level: scan_impl="pallas" == "lax" per layer
# ---------------------------------------------------------------------------

def _smoke(arch):
    from repro.configs.registry import get_smoke_config
    from repro.models.model import Model
    cfg = _fp32(get_smoke_config(arch))
    model = Model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    return model, params


def _layer_params(model, params, kind):
    for spec, lp in zip(model.period_specs, params["stage"]):
        if spec.kind == kind:
            return jax.tree.map(lambda x: x[0], lp)["mixer"]
    raise AssertionError(f"no {kind} layer in smoke config")


def test_mamba_forward_scan_impl_equiv():
    from repro.models.ssm import mamba_forward
    model, params = _smoke("jamba-1.5-large-398b")
    lp = _layer_params(model, params, "mamba")
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 48, model.cfg.d_model))
    y_lax, st_lax = mamba_forward(lp, model.cfg, x, scan_impl="lax")
    with trace_launches() as tr:
        y_pal, st_pal = mamba_forward(lp, model.cfg, x, scan_impl="pallas")
    assert sum(1 for r in tr if r.kind == "ssm_scan") >= 1
    np.testing.assert_allclose(np.asarray(y_lax), np.asarray(y_pal),
                               atol=1e-5, rtol=1e-5)
    np.testing.assert_allclose(np.asarray(st_lax["ssm"]),
                               np.asarray(st_pal["ssm"]),
                               atol=1e-5, rtol=1e-5)


def test_mlstm_forward_scan_impl_equiv():
    from repro.models.ssm import mlstm_forward
    model, params = _smoke("xlstm-1.3b")
    lp = _layer_params(model, params, "mlstm")
    # S = 4 chunks of 16 → the chunked carry-scan path on both impls
    x = jax.random.normal(jax.random.PRNGKey(2), (2, 64, model.cfg.d_model))
    y_lax, st_lax = mlstm_forward(lp, model.cfg, x, scan_impl="lax")
    with trace_launches() as tr:
        y_pal, st_pal = mlstm_forward(lp, model.cfg, x, scan_impl="pallas")
    assert sum(1 for r in tr if r.kind == "ssm_scan") == 1
    np.testing.assert_allclose(np.asarray(y_lax), np.asarray(y_pal),
                               atol=1e-4, rtol=1e-4)
    for k in st_lax:
        np.testing.assert_allclose(
            np.asarray(st_lax[k]).astype(np.float32),
            np.asarray(st_pal[k]).astype(np.float32),
            atol=1e-4, rtol=1e-4, err_msg=k)


def test_scan_impl_validated():
    from repro.models.model import Model
    from repro.configs.registry import get_smoke_config
    with pytest.raises(ValueError):
        Model(_fp32(get_smoke_config("xlstm-1.3b")), scan_impl="nope")


# ---------------------------------------------------------------------------
# serving: SSM state slots + entropy-gated early exit
# ---------------------------------------------------------------------------

def _serve(model, params, prompts, exit_entropy, scan_impl=None):
    from repro.serve.engine import ContinuousEngine, EngineConfig, Request
    eng = ContinuousEngine(model, params, EngineConfig(
        max_batch=2, max_seq=96, eos_id=EOS, decode_tick=4, page_size=16,
        exit_entropy=exit_entropy))
    for i, p in enumerate(prompts):
        eng.submit(Request(rid=i, prompt=p, max_new=12))
    done = []
    while eng.pending:
        done += eng.step()
    return {r.rid: np.asarray(r.result) for r in done}, eng


def test_ssm_decode_serving():
    """One xlstm smoke model served three ways: pallas ungated (reference),
    lax ungated (tokens must match exactly — scan_impl never changes
    tokens), and pallas gated (exact prefix, fewer steps, gate fired)."""
    from repro.configs.registry import get_smoke_config
    from repro.models.model import Model
    from repro.serve.engine import Request

    cfg = _fp32(get_smoke_config("xlstm-1.3b"))
    pal = Model(cfg, scan_impl="pallas")
    params = pal.init(jax.random.PRNGKey(0))
    lax_m = Model(cfg, scan_impl="lax")

    rng = np.random.RandomState(0)
    prompts = [rng.randint(3, cfg.vocab_size,
                           size=rng.randint(5, 30)).astype(np.int32)
               for _ in range(4)]

    # recurrent-only model → O(1) state slots, independent of prompt length
    assert pal.recurrent_only
    from repro.serve.engine import ContinuousEngine, EngineConfig
    eng = ContinuousEngine(pal, params, EngineConfig(
        max_batch=2, max_seq=96, eos_id=EOS, page_size=16))
    for p in prompts:
        assert eng._slot_span(Request(rid=0, prompt=p, max_new=12)) == 16

    base, eng0 = _serve(pal, params, prompts, None)
    lax_res, _ = _serve(lax_m, params, prompts, None)
    assert set(base) == set(lax_res)
    for k in base:
        np.testing.assert_array_equal(base[k], lax_res[k])

    gated, eng1 = _serve(pal, params, prompts, 8.0)
    assert eng1.telemetry.early_exits > 0
    assert eng1.telemetry.decode_steps < eng0.telemetry.decode_steps
    for k in base:
        np.testing.assert_array_equal(gated[k], base[k][:len(gated[k])])


def test_attention_model_not_recurrent_only():
    from repro.configs.registry import get_smoke_config
    from repro.models.model import Model
    assert not Model(_fp32(get_smoke_config("jamba-1.5-large-398b"))
                     ).recurrent_only


def test_gated_tick_matches_ungated_until_gate():
    """The gated tick's per-step token choice is the ungated argmax —
    gating only stops emission (the exactness property the benchmark
    pins), checked at the tick level with an impossible-to-fire gate."""
    from repro.configs.registry import get_smoke_config
    from repro.models.model import Model

    cfg = _fp32(get_smoke_config("xlstm-1.3b"))
    model = Model(cfg, scan_impl="pallas")
    params = model.init(jax.random.PRNGKey(0))
    rng = np.random.RandomState(1)
    prompts = [rng.randint(3, cfg.vocab_size, size=9).astype(np.int32)
               for _ in range(2)]
    # tau=0: entropy is never < 0, the gate can never fire — the gated
    # engine must reproduce the ungated run token-for-token.
    base, eng0 = _serve(model, params, prompts, None)
    never, eng1 = _serve(model, params, prompts, 1e-9)
    assert eng1.telemetry.early_exits == 0
    for k in base:
        np.testing.assert_array_equal(base[k], never[k])


# ---------------------------------------------------------------------------
# properties
# ---------------------------------------------------------------------------

if HAVE_HYPOTHESIS:
    from hypothesis import given, settings, strategies as st

    @settings(max_examples=20, deadline=None)
    @given(st.integers(1, 200), st.sampled_from([8, 16, 64]),
           st.integers(0, 10 ** 6))
    def test_affine_scan_property(L, block, seed):
        check_mamba_equiv(seed, L, block)

    @settings(max_examples=20, deadline=None)
    @given(st.lists(st.floats(-1e3, 1e3), min_size=1, max_size=24),
           st.data())
    def test_logspace_scan_property(la, draw):
        mS = draw.draw(st.lists(st.floats(-1e3, 1e3), min_size=len(la),
                                max_size=len(la)))
        check_logspace_equiv(la, mS)
else:
    _RNG = random.Random(0)
    _AFFINE_CASES = [(_RNG.randint(0, 10 ** 6), _RNG.randint(1, 200),
                      _RNG.choice([8, 16, 64])) for _ in range(12)]
    _LOG_CASES = []
    for _ in range(12):
        n = _RNG.randint(1, 24)
        _LOG_CASES.append(([_RNG.uniform(-1e3, 1e3) for _ in range(n)],
                           [_RNG.uniform(-1e3, 1e3) for _ in range(n)]))

    @pytest.mark.parametrize("seed,L,block", _AFFINE_CASES)
    def test_affine_scan_property(seed, L, block):
        check_mamba_equiv(seed, L, block)

    @pytest.mark.parametrize("la,mS", _LOG_CASES)
    def test_logspace_scan_property(la, mS):
        check_logspace_equiv(la, mS)
