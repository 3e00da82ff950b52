"""The comparison that decides ``correct``, at a size a test can hold: the
tiny cell served in bf16 passes it, the fp8 control fails it, and so does
the served path broken underneath at each place a served token is made.
The chip readings at the cells' own sizes are in PERF.md."""

import jax.numpy as jnp
import pytest

from bench.tests.conftest import run_tiny


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    from bench.tests.conftest import make_tiny_root
    return make_tiny_root(tmp_path_factory.mktemp("tiny"))


def test_program_correct(root):
    res = run_tiny(root, seed=11)
    assert res["correct"], res["checks"]
    assert res["compared"]["compared_tokens"] >= 24


def test_control_not_correct(root):
    """The fp8 control in the program's place: the harness's own decision
    comes out false on the gap of the tokens the control puts first."""
    res = run_tiny(root, seed=11, control=True)
    assert not res["correct"]
    gap = res["checks"]["max_logit_gap"]
    assert gap["value"] == res["compared"]["control_max_logit_gap"]
    assert gap["value"] > gap["limit"]
    assert res["compared"]["max_logit_gap"] <= gap["limit"]


def _shift(tokens, vocab):
    return jnp.where(tokens >= 0, (tokens + 1) % vocab, tokens)


def test_decoded_token_altered(root, monkeypatch):
    """A decode tick that emits the next token id instead of its argmax."""
    import repro.serve.engine as eng
    make = eng.make_decode_tick

    def broken(model, eos_id):
        tick = make(model, eos_id)

        def run(*a):
            out = list(tick(*a))
            vocab = model.cfg.vocab_size
            out[0] = _shift(out[0], vocab)       # the token fed back
            out[5] = _shift(out[5], vocab)       # the tokens emitted
            return tuple(out)
        return run

    monkeypatch.setattr(eng, "make_decode_tick", broken)
    res = run_tiny(root, seed=12)
    assert not res["correct"]
    assert res["checks"]["max_logit_gap"]["value"] > \
        res["checks"]["max_logit_gap"]["limit"]


def test_first_token_altered(root, monkeypatch):
    """A prefill whose finished logits favour the next token id."""
    from repro.serve.prefill import ChunkedPrefill
    run = ChunkedPrefill.run

    def broken(self, *a, **kw):
        logits, cache, st = run(self, *a, **kw)
        if logits is not None and not st.preempted:
            logits = jnp.roll(logits, 1, axis=-1)
        return logits, cache, st

    monkeypatch.setattr(ChunkedPrefill, "run", broken)
    res = run_tiny(root, seed=13)
    assert not res["correct"]
    assert res["checks"]["max_logit_gap"]["value"] > \
        res["checks"]["max_logit_gap"]["limit"]
