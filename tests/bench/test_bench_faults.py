"""The comparison that decides ``correct`` fails when the timed path is
broken underneath: a CPU run of the whole harness (past its look for a
chip) at small widths with one fault planted in the engine's jitted
steps, once for each fault a served cell can have, and once with the
control (the program's int8 kept-tile path) served; each for both
configurations (``qwen2-vl-2b`` adds q/k/v biases and a tied head). A
cell on one chip has no exchange between chips to leave out.

These runs serve in float32, where a sound run's served tokens are the
reference's best at every position (gap 0), so the limits can sit far
below what any fault or the int8 path reads. In bfloat16 at these
widths the sound runs' own rounding reads as much as the int8 path does
on some seeds; the bfloat16 control is read on the chip at the cell's
own size (``bench/control.py``)."""
import time

import jax.numpy as jnp
import pytest

import tiny
from bench import control, harness

CONFIGS = ("phi3-medium-14b", "qwen2-vl-2b")


def _state_unchanged(eng):
    """The decode step hands back the pool it was given: its KV writes
    are lost."""
    step = eng._decode_sample

    def broken(params, pool, *a):
        tokens, _ = step(params, pool, *a)
        return tokens, pool
    eng._decode_sample = broken


def _half_batch(eng):
    """Only the first half of the slots is computed; the second half
    gets the first half's tokens."""
    step = eng._decode_sample

    def broken(params, pool, *a):
        tokens, pool = step(params, pool, *a)
        h = tokens.shape[0] // 2
        return tokens.at[h:].set(tokens[:tokens.shape[0] - h]), pool
    eng._decode_sample = broken


def _token_altered(eng):
    """Each request's first token is altered where it is sampled."""
    first = eng._first_sample
    vocab = eng.cfg.vocab

    def broken(logits_row, base, temp):
        return (first(logits_row, base, temp) + 1) % vocab
    eng._first_sample = broken


def _run(config, hooks, seed=5):
    spec = tiny.spec(config, "chat", "float32")
    return harness.run_cell(spec, seed, 2.0, False, time.monotonic(),
                            cache=False, hooks=hooks,
                            log=lambda *a, **k: None)


@pytest.mark.parametrize("config", CONFIGS)
@pytest.mark.parametrize("fault", [_state_unchanged, _half_batch,
                                   _token_altered],
                         ids=["state_unchanged", "half_batch",
                              "token_altered"])
def test_fault_makes_the_run_not_correct(fault, config):
    out = _run(config, {"engine": fault})
    assert out["correct"] is False
    gap = out["compared"]["logit_gap_mean"]
    assert gap["value"] > gap["limit"]


@pytest.mark.parametrize("config", CONFIGS)
def test_sound_run_is_correct(config):
    out = _run(config, {})
    assert out["correct"] is True, out["compared"]


@pytest.mark.parametrize("config", CONFIGS)
def test_control_program_int8_path_is_not_correct(config):
    out = _run(config, {"artifact": control.int8_artifact})
    assert out["correct"] is False, out["compared"]
    assert jnp.isfinite(out["compared"]["logit_gap_mean"]["value"])
