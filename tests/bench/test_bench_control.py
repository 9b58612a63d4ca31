"""The check's control and its faults at a tiny size: the reference put in
the program's place at a lower precision, and the timed path broken
underneath the harness, must each come out as not correct."""

import jax.numpy as jnp
import pytest
from conftest import run_tiny, tiny_cell, tiny_shape

from bench import harness

# d 128, vocab 512: the engine's widest gap reads 0 to 0.0124 on these
# seeds, the fp8 control 0.108 to 0.208 (CPU)
SHAPE = tiny_shape(d_model=128, head_dim=32, d_ff=256, vocab=512)
LIMIT = 0.05
SEEDS = (11, 12, 13)


def _cell(check_requests=6):
    return tiny_cell(shape=SHAPE, gap_limit=LIMIT,
                     check_requests=check_requests)


@pytest.mark.parametrize("seed", SEEDS)
def test_program_passes_and_control_fails(seed):
    """The same run's check passes the program and, with the fp8 reference
    in its place, fails the control."""
    res = run_tiny(_cell(), seed=seed)
    assert res["correct"], res["compared"]
    assert res["compared"]["widest_gap"]["value"] <= LIMIT
    ctl = run_tiny(_cell(), seed=seed, control="fp8")
    assert not ctl["correct"]
    assert ctl["compared"]["widest_gap"]["value"] > LIMIT


def _state_unchanged(engine):
    """The decode step returns the cache it was given."""
    from repro.serving.engine import step_programs

    _, _, decode = step_programs(engine.cfg, donate_state=False)
    engine._decode = lambda p, t, s, pos: (decode(p, t, s, pos)[0], s)


def _half_the_batch(engine):
    """The upper half of the slots gets the lower half's logits."""
    decode = engine._decode

    def half(p, t, s, pos):
        logits, state = decode(p, t, s, pos)
        h = logits.shape[0] // 2
        return logits.at[h:2 * h].set(logits[:h]), state

    engine._decode = half


def _token_altered(engine):
    """Every seventh sampling returns the next token id instead."""
    pick, calls = engine._pick, [0]

    def altered(logits):
        calls[0] += 1
        tok = pick(logits)
        return (tok + 1) % logits.shape[-1] if calls[0] % 7 == 0 else tok

    engine._pick = altered


@pytest.mark.parametrize("fault", [_state_unchanged, _half_the_batch,
                                   _token_altered],
                         ids=["state-unchanged", "half-batch",
                              "token-altered"])
def test_fault_is_not_correct(fault):
    res = run_tiny(_cell(check_requests=64), seed=SEEDS[0],
                   engine_hook=fault)
    assert not res["correct"]
    assert res["compared"]["widest_gap"]["value"] > LIMIT


def test_unbroken_hooked_engine_is_correct():
    """The hook itself changes nothing."""
    res = run_tiny(_cell(check_requests=64), seed=SEEDS[0],
                   engine_hook=lambda engine: None)
    assert res["correct"]
    assert jnp.isfinite(res["compared"]["widest_gap"]["value"])
