"""CPU tests that the comparison deciding `correct` can fail.

A whole run of the olmo-1b training cell is driven at a small size (no
chip: the harness's look for one is skipped), with the cell's own limits:
sound, it is correct; with the timed path broken underneath (the step
returns its state unchanged; half of the batch is left out and the mean
taken over the rest) it is not. The control, the float32 reference put in
the program's place but computed with float8 matrix products, fails the
limits too.
"""
import copy

import jax
import jax.numpy as jnp
import pytest

import harness
import oracle

CELL = "olmo-1b.train.s2048b4"
SEED = 2 ** 31 + 4242


def small_cell():
    cell = copy.deepcopy(harness.load_cell(CELL))
    cell["config"]["model"].update(num_layers=4, d_model=128, num_heads=4,
                                   num_kv_heads=4, head_dim=32, d_ff=256,
                                   vocab_size=512)
    cell["traffic"].update(seq_len=64, global_batch=4)
    return cell


def state_unchanged(step):
    def broken(state, batch):
        kept = jax.tree.map(jnp.copy, state)
        _, metrics = step(state, batch)
        return kept, metrics
    return broken


def half_batch(step):
    def broken(state, batch):
        half = {k: jnp.concatenate([v[: v.shape[0] // 2]] * 2)
                for k, v in batch.items()}
        return step(state, half)
    return broken


@pytest.mark.parametrize("fault,correct", [(None, True),
                                           (state_unchanged, False),
                                           (half_batch, False)],
                         ids=["sound", "state_unchanged", "half_batch"])
def test_a_run_is_correct_only_when_the_step_is_sound(fault, correct):
    result, code = harness.run(small_cell(), SEED, 0.5, False,
                               require_tpu=False, compile_cache=False,
                               step_wrapper=fault, log=lambda _: None)
    assert code == 0
    assert result["correct"] is correct, result["checks"]
    assert list(result)[-1] == "checks"


def test_the_float8_control_fails_the_limits():
    cell = small_cell()
    prog = harness.reference_readings(cell, SEED, variant="fp8")
    ref = harness.reference_readings(cell, SEED)
    numbers = oracle.compare(prog, ref)
    limits = cell["checks"]["limits"]
    assert any(numbers[k][0] > limits[k] for k in oracle.NUMBERS), numbers
