"""Skips the harness's look for a chip and drives the rest of a run
with the timed path broken underneath: ``correct`` must come out
false, once for each fault a one-chip training cell can have.  (The
exchange between chips left out belongs to the four-chip cell, which
this benchmark does not have yet: PERF.md, Open questions.)"""

import pytest

import bench_smoke
from bench_smoke import program_config_put_back  # noqa: F401
from benchmark import harness


def state_unchanged(trainer):
    step = trainer._train_step

    def broken(state, batch):
        _, metrics = step(state, batch)
        return state, metrics

    trainer._train_step = broken


def half_batch_left_out(trainer):
    step = trainer._train_step

    def broken(state, batch):
        n = batch["images"].shape[0] // 2
        new_state, metrics = step(
            state, {k: v[:n] for k, v in batch.items()})
        return new_state, metrics        # the mean is over the rest

    trainer._train_step = broken


@pytest.mark.parametrize("fault,fails", [
    (state_unchanged, {"first_grad_worst_leaf", "delta3_worst_leaf"}),
    (half_batch_left_out, {"loss_step1", "first_grad_worst_leaf",
                           "rpn_box_loss_step1"}),
], ids=["state_unchanged", "half_batch"])
def test_a_broken_step_reads_not_correct(fault, fails):
    import jax

    cell = bench_smoke.smoke_cell(mask=True)
    out = harness.run_cell(cell, seed=31, seconds=0.5, trace=False,
                           t_start=0.0, devices=jax.devices()[:1],
                           peaks=bench_smoke.CPU_PEAK, on_trainer=fault)
    assert out["correct"] is False
    over = {k for k, r in out["compared"].items() if r["value"] > r["limit"]}
    assert fails <= over, out["compared"]
    if fault is state_unchanged:
        # a state left unchanged reads 1 by the worst-leaf measure
        assert out["compared"]["delta3_worst_leaf"]["value"] == 1.0
        assert out["compared"]["first_grad_worst_leaf"]["value"] == 1.0
