"""The training cell's tiny rehearsal on the CPU, the tests that break the
timed path underneath and see ``correct`` come out false, and the float8
control at a size a test run can hold.

A rehearsal walks the harness with its own tiny sizes: its result says
``platform: cpu`` and carries no metric."""

import numpy as np
import pytest

from perfbench import train_cell, weights
from perfbench_helpers import compared, rehearse


def check_rehearsal_line(result):
    assert set(result) >= {"correct", "attempted", "failed", "metrics",
                           "device"}
    assert result["device"]["platform"] == "cpu"
    assert result["metrics"] == {} and "breakdown" not in result
    assert "busy_s" not in result["device"]


def test_train_rehearsal_is_correct_and_reports_nothing(capsys):
    rc, result, lines = rehearse(capsys, "big-train-s256", seed=2**31 + 7,
                                 trace=1)
    assert rc == 0 and result["correct"] is True
    check_rehearsal_line(result)
    assert result["attempted"] > 0 and result["failed"] == 0
    c = compared(lines)
    assert set(c) >= {"token_loss_rms_gap", "loss_gap_max",
                      "grad_norm_gap_worst_leaf",
                      "delta_norm_gap_worst_leaf", "nonfinite_losses",
                      "compiles_in_window"}
    assert all("limit" in v and "value" in v for v in c.values())
    info = next(ln["info"] for ln in lines if "info" in ln)
    assert len(info["first_losses"]) == len(info["reference_losses"]) == 3


class _UnchangedState(train_cell.TrainStep):
    """A step that returns its state unchanged."""

    def dispatch(self, index):
        out = super().dispatch(index)
        for name, value in weights.make(self.params, self.seed).items():
            self.scope.set_var(name, value)
        for name, value in self._state("moment1").items():
            found = [v for v in self.scope.vars
                     if v.startswith(f"{name}_moment1")]
            self.scope.set_var(found[0], np.zeros(value.shape, np.float32))
        return out


class _HalfTheBatch(train_cell.TrainStep):
    """A step that leaves a part of the batch out of its loss."""

    def __init__(self, cfg, mix, batches, seed):
        cut = [dict(b, lbl_weight=b["lbl_weight"] * (
            np.arange(b["lbl_weight"].shape[0])[:, None] % 2))
            for b in batches]
        super().__init__(cfg, mix, cut, seed)


@pytest.mark.parametrize("broken, fails", [
    (_UnchangedState, "delta_norm_gap_worst_leaf"),
    (_HalfTheBatch, "grad_norm_gap_worst_leaf")])
def test_a_broken_train_step_comes_out_not_correct(capsys, broken, fails):
    def patch(ctx):
        ctx.make_step = broken

    rc, result, lines = rehearse(capsys, "big-train-s256", patch=patch)
    assert rc == 0 and result["correct"] is False
    assert compared(lines)[fails]["ok"] is False


def test_train_control_in_float8_fails_a_limit(capsys):
    """The reference in the program's place, in the precision below the
    configuration's bfloat16."""
    rc, _, lines = rehearse(capsys, "big-train-s256", seed=11,
                            control="float8")
    assert rc == 0
    info = next(ln["info"] for ln in lines if "info" in ln)
    limits = {k: v["limit"] for k, v in compared(lines).items()}
    control = info["control"]
    assert control["precision"] == "float8"
    assert control["token_loss_rms_gap"] > 2 * limits["token_loss_rms_gap"]
    assert control["grad_norm_gap_worst_leaf"] > \
        1.5 * limits["grad_norm_gap_worst_leaf"]


def test_a_mesh_in_the_traffic_file_spreads_the_step_over_devices():
    """``mesh_axes`` is a parameter of a ``train`` mix: the same TrainStep
    under dp=4 (virtual CPU devices here) keeps its state on four devices
    and takes the steps one device takes."""
    from perfbench import cells, traffic

    tiny = cells.REHEARSAL["train"]
    cfg = dict(tiny["cfg"], dropout=0.0, amp_dtype="bfloat16",
               learning_rate=1e-3, param_prefix="t")
    mix = dict(tiny["mix"], kind="train", check_steps=2)
    batches = traffic.train_batches(mix, cfg["src_vocab_size"], 7)
    one = train_cell.TrainStep(cfg, mix, batches, 7)
    four = train_cell.TrainStep(cfg, dict(mix, mesh_axes={"dp": 4}),
                                batches, 7)
    a = [one.step(i) for i in range(2)]
    b = [four.step(i) for i in range(2)]
    assert one.state_devices() == 1 and four.state_devices() == 4
    assert np.allclose(a, b, rtol=2e-2)
