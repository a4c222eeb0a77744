"""The serving cells' tiny rehearsals on the CPU, the test that alters
tokens where they are produced and sees ``correct`` come out false, and
the float8 control at a size a test run can hold."""

import numpy as np
import pytest

from perfbench import manifest, serve_cell, weights
from perfbench.reference import transformer as ref
from perfbench_helpers import compared, rehearse


def check_rehearsal_line(result):
    assert set(result) >= {"correct", "attempted", "failed", "metrics",
                           "device"}
    assert result["device"]["platform"] == "cpu"
    assert result["metrics"] == {} and "breakdown" not in result
    assert "busy_s" not in result["device"]


@pytest.mark.parametrize("cell, trace", [("base-serve-flood", 0),
                                         ("base-serve-steady", 1)])
def test_serve_rehearsal_is_correct_and_reports_nothing(capsys, cell, trace):
    rc, result, lines = rehearse(capsys, cell, seed=2**31 + 9, seconds=1.5,
                                 trace=trace)
    assert rc == 0 and result["correct"] is True
    check_rehearsal_line(result)
    assert result["attempted"] > 0 and result["failed"] == 0
    info = next(ln["info"] for ln in lines if "info" in ln)
    # every request sent was answered in full or cut short at the close
    assert info["requests_failed"] == 0 and info["failures"] == []
    assert info["requests_ok"] + info["requests_cut_at_close"] \
        == info["requests_sent"] and info["requests_ok"] > 0
    assert info["checked_requests"] >= 2 and info["checked_tokens"] > 0
    assert "logit_gap_max" in compared(lines)
    if trace:
        read = {ln["rehearsal_reader"] for ln in lines
                if "rehearsal_reader" in ln}
        due = {m["name"] for m in manifest.metrics_for(
            manifest.load(), cell, "per_layer")}
        assert {"queue_wait_p50_ms", "ttft_tail_p95_ms",
                "step_wall_ms.serve"} <= read <= due


class _AlteredTokens(serve_cell.Served):
    """Every token altered where it is produced."""

    def __init__(self, cfg, seed, work_dir):
        super().__init__(cfg, seed, work_dir)
        real, vocab = self.inst.lane_step, cfg["trg_vocab_size"]
        self.inst.lane_step = lambda: {
            slot: (tok + 1) % vocab for slot, tok in real().items()}


def test_altered_tokens_come_out_not_correct(capsys):
    def patch(ctx):
        ctx.make_served = _AlteredTokens

    rc, result, lines = rehearse(capsys, "base-serve-flood", patch=patch)
    assert rc == 0 and result["correct"] is False
    assert compared(lines)["logit_gap_max"]["ok"] is False
    assert result["failed"] == 0        # every answer came, and was wrong


def test_serve_control_in_float8_fails_the_limit(capsys):
    def patch(ctx):
        # a widest gap over 4 tiny requests swings with which of them a
        # loaded host finished (0.007-0.23); over 64 it does not
        ctx.mix = dict(ctx.mix, check_sample=64)

    rc, result, lines = rehearse(capsys, "base-serve-flood", seed=1,
                                 patch=patch, control="float8")
    assert rc == 0 and result["correct"] is True
    info = next(ln["info"] for ln in lines if "info" in ln)
    limit = compared(lines)["logit_gap_max"]["limit"]
    assert info["control"]["logit_gap_max"] > 3 * limit


def test_the_reference_names_the_programs_parameters():
    """Names and shapes of the reference's leaves are those of the served
    program's persistables (the training program is checked when it is
    built, in every run)."""
    from paddle_tpu.serving.decoder import _Cfg
    from paddle_tpu.serving.paged_decoder import build_unified_program

    cfg = {"n_layer": 2, "n_head": 2, "d_key": 4, "d_value": 4, "d_model": 8,
           "d_inner_hid": 16, "src_vocab_size": 11, "trg_vocab_size": 13,
           "max_length": 9}
    prog, _, _, _ = build_unified_program(
        _Cfg(11, 13, 2, 2, 4, 4, 8, 16, 9), src_len=8, max_out_len=8,
        page_size=4, num_pages=16, chunk_size=4, param_prefix="p")
    have = {v.name: tuple(v.shape) for v in prog.list_vars()
            if v.persistable and "@" not in v.name}
    assert have == ref.param_shapes(cfg, "p")
    made = weights.make(ref.param_shapes(cfg, "p"), 2**31 + 3)
    again = weights.make(ref.param_shapes(cfg, "p"), 2**31 + 3)
    other = weights.make(ref.param_shapes(cfg, "p"), 2**31 + 4)
    assert all(np.array_equal(made[k], again[k]) for k in made)
    assert not np.array_equal(made["p.enc0.ffn.fc1.w"],
                              other["p.enc0.ffn.fc1.w"])
    assert np.allclose(made["p.src_pos_emb.w"][0, 0::2], 0.0)   # sin(0)
    assert np.allclose(made["p.src_pos_emb.w"][0, 1::2], 1.0)   # cos(0)
