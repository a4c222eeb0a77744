"""A cell's files: its configuration and its traffic mix, found by the names
``BENCHMARK.json`` gives them; and the tiny sizes of ``--rehearse-cpu``."""

from __future__ import annotations

import json
from typing import Dict

from . import manifest as mf
from . import traffic as tr

# --rehearse-cpu: the harness's own tiny sizes.  A different, tiny model
# whose only job is to walk the same code; never a measurement.
REHEARSAL_MODEL = {"n_layer": 1, "n_head": 2, "d_key": 8, "d_value": 8,
                   "d_model": 16, "d_inner_hid": 32, "src_vocab_size": 64,
                   "trg_vocab_size": 64}
REHEARSAL = {
    "train": {
        "cfg": dict(REHEARSAL_MODEL, max_length=9, check={
            # a 16-wide model in bfloat16 on the CPU backend, seeds 1, 2,
            # 3, 11: sound 0.0016 / 4e-4 / 0.014 / 0.047 at most; the
            # float8 control's token-loss gap 0.0135 and gradient gap
            # 0.051 at least (its mean loss 5e-4-6e-3)
            "token_loss_rms_gap": 0.005, "loss_gap_max": 0.002, "grad_norm_gap_worst_leaf": 0.03,
            "delta_norm_gap_worst_leaf": 0.15}),
        "mix": {"batch": 8, "seq_len": 8, "reference_block_rows": 4,
                "pool_batches": 4, "warm_steps": 1, "trace_seconds": 1},
    },
    "serve": {
        "cfg": dict(REHEARSAL_MODEL, max_length=17, src_len=16,
                    max_out_len=16, page_size=4, chunk_size=8, num_pages=96,
                    n_slots=4, end_id=64,
                    # CPU float32 is exact (sound 0.0); float8 control 0.03+
                    check={"logit_gap_max": 0.01}),
        "mix": {"clients": 6, "workers": 16, "rate_per_s": 12.0,
                "prompt_len": {"dist": "lognormal", "median": 6,
                               "sigma": 0.5, "min": 2, "max": 16},
                "max_new": {"ratio_uniform": [0.8, 1.2], "min": 2, "max": 16},
                "ramp_s": 0.5, "population": 256,
                "check_sample": 4, "trace_seconds": 1},
    },
}



def load_cell(manifest: Dict, name: str, rehearse: bool):
    cell = mf.cell(manifest, name)
    with open(mf.config_path(manifest, cell["config"]), encoding="utf-8") as f:
        cfg = json.load(f)
    mix = tr.load(mf.traffic_path(cell["traffic"]))
    if rehearse:
        tiny = REHEARSAL[mix["kind"]]
        cfg = {**cfg, **tiny["cfg"]}
        mix = {**mix, **tiny["mix"]}
    return cell, cfg, mix
