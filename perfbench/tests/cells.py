"""The cells at the sizes of the repo's reduced presets, for CPU tests."""

import json
from pathlib import Path

from perfbench.lib.spec import Cell

HERE = Path(__file__).resolve().parent
LIMITS = json.loads((HERE / "data" / "limits-tiny.json").read_text())

TINY = {
    # workload: (tiny config, plan, global batch, chips)
    "gpt2-1.5b.gc.b2x1024": ("tiny-gpt2", {"gc": True}, 2, 1),
    "starcoder2-3b.zero3.b4x4096": ("tiny-starcoder2",
                                    {"dp": 4, "zero_stage": 3, "gc": True}, 4, 4),
}
SEQ = 64       # a multiple of the reduced presets' attention tiles (16, 32)
E2E = [{"name": "tokens_per_s", "unit": "tokens/s"},
       {"name": "step_s_p95", "unit": "s"}, {"name": "setup_s", "unit": "s"}]


def tiny_cell(workload: str) -> Cell:
    conf, plan, batch, chips = TINY[workload]
    traffic = {"plan": plan, "global_batch": batch, "seq_len": SEQ,
               "tokens": {"kind": "markov", "mix": 257, "follow": 0.7},
               "trace_steps": 2}
    return Cell(name=workload, chips=chips,
                config=json.loads((HERE / "data" / f"{conf}.json").read_text()),
                traffic=traffic,
                limits=LIMITS[workload],
                end_to_end=E2E, per_layer=[])
