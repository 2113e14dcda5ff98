"""The one-stage StarCoder2-3B cell at the reduced preset's sizes on the CPU.

The preset's window (16) is shorter than the sequence (64), so the band,
and not only the causal triangle, decides which keys a query sees, as the
published window (4096) does in the cell's 8192 tokens.  A sound run comes
out correct; the program's step built without the window, a step that
leaves the state unchanged, and the reference in int8 in the program's
place come out not correct, under the limits set for that size
(``data/limits-tiny-stage.json``).
"""

import json
from pathlib import Path

import jax
import pytest

import repro.launch.train as launch_train
import repro.train.step as step_mod
from perfbench import run as harness
from perfbench.lib import compare, flops, weights
from perfbench.lib.spec import Cell
from perfbench.lib.traffic import MarkovTokens
from perfbench.reference.decoder import Reference
from repro import configs
from repro.models import build
from test_faults import broken

HERE = Path(__file__).resolve().parent
BENCH = HERE.parent
WORKLOAD = "starcoder2-3b-stage.gc.b1x8192"
SEQ = 64
SEED = 2**31 + 12345
CONTROL_SEEDS = (2**31 + 1, 2**31 + 7920, 2**31 + 15839)


def stage_cell() -> Cell:
    """The cell with the tiny configuration, its own traffic at ``SEQ``."""
    traffic = json.loads((BENCH / "traffic" / "gc.b1x8192.json").read_text())
    limits = json.loads((HERE / "data" / "limits-tiny-stage.json").read_text())
    return Cell(name=WORKLOAD, chips=1,
                config=json.loads((HERE / "data" /
                                   "tiny-starcoder2-stage.json").read_text()),
                traffic=dict(traffic, seq_len=SEQ), limits=limits[WORKLOAD],
                end_to_end=[{"name": "tokens_per_s", "unit": "tokens/s"},
                            {"name": "step_s_p95", "unit": "s"},
                            {"name": "setup_s", "unit": "s"}],
                per_layer=[])


def one_run():
    return harness.run(stage_cell(), SEED, 0.5, False, jax.devices()[:1],
                       reduced=True)


def test_flops_per_token():
    m = json.loads((BENCH / "configs" / "starcoder2-3b-stage.json").read_text())
    m = m["model"]
    # 10 x (2 x 3072^2 + 2 x 3072 x 256 + 2 x 3072 x 12288) + 3072 x 49152
    assert flops.matmul_params(m) == 1_110_441_984
    # keys 1..4096 for the first 4096 queries, then 4096 each
    assert flops.mean_keys(8192, m["sliding_window"]) == 3072.25
    assert flops.per_token(m, 8192) == pytest.approx(7.795e9, rel=1e-4)


def test_stage_is_starcoder2_cut_in_depth():
    full, stage = configs.get("starcoder2-3b"), configs.get("starcoder2-3b-stage")
    assert stage == full.with_(name="starcoder2-3b-stage", n_layers=10)
    tiny = configs.get_reduced("starcoder2-3b-stage")
    assert 0 < tiny.sliding_window < SEQ


def test_sound_run_is_correct():
    res = one_run()
    assert res["correct"], res["checks"]


def _window_dropped(monkeypatch):
    """The program's step built with ``sliding_window=0``: causal over every
    key, while the reference keeps the band."""
    make = launch_train.build_runtime

    def build_runtime(*args, **kw):
        cfg, model, plan = make(*args, **kw)
        return cfg, build(cfg.with_(sliding_window=0), model.opts), plan
    monkeypatch.setattr(launch_train, "build_runtime", build_runtime)


def _unchanged(monkeypatch):
    """A step that returns the state it was given."""
    monkeypatch.setattr(step_mod, "make_train_step", broken("unchanged"))


@pytest.mark.parametrize("fault", [_window_dropped, _unchanged],
                         ids=["window_dropped", "unchanged"])
def test_broken_run_is_not_correct(fault, monkeypatch):
    fault(monkeypatch)
    res = one_run()
    assert not res["correct"], res["checks"]


@pytest.mark.parametrize("seed", CONTROL_SEEDS)
def test_int8_control_is_not_correct(seed):
    cell = stage_cell()
    m, opt = cell.config["model"], cell.config["optimizer"]
    devs = jax.devices()[:1]
    gen = MarkovTokens(m["vocab_size"], cell.traffic, seed)
    batches = [gen.batch(k) for k in range(3)]
    key = weights.seed_key(seed)
    values, _ = compare.numbers(Reference(m, opt, devs, "int8").train(key, batches),
                                Reference(m, opt, devs, "f32").train(key, batches))
    ok, checks = compare.judge(dict(values, nonfinite_steps=0), cell.limits)
    assert not ok, (seed, checks)
