"""A run whose timed path is broken underneath comes out not correct.

Each test skips the harness's look for a chip and drives the rest of a
run (``run.run``) on CPU devices at the reduced presets' sizes, with the
program's train step replaced by a broken one, under the limits set for
that size (``data/limits-tiny.json``).  A sound run of each cell comes out
correct.
"""

import jax
import pytest

import repro.train.step as step_mod
from cells import TINY, tiny_cell
from perfbench import run as harness
from perfbench.lib import weights

SEED = 2**31 + 12345
LEAVES = ("emb", "layers/attn/bk", "ln_f")   # the largest leaf and two small ones


def broken(kind: str):
    """``kind``: a fault, or ``frozen:<leaf>`` / ``double:<leaf>`` for one
    leaf left unmoved or moved by twice its update."""
    make = step_mod.make_train_step
    fault, _, leaf = kind.partition(":")

    def make_broken(model, plan, optcfg):
        step = make(model, plan, optcfg)

        def train_step(params, opt_state, batch):
            if kind == "half_batch":        # half the rows; the mean over the rest
                batch = {k: v[: v.shape[0] // 2] for k, v in batch.items()}
            elif kind == "no_exchange":     # one chip's rows alone
                batch = {k: v[: v.shape[0] // plan.dp] for k, v in batch.items()}
            new_p, new_o, out = step(params, opt_state, batch)
            if kind == "unchanged":
                return params, opt_state, out
            if leaf:
                old, new = weights.flat(params)[leaf], weights.flat(new_p)
                new[leaf] = old if fault == "frozen" else new[leaf] + (new[leaf] - old)
                new_p = weights.unflat(new)
            return new_p, new_o, out
        return train_step
    return make_broken


CASES = [(w, k) for w in TINY for k in ("unchanged", "half_batch")]
CASES += [(w, f"{f}:{leaf}") for w in TINY for f in ("frozen", "double")
          for leaf in LEAVES]
CASES.append(("starcoder2-3b.zero3.b4x4096", "no_exchange"))


def one_run(workload):
    cell = tiny_cell(workload)
    return harness.run(cell, SEED, 0.5, False, jax.devices()[:cell.chips],
                       reduced=True)


@pytest.mark.parametrize("workload", list(TINY))
def test_sound_run_is_correct(workload):
    res = one_run(workload)
    assert res["correct"], res["checks"]


@pytest.mark.parametrize("workload,kind", CASES)
def test_broken_step_is_not_correct(workload, kind, monkeypatch):
    monkeypatch.setattr(step_mod, "make_train_step", broken(kind))
    res = one_run(workload)
    assert not res["correct"], res["checks"]
