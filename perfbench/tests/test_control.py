"""The reference computed in int8 or in float8, put in the program's place,
comes out not correct at the reduced presets' sizes on the CPU, under the
limits set for that size (``data/limits-tiny.json``).  The same controls at
the cells' own sizes are read on the chip by ``calibrate.py``."""

import jax
import pytest

from cells import TINY, tiny_cell
from perfbench.lib import compare, weights
from perfbench.lib.traffic import MarkovTokens
from perfbench.reference.decoder import Reference

SEEDS = (2**31 + 1, 2**31 + 7920, 2**31 + 15839)


@pytest.mark.parametrize("mode", ["int8", "fp8"])
@pytest.mark.parametrize("workload", list(TINY))
def test_control_is_not_correct(workload, mode):
    cell = tiny_cell(workload)
    m, opt = cell.config["model"], cell.config["optimizer"]
    devs = jax.devices()[:cell.chips]
    ref, control = Reference(m, opt, devs, "f32"), Reference(m, opt, devs, mode)
    for seed in SEEDS:
        gen = MarkovTokens(m["vocab_size"], cell.traffic, seed)
        batches = [gen.batch(k) for k in range(3)]
        key = weights.seed_key(seed)
        values, _ = compare.numbers(control.train(key, batches),
                                    ref.train(key, batches))
        ok, checks = compare.judge(dict(values, nonfinite_steps=0), cell.limits)
        assert not ok, (seed, checks)
