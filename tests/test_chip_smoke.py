"""chip_smoke.py's phases at reduced width on the CPU, and its refusal to
run anywhere but on a TPU."""

import sys

import jax
import pytest

from conftest import REPO

sys.path.insert(0, str(REPO))
import chip_smoke  # noqa: E402

SMALL = chip_smoke.Setup(reduced=True, seq=64, batch=8, plans_batch=4,
                         lr=1e-2)


def test_one_chip_phases(tmp_path):
    devices = jax.devices()
    a = chip_smoke.phase_train(SMALL, devices, tmp_path / "ck")
    assert len(a["losses"]) == SMALL.steps + 1
    b = chip_smoke.phase_reconfigure(SMALL, devices, tmp_path / "ck",
                                     a["losses"])
    assert len(b["losses"]) == 2
    assert b["compile_s"] > 0 and b["warm_step_s"] > 0


def test_four_chip_phase(multidevice):
    out = multidevice(f"""
import sys
sys.path.insert(0, {str(REPO)!r})
import jax
import chip_smoke
setup = chip_smoke.Setup(reduced=True, seq=32, batch=2, plans_batch=4)
res = chip_smoke.phase_plans(setup, jax.devices())
assert len(res) == 1 + len(setup.plans)
print("OK")
""", n_devices=4)
    assert "replicated under TP" in out
    assert "OK" in out


def test_main_refuses_without_tpu(capsys):
    with pytest.raises(SystemExit) as e:
        chip_smoke.main([])
    assert e.value.code not in (0, None)
    assert "no TPU" in str(e.value.code)
    assert '"ok"' not in capsys.readouterr().out
