"""`chip_smoke.py` on the CPU mesh: its NumPy f64 reference agrees with
the package's XLA diffusion step, and it refuses a device that is not a
TPU (in-process; the chip run itself happens on the chip machine)."""

import pathlib
import sys

import jax
import numpy as np
import pytest

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1]))

import chip_smoke  # noqa: E402


def test_numpy_reference_matches_package_xla_step():
    out = chip_smoke.run_tier("diffusion3d", 10, True, 2, 2,
                              jax.devices()[:4], impl="xla", numpy_steps=3)
    p = out["params"]
    ref = chip_smoke.numpy_diffusion(out["initial"]["T"], out["initial"]["Cp"],
                                     p.lam, p.dt, (p.dx, p.dy, p.dz), 3)
    assert out["tier"] == "xla" and out["steps"] == 5
    assert ref.shape == out["early"]["T"].shape == (16, 16, 8)
    err = chip_smoke.rel_err({"T": out["early"]["T"]}, {"T": ref})
    assert err < chip_smoke.TOL_NUMPY, err


def test_refuses_a_device_that_is_not_a_tpu(capsys):
    with pytest.raises(chip_smoke.SmokeFailure, match="no TPU"):
        chip_smoke.check_tpu(jax.devices(), 1)
    with pytest.raises(chip_smoke.SmokeFailure, match="no TPU"):
        chip_smoke.main([])
    assert '"ok"' not in capsys.readouterr().out
