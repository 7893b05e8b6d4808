"""The platform choice hides no device: off a TPU the kernels interpret,
and the chip smoke refuses to succeed anywhere but on a TPU."""
import importlib.util
import pathlib

import jax
import pytest

from repro.platform import pallas_interpret

ROOT = pathlib.Path(__file__).resolve().parents[1]


def _chip_smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke",
                                                  ROOT / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_pallas_interprets_off_tpu():
    assert jax.default_backend() != "tpu"
    assert pallas_interpret() is True


def test_chip_smoke_refuses_a_non_tpu_device():
    with pytest.raises(RuntimeError, match="no TPU found"):
        _chip_smoke().require_tpu()

