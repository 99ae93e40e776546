"""The train reference sharded over four devices, and a four-chip FSDP
rehearsal of the train driver, on four virtual CPU devices in a process of
their own (``fsdp_cpu.py``)."""

from __future__ import annotations

import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

HERE = Path(__file__).resolve().parent


@pytest.fixture(scope="module")
def got():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    run = subprocess.run([sys.executable, str(HERE / "fsdp_cpu.py")],
                         env=env, capture_output=True, text=True, timeout=600)
    assert run.returncode == 0, run.stderr[-4000:]
    return json.loads(run.stdout.strip().splitlines()[-1])


def test_four_devices_give_the_one_device_readings_to_f32_rounding(got):
    # f32 sums in another order: the tiny model reads 1e-7 to 2e-6 here,
    # the fp8 control 2e-3 to 0.1 and a half batch 0.02 to 0.8.  AdamW
    # divides each step by the gradient's own scale, so the key bias, whose
    # gradient nearly cancels under softmax, carries that rounding into its
    # change: 9e-6 of the median leaf's
    ref = got["reference"]
    assert ref["loss_gap"] < 1e-6, ref
    assert ref["grad_gap"] < 1e-5, ref
    assert ref["grad_err"] < 1e-5, ref
    assert ref["change_gap"] < 1e-4, ref


def test_a_four_chip_rehearsal_is_correct(got):
    assert got["sound"]["correct"], got["sound"]


def test_one_chips_quarter_left_out_of_the_gradient_is_not_correct(got):
    bad = got["quarter_left_out"]
    assert not bad["correct"]
    c = bad["checks"]["loss_gap"]
    assert c["value"] > c["limit"], bad
