#!/usr/bin/env python3
"""The sharded reference and a four-chip rehearsal on four virtual CPU
devices, for ``test_fsdp.py``, which runs this in a process of its own
(the device count is fixed when JAX starts):

    XLA_FLAGS=--xla_force_host_platform_device_count=4 \\
        JAX_PLATFORMS=cpu python3 bench/tests/fsdp_cpu.py

Prints one JSON object: ``reference`` (the tiny model's ``train_steps``
over four devices against one, by the numbers a train cell compares),
``sound`` and ``quarter_left_out`` (the train driver's rehearsal of a tiny
four-chip FSDP cell, as is and with one chip's quarter of every batch left
out of the gradient).
"""

from __future__ import annotations

import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

import jax  # noqa: E402
import numpy as np  # noqa: E402

from bench import compare, harness  # noqa: E402
from bench.tests import tiny  # noqa: E402

SEED = 2 ** 33 + 5


def reference() -> dict:
    ref = harness.reference_for(tiny.FSDP4)
    rng = np.random.default_rng(0)
    batches = []
    for _ in range(3):
        block = rng.integers(0, 512, (4, 65)).astype(np.int32)
        batches.append({"tokens": block[:, :-1], "targets": block[:, 1:],
                        "loss_mask": np.ones((4, 64), np.float32)})
    s, opt = ref.Sizes.of(tiny.FSDP4), tiny.FSDP4["optimizer"]
    one = ref.train_steps(s, opt, 7, batches, keep_grad=True,
                          devices=jax.devices()[:1])
    four = ref.train_steps(s, opt, 7, batches, against=one["grad"],
                           devices=jax.devices()[:4])
    return {"loss_gap": compare.loss_gap(four["losses"], one["losses"]),
            "grad_gap": compare.norm_gap(four["grad_norms"],
                                         one["grad_norms"]),
            "grad_err": compare.rel_to_leaf(four["grad_diff_norms"],
                                            one["grad_norms"]),
            "change_gap": compare.norm_gap(four["change_norms"],
                                           one["change_norms"])}


def _quarter_left_out(objs):
    """The last chip's rows of every batch weigh nothing in the loss: the
    mean is taken over the other three quarters."""
    tr = objs["trainer"]
    step = tr.step_fn
    keep = tr.job.global_batch * 3 // 4

    def stepped(state, batch):
        return step(state, dict(batch, loss_mask=batch["loss_mask"]
                                .at[keep:].set(0.0)))

    tr.step_fn = stepped


def rehearsal(patch=None) -> dict:
    line = harness.run_cell(tiny.cell("lake", tiny.FSDP4), SEED, 1.0, False,
                            jax.devices()[:4], patch=patch)
    return {"correct": line["correct"], "checks": line["checks"]}


def main() -> None:
    if len(jax.devices()) < 4:
        raise SystemExit("fsdp_cpu: needs 4 devices (XLA_FLAGS="
                         "--xla_force_host_platform_device_count=4)")
    out = {"reference": reference(), "sound": rehearsal(),
           "quarter_left_out": rehearsal(_quarter_left_out)}
    print(json.dumps(out), flush=True)


if __name__ == "__main__":
    main()
