"""CPU rehearsals of each driver at a tiny size, through ``run_cell`` (not
the chip command): a sound run is ``correct``, and each fault a cell can
have, planted in the timed path underneath, makes ``correct`` false."""

from __future__ import annotations

import jax
import pytest

from bench import harness
from bench.tests import tiny

SEED = 2 ** 33 + 5          # wider than 32 bits, as a run's seed may be


def run(name, patch=None, seconds=1.0, cell=None):
    return harness.run_cell(cell or tiny.cell(name), SEED, seconds, False,
                            jax.devices(), patch=patch)


@pytest.mark.parametrize("name", ["lake", "staged", "serve"])
def test_a_sound_run_is_correct(name, capsys):
    line = run(name)
    assert "compilations inside it: 0" in capsys.readouterr().out
    assert line["correct"], line["checks"]
    assert line["attempted"] > 0 and line["failed"] == 0
    assert line["device"]["platform"] == "cpu"
    for m in line["metrics"].values():
        assert m["value"] > 0


def test_epochs_that_end_inside_the_window_are_checked_too():
    cell = tiny.cell("lake")
    cell.traffic["corpus"]["total_tokens"] = 2500
    line = run("lake", cell=cell)
    assert line["attempted"] > 30          # several epochs of ~8 steps
    assert line["correct"], line["checks"]


def _step_keeps_state(objs):
    from repro.launch.steps import make_train_step
    tr = objs["trainer"]
    step = jax.jit(make_train_step(tr.model, tr.opt))
    tr.step_fn = lambda state, batch: (state, step(state, batch)[1])


def _half_batch(objs):
    tr = objs["trainer"]
    step = tr.step_fn
    half = tr.job.global_batch // 2
    tr.step_fn = lambda state, batch: step(
        state, {k: v[:half] for k, v in batch.items()})


def _token_altered_in_the_feed(objs):
    tr = objs["trainer"]
    batches = tr._batches

    def altered():
        it = batches()
        for i, b in enumerate(it):
            if i == 1:
                b = dict(b, tokens=b["tokens"].at[0, 5].add(1))
            yield b

    tr._batches = altered


def _token_altered_where_sampled(objs):
    server = objs["server"]
    sample = server._sample

    def altered(logits, rng, t):
        tok = sample(logits, rng, t)
        if t == server.job.prompt_len + 1:     # one position of every request
            tok = (tok + 1) % server.cfg.vocab_size
        return tok

    server._sample = altered


@pytest.mark.parametrize("name,fault,check", [
    ("staged", _step_keeps_state, "change_gap"),
    ("staged", _half_batch, "loss_gap"),
    ("lake", _token_altered_in_the_feed, "lake_bad_blocks"),
    ("serve", _token_altered_where_sampled, "logit_gap"),
])
def test_a_fault_underneath_is_not_correct(name, fault, check):
    line = run(name, patch=fault)
    assert not line["correct"]
    c = line["checks"][check]
    assert c["value"] > c["limit"], line["checks"]
