"""CPU rehearsals of each driver at a tiny size, through ``run_cell`` (not
the chip command): a sound run is ``correct``, and each fault a cell can
have, planted in the timed path underneath, makes ``correct`` false."""

from __future__ import annotations

import copy
import json
import shutil
from pathlib import Path

import jax
import pytest

from bench import harness
from bench.tests import tiny

SEED = 2 ** 33 + 5          # wider than 32 bits, as a run's seed may be
HERE = Path(__file__).resolve().parent

#: the numbers each rehearsal compared before the reference was found by
#: name and could shard itself (the tree before that change, on the CPU):
#: on one device they read exactly the same
BEFORE = {
    "lake": {"lake_bad_blocks": 0.0, "loss_gap": 1.6045348004343798e-07,
             "grad_gap": 1.0052048591526574e-06,
             "grad_err": 6.933329413352672e-07,
             "change_gap": 3.941905173529145e-06},
    "staged": {"lake_bad_blocks": 0.0, "loss_gap": 8.003773298884026e-08,
               "grad_gap": 7.213413484258062e-07,
               "grad_err": 7.27539519795663e-07,
               "change_gap": 1.7591658789656695e-05},
    "serve": {"prompt_kept": 0.0, "logit_gap": 0.0},
}


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
    assert {k: c["value"] for k, c in line["checks"].items()} == BEFORE[name]


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


def test_a_cell_of_a_new_config_and_reference_needs_only_new_files(tmp_path):
    """A configuration, its reference, a traffic mix and a per-layer metric,
    each a new file, and new entries in ``BENCHMARK.json``: the cell
    resolves, rehearses on the CPU through the new reference, and is
    correct."""
    root = tmp_path / "checkout"
    shutil.copytree(harness.ROOT / "bench", root / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    shutil.copy(HERE / "stub_reference.py",
                root / "bench" / "reference" / "stub_model.py")
    config = dict(copy.deepcopy(tiny.CONFIG), reference="stub_model")
    (root / "bench/configs/stub-model.json").write_text(json.dumps(config))
    (root / "bench/traffic/stub_staged.json").write_text(
        json.dumps(tiny.TRAFFIC["staged"]))
    (root / "bench/metrics/stub_steps.py").write_text(
        "def read(rec):\n    return rec.layer.get('steps')\n")
    spec = harness.load_json(harness.ROOT / "BENCHMARK.json")
    spec["configs"].append({"name": "stub-model", "source": "a test",
                            "file": "bench/configs/stub-model.json",
                            "reduced": [], "why": "a test"})
    spec["workloads"].append({"name": "stub-cell", "config": "stub-model",
                              "traffic": "stub_staged", "chips": 1,
                              "why": "a test"})
    spec["per_layer"].append({"name": "stub_steps", "unit": "steps",
                              "better": "higher", "source": "host_clock",
                              "layer": "train step",
                              "moves": "train_tokens_per_s",
                              "workloads": ["stub-cell"]})
    for m in spec["end_to_end"]:
        if m["name"].startswith("train_"):
            m["workloads"].append("stub-cell")
    cell = harness.resolve_cell(spec, "stub-cell", root)
    assert cell.reference.CALLS == []
    ctx = harness.RunContext(cell=cell, seed=SEED, seconds=1.0, trace=False,
                             devices=jax.devices()[:1])
    rec = harness.driver_for(cell, root).run(ctx)
    assert cell.reference.CALLS == ["train_steps"]
    line = harness.result_line(cell, rec, False, {"platform": "cpu"}, root)
    assert line["correct"], line["checks"]
    got = harness.read_per_layer(cell, rec, root)
    assert got["stub_steps"]["value"] == rec.layer["steps"] > 0
