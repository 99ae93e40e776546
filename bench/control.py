#!/usr/bin/env python3
"""Readings of the program, of the control and of planted faults, from
which the limits of ``correct`` are set (see ``PERF.md``), each judged by
the cell's own limits as a run is.  Not part of a benchmark run.

    python3 bench/control.py --workload <cell> --seeds 1 2 3

Kinds, at the cell's own sizes:

- ``program``: a run of the cell with a one-second window, its numbers as
  the run compares them: the lower readings;
- ``fp8``: the control, the reference with float8 (e4m3) products, the
  precision below the configuration's bfloat16 (the reference module's
  ``CONTROLS``).  Train cells compare it with the float32 reference by the
  numbers the cell compares, from each seed's weights and the first blocks
  of its corpus under the cell's filter, on the cell's devices; serve
  cells take, over the same sample of one round's served
  requests, the widest logit gap of the tokens it puts first;
- ``half_batch`` (train cells): the reference with half of each batch's
  rows left out of the loss, the mean taken over the rest (a planted
  fault).  A step that returns its state unchanged reads 1 on
  ``grad_gap`` and ``change_gap`` by their definition.

Prints one JSON line per seed and kind: the numbers, each beside its
limit, and ``correct`` as ``harness.Record`` decides it.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parents[1]


def first_blocks(corpus, keep, B: int, S: int, n: int):
    """The first ``n`` blocks of the corpus's kept documents in order, as
    ``TokenBatcher`` packs them."""
    need = B * (S + 1)
    docs = [corpus.doc(i) for i in np.flatnonzero(keep)]
    stream = np.concatenate(docs)[: need * n].reshape(n, B, S + 1)
    return [{"tokens": b[:, :-1], "targets": b[:, 1:],
             "loss_mask": np.ones((B, S), np.float32)} for b in stream]


def train_readings(cell, seed: int, devices=None):
    """The control's and the half batch's numbers against the reference,
    which runs over ``devices`` as the cell's own run does."""
    from bench import compare, harness
    from bench.gen.corpus import token_corpus

    ref = cell.reference
    cfg, traffic = cell.config, cell.traffic
    B, S = cfg["train"]["global_batch"], cfg["train"]["seq_len"]
    corpus = token_corpus(traffic["corpus"], seed, cfg["vocab_size"])
    langs = traffic["view"].get("langs")
    keep = (np.isin(corpus.lang, langs) if langs is not None
            else np.ones(len(corpus), bool))
    batches = first_blocks(corpus, keep, B, S, traffic["check_steps"])
    s, opt, js = ref.Sizes.of(cfg), cfg["optimizer"], harness.jax_seed(seed)
    base = ref.train_steps(s, opt, js, batches, keep_grad=True,
                           devices=devices)
    half = [dict(b, loss_mask=np.concatenate(
        [b["loss_mask"][: B // 2], np.zeros_like(b["loss_mask"][B // 2:])]))
        for b in batches]
    moved = compare.moved_leaves(base["grad_norms"])
    out = {}
    runs = [(k, {"dot": d}) for k, d in ref.CONTROLS.items()]
    runs.append(("half_batch", {}))
    for kind, kw in runs:
        got = ref.train_steps(s, opt, js, half if kind == "half_batch"
                              else batches, against=base["grad"],
                              devices=devices, **kw)
        out[kind] = {
            "loss_gap": compare.loss_gap(got["losses"], base["losses"]),
            "grad_gap": compare.norm_gap(got["grad_norms"],
                                         base["grad_norms"]),
            "grad_err": compare.rel_to_leaf(
                {k: got["grad_diff_norms"][k] for k in moved},
                base["grad_norms"]),
            "change_gap": compare.norm_gap(got["change_norms"],
                                           base["change_norms"], moved)}
    return out


def serve_control(cell, seed: int, devices):
    """The program's widest gap and the fp8 control's, over the same
    sample of one window's served requests."""
    from bench import harness

    ref = cell.reference
    seqs = []

    def grab(objs):
        server = objs["server"]
        generate = server.generate

        def keep(prompts, *a, **kw):
            out = generate(prompts, *a, **kw)
            seqs.append(out)
            return out

        server.generate = keep

    line = harness.run_cell(cell, seed, 1.0, False, devices, patch=grab)
    P = cell.traffic["prompt_tokens"]
    done = np.concatenate(seqs)                 # the window's rounds
    pick = np.random.default_rng(seed).choice(
        len(done), size=min(cell.traffic["check_requests"], len(done)),
        replace=False)
    out = {"program": {k: c["value"] for k, c in line["checks"].items()}}
    for kind, dot in ref.CONTROLS.items():
        gaps = ref.served_gaps(ref.Sizes.of(cell.config),
                               harness.jax_seed(seed), done[pick], P, dot=dot)
        out[kind] = {"prompt_kept": 0.0, "logit_gap": float(gaps.max())}
    return out


def judged(cell, numbers):
    """A ``harness.Record`` of the numbers read, with the cell's limits."""
    from bench import harness
    limits = cell.config["limits"]
    return harness.Record(
        metrics={}, attempted=1, failed=0, memory_peak_bytes=0,
        checks={k: {"value": float(v), "limit": float(limits[k])}
                for k, v in numbers.items()})


def readings(cell, seed: int, devices):
    """{kind: numbers read} for one seed."""
    from bench import harness
    if cell.traffic["kind"] != "train":
        return serve_control(cell, seed, devices)
    line = harness.run_cell(cell, seed, 1.0, False, devices)
    out = {"program": {k: c["value"] for k, c in line["checks"].items()}}
    out.update(train_readings(cell, seed, devices))
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    from bench import harness, window

    spec = harness.load_json(ROOT / "BENCHMARK.json")
    cell = harness.resolve_cell(spec, args.workload, ROOT)
    devices = harness.require_chips(cell.chips)
    window.use_cache()
    for seed in args.seeds:
        for kind, numbers in readings(cell, seed, devices).items():
            rec = judged(cell, numbers)
            print(json.dumps({"workload": cell.name, "seed": seed,
                              "kind": kind, "correct": rec.correct,
                              "checks": rec.checks}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
