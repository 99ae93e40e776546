"""Serve driver: a closed loop of clients around ``Server.generate``.

Each of ``clients`` clients waits for its reply before it sends the next
request, so every round is one ``generate`` call of the whole batch: the
first ``prompt_tokens`` tokens of the next documents of a corpus held in a
lake view in memory, and ``new_tokens`` greedy tokens.  Reading the prompts
from the lake and absorbing them are inside the window.

A generated token's time is when the host holds it: ``Server.generate``
fetches each sampled token to the host before its next ``decode_step``
call, so the driver stamps the calls of ``Server._decode`` (a wrapper that
only records the time).  Rounds start until ``--seconds`` have passed, and
each runs to its end: the window lasts from its start to the last token of
its last round, and ``serve_tokens_per_s`` is every generated token over
that time.  (A round absorbs its prompts first and generates after, so a
window cut at a fixed time would count tokens by where the cut fell.)

``correct`` compares ``logit_gap``: for a sample of the finished requests
drawn from the seed, at each served token, how far the reference's logit
of that token lies below the reference's best, over the prompt-end and
decode logits (the widest gap).  ``prompt_kept`` is 0 when every returned
sequence starts with its prompt and every id is in the vocabulary.
"""

from __future__ import annotations

import gc
import time
from typing import Any, Dict, List

import numpy as np

from bench import harness, window
from bench.drivers.train import model_diff
from bench.gen.corpus import token_corpus
from bench.gen.lake import build_store


def run(ctx: harness.RunContext) -> harness.Record:
    import jax
    import jax.numpy as jnp
    from repro.core.views import DatasetView
    from repro.launch.serve import Server, ServeJob

    cfg, traffic = ctx.cell.config, ctx.cell.traffic
    C, P, new = traffic["clients"], traffic["prompt_tokens"], \
        traffic["new_tokens"]
    t0 = time.perf_counter()
    window.log(f"compile cache {window.use_cache()}")
    counter = window.CompileCounter()
    corpus = token_corpus(traffic["corpus"], ctx.seed, cfg["vocab_size"])
    if int(corpus.lengths.min()) < P:
        raise harness.BenchError("a document is shorter than the prompt")
    window.log(f"corpus: {len(corpus)} documents in "
               f"{time.perf_counter() - t0:.3f}s")
    ds, _ = build_store(corpus, {"kind": "memory"})
    view = DatasetView.full(ds)

    smoke = bool(cfg.get("smoke", False))
    job = ServeJob(arch=cfg["arch"], smoke=smoke,
                   num_layers=None if smoke else cfg["num_hidden_layers"],
                   batch=C, prompt_len=P, max_new_tokens=new,
                   temperature=0.0, seed=ctx.jax_seed)
    server = Server(job)
    diff = model_diff(server.cfg, cfg)
    if diff:
        raise harness.BenchError(f"program config differs from the file "
                                 f"(file, program): {diff}")
    jax.block_until_ready(server.params)
    stamps: List[float] = []
    decode = server._decode

    def stamped(*args):
        stamps.append(time.perf_counter())
        return decode(*args)

    server._decode = stamped
    if ctx.patch:
        ctx.patch({"server": server})

    def prompts(r: int):
        docs = [(r * C + j) % len(corpus) for j in range(C)]
        rows = [view.row(d, ["tokens"])["tokens"][:P] for d in docs]
        return np.stack(rows).astype(np.int32), docs

    # warm-up: the cache init, the decode step and the sampling are every
    # program a round runs.  Calls made as ``generate`` makes them (inside
    # the mesh, which is part of a jitted call's key; the second decode on
    # the first's cache, as its sharding is) compile them all.
    cache = server._init_cache(C, P + new)
    rng = jax.random.PRNGKey(job.seed)
    with server.mesh:
        for t in range(2):
            logits, cache = server._decode(
                server.params, cache, jnp.asarray(np.zeros((C,), np.int32)),
                jnp.int32(t))
        np.asarray(server._sample(logits, rng, P))
    del cache, logits
    setup_s = time.perf_counter() - t0
    window.log(f"set-up {setup_s:.3f}s")

    # ------------------------------------------------------------ window
    rounds: List[Dict[str, Any]] = []
    counter.active = True
    traced = window.DeviceTrace(ctx.trace, ctx.devices,
                                traffic["trace_seconds"])
    with window.quiet_host():
        start = time.perf_counter()
        traced.start()
        r = 1
        while time.perf_counter() - start < ctx.seconds:
            rt0 = time.perf_counter()
            with window.span("bench.prompt_read", ctx.trace):
                pr, docs = prompts(r)
            stamps.clear()
            with window.span("bench.generate", ctx.trace):
                out = server.generate(pr)
            rounds.append({"start": rt0, "stamps": list(stamps), "out": out,
                           "prompts": pr})
            r += 1
            if traced.due(time.perf_counter() - start):
                traced.stop()
    traced.stop()
    counter.active = False
    traced.summarize()
    window.log(f"window: {len(rounds)} rounds; compilations inside it: "
               f"{counter.count}")

    calls = [np.diff([rd["start"]] + rd["stamps"]) for rd in rounds]
    worst = max(range(len(rounds)), key=lambda r: calls[r].max())
    window.log(f"longest host gap before a decode call: "
               f"{calls[worst].max() * 1e3:.1f} ms (round {worst} of "
               f"{len(rounds)}, call {int(calls[worst].argmax())})")
    held = [np.asarray(rd["stamps"][P:P + new]) for rd in rounds]
    ttft = [rd["stamps"][P] - rd["start"] for rd in rounds]
    token_gaps = np.concatenate([np.diff(h) for h in held])
    # the window runs from its start to the last token of its last round
    end = held[-1][-1]
    tokens = len(rounds) * C * new
    metrics_out = {"serve_tokens_per_s": tokens / (end - start),
                   "setup_s": setup_s}
    mem = window.memory_peak(ctx.devices)

    # --------------------------------------------- after the window: checks
    seqs = np.concatenate([rd["out"] for rd in rounds])
    kept = all(np.array_equal(rd["out"][:, :P], rd["prompts"])
               for rd in rounds) and bool(
        ((seqs >= 0) & (seqs < cfg["vocab_size"])).all())
    pick = np.random.default_rng(ctx.seed).choice(
        len(seqs), size=min(traffic["check_requests"], len(seqs)),
        replace=False)
    del server, decode, stamped
    gc.collect()
    window.log(f"live device bytes before the reference: "
               f"{sum(a.nbytes for a in jax.live_arrays())}")
    ref = ctx.cell.reference
    gaps = ref.served_gaps(ref.Sizes.of(cfg), ctx.jax_seed, seqs[pick], P)
    limits = cfg["limits"]
    checks = {"prompt_kept": {"value": 0.0 if kept else 1.0, "limit": 0.0},
              "logit_gap": {"value": float(gaps.max()),
                            "limit": float(limits["logit_gap"])}}
    window.log(f"logit gaps over {gaps.size} served tokens: median "
               f"{float(np.median(gaps))}, max {float(gaps.max())}")
    fl = harness.flops_for(cfg["family"])
    per_round = P + new
    keys = (per_round + 1) / 2                   # mean keys of a call
    layer = {
        "kind": "serve", "rounds": len(rounds), "clients": C,
        "window_s": end - start,
        "ttft_s": ttft, "token_gaps_s": token_gaps.tolist(),
        "decode_calls": len(rounds) * per_round,
        "processed_tokens": len(rounds) * per_round * C,
        "flops_per_token": fl.forward_flops_per_token(cfg, keys),
        "decode_bytes": fl.decode_bytes(cfg, C, keys),
        "decode_flops": fl.forward_flops_per_token(cfg, keys) * C,
        "chips": len(ctx.devices),
        "peaks": harness.peaks(ctx.devices[0].device_kind)
        if ctx.devices[0].platform == "tpu" else None,
    }
    return harness.Record(metrics=metrics_out,
                          attempted=len(rounds) * C, failed=0,
                          memory_peak_bytes=mem, checks=checks, layer=layer,
                          trace=traced.summary)
