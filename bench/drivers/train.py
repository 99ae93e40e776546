"""Train driver: a lake view → ``TokenBatcher`` → ``DeviceFeeder`` →
``Trainer.step_fn`` window, as ``Trainer.run`` drives it.

Set-up builds one ``Trainer`` on the cell's corpus and store, its state on
the device from the seed (``Trainer.initial_state(restore=False)``), and the
feed ``Trainer._batches()``.  It then drives that same state and feed
through the first steps, which compile the step and are the steps the
reference follows.  The window goes on with the same objects: each step
takes the next batch, runs the step and fetches its loss to the host.
No checkpoint is saved.

``correct`` compares:

- ``lake_bad_blocks``: blocks of every step, set-up and window, that are
  not whole kept documents of the corpus back to back (limit 0);
- ``loss_gap``: the first steps' losses against the reference's;
- ``grad_gap``: per-leaf norms of the first gradient as AdamW took it
  (read from its first moment after one step) against the reference's;
- ``grad_err``: the norm of the difference of that gradient and the
  reference's, per leaf the reference's gradient moves, over the same
  norms: the number that tells a lower precision from this one;
- ``change_gap``: per-leaf norms of the weights' change over the first
  steps against the reference's, over the leaves the reference's gradient
  moves.
"""

from __future__ import annotations

import contextlib
import gc
import time
from typing import Any, Dict, List

import numpy as np

from bench import compare, harness, window
from bench.gen.corpus import token_corpus
from bench.gen.lake import build_store

PROGRAM_KEYS = {"hidden_size": "d_model", "num_attention_heads": "num_heads",
                "num_key_value_heads": "num_kv_heads", "head_dim": "head_dim",
                "intermediate_size": "d_ff", "vocab_size": "vocab_size",
                "num_hidden_layers": "num_layers",
                "sliding_window": "sliding_window", "rope_theta": "rope_theta",
                "norm_epsilon": "norm_eps", "dtype": "dtype"}
OPT_KEYS = ("b1", "b2", "eps", "weight_decay", "clip_norm")


def model_diff(model_cfg, cfg: Dict[str, Any]) -> Dict[str, Any]:
    """(file, program) of every size where the program's model config
    differs from the configuration file."""
    return {k: (cfg[k], getattr(model_cfg, v)) for k, v in PROGRAM_KEYS.items()
            if cfg[k] != getattr(model_cfg, v)}


def check_config(model_cfg, opt, cfg: Dict[str, Any]) -> None:
    """The program runs what the configuration file states, or nothing."""
    diff = model_diff(model_cfg, cfg)
    diff.update({k: (cfg["optimizer"][k], getattr(opt, k)) for k in OPT_KEYS
                 if cfg["optimizer"][k] != getattr(opt, k)})
    if cfg["optimizer"]["moment_dtype"] != opt.moment_dtype:
        diff["moment_dtype"] = (cfg["optimizer"]["moment_dtype"],
                                opt.moment_dtype)
    if diff:
        raise harness.BenchError(f"program config differs from the file "
                                 f"(file, program): {diff}")


def leaf_norms(tree) -> Dict[str, Any]:
    import jax
    import jax.numpy as jnp
    flat, _ = jax.tree_util.tree_flatten_with_path(tree)
    return {jax.tree_util.keystr(p, simple=True, separator="/"):
            jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32))))
            for p, x in flat}


def run(ctx: harness.RunContext) -> harness.Record:
    import jax
    import jax.numpy as jnp
    from repro.core import telemetry
    from repro.launch.mesh import make_local_mesh
    from repro.launch.train import Trainer, TrainJob

    cfg, traffic = ctx.cell.config, ctx.cell.traffic
    t0 = time.perf_counter()
    window.log(f"compile cache {window.use_cache()}")
    counter = window.CompileCounter()
    tr, opt = cfg["train"], cfg["optimizer"]
    B, S = tr["global_batch"], tr["seq_len"]

    corpus = token_corpus(traffic["corpus"], ctx.seed, cfg["vocab_size"])
    t1 = time.perf_counter()
    window.log(f"corpus: {len(corpus)} documents, {len(corpus.tokens)} "
               f"tokens in {t1 - t0:.3f}s")
    ds, s3 = build_store(corpus, traffic["storage"])
    view = traffic["view"]
    keep = (np.isin(corpus.lang, view["langs"]) if view.get("langs")
            is not None else np.ones(len(corpus), bool))
    window.log(f"store ({traffic['storage']['kind']}): written in "
               f"{time.perf_counter() - t1:.3f}s; filter keeps "
               f"{int(corpus.lengths[keep].sum())} tokens")

    smoke = bool(cfg.get("smoke", False))
    job = TrainJob(arch=cfg["arch"], smoke=smoke,
                   num_layers=None if smoke else cfg["num_hidden_layers"],
                   steps=opt["schedule_steps"], global_batch=B, seq_len=S,
                   lr=opt["lr"], warmup=opt["warmup"],
                   shuffle=view["shuffle"], tql_filter=view.get("tql"),
                   seed=ctx.jax_seed, checkpoint_every=1 << 40,
                   log_every=1 << 40)
    mesh = make_local_mesh(devices=ctx.devices)
    if dict(mesh.shape) != cfg["mesh"]:
        raise harness.BenchError(f"the cell's devices make the mesh "
                                 f"{dict(mesh.shape)}; the config states "
                                 f"{cfg['mesh']}")
    trainer = Trainer(job, data_ds=ds, mesh=mesh)
    check_config(trainer.cfg, trainer.opt, cfg)
    if ctx.patch:
        ctx.patch({"trainer": trainer})
    state, _ = trainer.initial_state(restore=False)
    batches = trainer._batches()
    window.log(f"trainer and state: {time.perf_counter() - t0:.3f}s into "
               f"set-up")

    # the first steps: the window's own call and feed, followed by the
    # reference.  Their batches, and every later one, are kept (on the
    # device) for the lake check once the window has closed.
    fed: List[Dict[str, Any]] = []
    epoch_starts = [0]
    norms = jax.jit(leaf_norms)
    # the weights the steps start from, kept on the host: a second init
    # from the seed need not round every element alike on the chip
    p0 = jax.device_get(state["params"])
    diff_norm = jax.jit(lambda a, b: jnp.sqrt(jnp.sum(jnp.square(
        a.astype(jnp.float32) - b.astype(jnp.float32)))))

    def next_batch():
        nonlocal batches
        try:
            return next(batches)
        except StopIteration:       # next epoch, as Trainer.run does
            batches = trainer._batches()
            epoch_starts.append(len(fed))
            return next(batches)

    losses, grad_prog = [], None
    with mesh:
        for i in range(traffic["check_steps"]):
            batch = next_batch()
            fed.append(batch)
            state, metrics = trainer.step_fn(state, batch)
            losses.append(float(metrics["loss"]))
            if i == 0:
                grad_prog = {k: float(v) / (1 - opt["b1"]) for k, v in
                             norms(state["opt"]["m"]).items()}
                # the whole first gradient, for the element-wise comparison
                flat, _ = jax.tree_util.tree_flatten_with_path(
                    state["opt"]["m"])
                grad_host = {
                    jax.tree_util.keystr(path, simple=True, separator="/"):
                    np.asarray(x) / np.float32(1 - opt["b1"])
                    for path, x in flat}
        window.log(f"first steps and their gradient on the host: "
                   f"{time.perf_counter() - t0:.3f}s into set-up")
        flat, _ = jax.tree_util.tree_flatten_with_path(state["params"])
        flat0 = jax.tree_util.tree_leaves(p0)
        change_prog = {
            jax.tree_util.keystr(path, simple=True, separator="/"):
            float(diff_norm(x, jax.device_put(x0, x.sharding)))
            for (path, x), x0 in zip(flat, flat0)}
        del p0, flat0
    setup_s = time.perf_counter() - t0
    window.log(f"set-up {setup_s:.3f}s; first losses {losses}")

    # ------------------------------------------------------------ window
    s3_before = dict(s3.stats) if s3 is not None else {}
    ends, waits, dispatched, usage, wlosses = [], [], [], [], []
    tokens_per_step = B * S
    counter.active = True
    traced_steps = None
    traced = window.DeviceTrace(ctx.trace, ctx.devices,
                                traffic["trace_seconds"])
    program_spans = (telemetry.tracing() if ctx.trace
                     else contextlib.nullcontext())
    with program_spans as spans, mesh, window.quiet_host():
        usage0 = window.host_usage()
        start = time.perf_counter()
        traced.start()
        while True:
            ta = time.perf_counter()
            with window.span("bench.input_wait", ctx.trace):
                batch = next_batch()
            waits.append(time.perf_counter() - ta)
            fed.append(batch)
            with window.span("bench.step", ctx.trace):
                state, metrics = trainer.step_fn(state, batch)
            dispatched.append(time.perf_counter())
            with window.span("bench.loss_fetch", ctx.trace):
                wlosses.append(float(metrics["loss"]))
            ends.append(time.perf_counter())
            usage.append(window.host_usage())
            if traced.due(ends[-1] - start):
                traced.stop()
                traced_steps = len(ends)
            if ends[-1] - start >= ctx.seconds:
                break
        traced.stop()
    counter.active = False
    traced.summarize()
    stall = (sum(e.dur for e in spans.find("loader.stall")) if ctx.trace
             else None)
    s3_delta = ({k: s3.stats[k] - s3_before[k] for k in s3_before}
                if s3 is not None else None)
    window_s = ends[-1] - start
    steps = len(ends)
    window.log(f"window: {steps} steps in {window_s:.3f}s; compilations "
               f"inside it: {counter.count}")
    steps_s = np.diff([start] + ends)
    i = int(steps_s.argmax())
    starts = [start] + ends[:-1]
    window.log(
        f"longest step: {steps_s[i] * 1e3:.1f} ms (step {i} of {steps}, "
        f"median {float(np.median(steps_s)) * 1e3:.1f} ms): input "
        f"{waits[i] * 1e3:.1f} ms, dispatch "
        f"{(dispatched[i] - starts[i] - waits[i]) * 1e3:.1f} ms, loss "
        f"fetch {(ends[i] - dispatched[i]) * 1e3:.1f} ms; host "
        f"{window.usage_delta(usage[i - 1] if i else usage0, usage[i])} "
        f"in it, {window.usage_delta(usage0, usage[-1])} in the window")
    step_spans = harness.spans_of(ends, start)
    metrics_out = {
        "train_tokens_per_s": steps * tokens_per_step / window_s,
        "train_step_ms_p90": harness.percentile(step_spans, 90) * 1e3,
        "setup_s": setup_s,
    }
    mem = window.memory_peak(ctx.devices)

    # --------------------------------------------- after the window: checks
    blocks = [{k: np.asarray(b[k]) for k in ("tokens", "targets")} for b in fed]
    first = [{k: np.asarray(fed[i][k]) for k in ("tokens", "targets",
                                                 "loss_mask")}
             for i in range(traffic["check_steps"])]
    del state, metrics, batch, fed
    gc.collect()
    window.log(f"live device bytes before the reference: "
               f"{sum(a.nbytes for a in jax.live_arrays())}")
    bad_blocks = compare.stream_errors(blocks, corpus, keep, epoch_starts)
    ref = ctx.cell.reference
    reference = ref.train_steps(ref.Sizes.of(cfg), opt, ctx.jax_seed, first,
                                against=grad_host, devices=ctx.devices)
    # the peak never falls: above the window's, it is the reference's
    window.log(f"device memory peak after the reference "
               f"{window.memory_peak(ctx.devices)} bytes (window's {mem})")
    del grad_host
    moved = compare.moved_leaves(reference["grad_norms"])
    limits = cfg["limits"]
    readings = {
        "lake_bad_blocks": float(bad_blocks),
        "loss_gap": compare.loss_gap(losses, reference["losses"]),
        "grad_gap": compare.norm_gap(grad_prog, reference["grad_norms"]),
        "grad_err": compare.rel_to_leaf(
            {k: reference["grad_diff_norms"][k] for k in moved},
            reference["grad_norms"]),
        "change_gap": compare.norm_gap(change_prog,
                                       reference["change_norms"], moved),
    }
    window.log(f"reference losses {reference['losses']}; leaves left out of "
               f"the change: {sorted(set(reference['grad_norms']) - set(moved))}")
    checks = {k: {"value": v, "limit": float(limits[k])}
              for k, v in readings.items()}
    n_bad = int(np.sum(~np.isfinite(wlosses)))
    flops = harness.flops_for(cfg["family"]).train_flops_per_token(cfg, S)
    n_tr = traced_steps or steps
    layer = {
        "kind": "train", "steps": steps, "window_s": window_s,
        "traced_tokens_per_s": n_tr * tokens_per_step / (ends[n_tr - 1]
                                                         - start),
        "tokens": steps * tokens_per_step,
        "input_wait_s": float(sum(waits)), "loader_stall_s": stall,
        "s3": s3_delta, "flops_per_token": flops, "chips": len(ctx.devices),
        "peaks": harness.peaks(ctx.devices[0].device_kind)
        if ctx.devices[0].platform == "tpu" else None,
    }
    return harness.Record(metrics=metrics_out, attempted=steps,
                          failed=n_bad, memory_peak_bytes=mem, checks=checks,
                          layer=layer, trace=traced.summary)
