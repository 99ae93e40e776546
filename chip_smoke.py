#!/usr/bin/env python3
"""Bring-up smoke test: the lake-fed train and serve path on a TPU chip.

    python chip_smoke.py             # one chip: phases (a)-(d) below
    python chip_smoke.py --chips 4   # four chips: FSDP training vs one device

Phases, all in this one process (a chip belongs to one process at a time):

(a) device check: the first JAX device must be a TPU; there is no CPU
    fallback.
(b) lake -> train: starcoder2-3b at published widths cut to 4 layers, global
    batch 2 x 2048, 8 steps and a checkpoint, fed from a TQL-filtered view
    streamed from simulated S3 behind an LRU cache, through TokenBatcher ->
    DeviceFeeder -> Trainer.
(c) serve: starcoder2-3b at full depth through Server, batch 8, prompt 128,
    32 greedy tokens; the logits after the last prompt token are checked
    against Model.prefill on the same prompts.
(d) kernels: each Pallas kernel compiled for the chip against its ref.py.

``--chips 4`` runs only the four-chip phase: Trainer on a (data=4, model=1)
mesh at global batch 4 x 1024 against the same job on one device, and one
step on a quarter of that batch to show the loss check would see it.

Lines before the last are bring-up observations (times on the host clock,
compile included where said).  The last line is one JSON object:
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
Any failed phase raises, so the script exits non-zero and prints no result.
"""

from __future__ import annotations

import argparse
import dataclasses
import gc
import json
import sys
import time
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

ARCH = "starcoder2-3b"
TQL = "SELECT * FROM dataset WHERE doc_id % 2 == 0"


def log(msg: str) -> None:
    print(f"[chip_smoke] {msg}", flush=True)


def check(ok: bool, msg: str) -> None:
    if not ok:
        raise AssertionError(msg)


def require_tpu():
    import jax
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise SystemExit(f"chip_smoke: needs a TPU; JAX found platform "
                         f"{dev.platform!r} ({dev.device_kind})")
    return jax.devices()


def peak_gb(dev) -> str:
    stats = dev.memory_stats() or {}
    return f"{stats.get('peak_bytes_in_use', 0) / 1e9:.2f} GB"


def train_phase() -> None:
    from repro.launch.train import Trainer, TrainJob
    # Adam's first steps move every weight by about the rate: at 3e-4 on a
    # 10-step ramp this model's gradient norm jumps 8x by step 2 and its loss
    # with it (v5e, PERF.md).  1e-5 from the first step keeps the gradient
    # norm within 10% of its start, and the loss falls at every step but one.
    job = TrainJob(arch=ARCH, smoke=False, num_layers=4, steps=8,
                   global_batch=2, seq_len=2048, lr=1e-5, warmup=1,
                   remote_data=True, num_docs=16, tql_filter=TQL,
                   checkpoint_every=8, log_every=1)
    t0 = time.perf_counter()
    trainer = Trainer(job)
    log(f"train: {trainer.cfg.num_layers} layers, d_model "
        f"{trainer.cfg.d_model}, lake view built in "
        f"{time.perf_counter() - t0:.1f}s")
    out = trainer.run(restore=False)
    losses = [h["loss"] for h in out["history"]]
    secs = [h["sec"] for h in out["history"]]
    log(f"train: losses {losses}")
    log(f"train: first step incl. compile {secs[0]:.2f}s; later steps "
        f"{[round(s, 4) for s in secs[1:]]} s (host clock, each ended by "
        f"fetching its loss)")
    log(f"train: peak_bytes_in_use {peak_gb(trainer.mesh.devices.flat[0])}")
    check(out["final_step"] == job.steps, f"stopped at {out['final_step']}")
    check(bool(np.isfinite(losses).all()), f"non-finite loss: {losses}")
    # At the initial weights the 8 batches' losses span 0.06 (v5e, PERF.md):
    # a fall of 0.3 is learning, not an easier batch.  The run reads 0.90.
    check(losses[-1] <= losses[0] - 0.3, f"loss did not fall: {losses}")
    # a spike as at rate 3e-4, where step 2 read 1.24x the first, fails here
    check(max(losses[1:]) < losses[0], f"loss rose above the first: {losses}")
    check(trainer.ckpt.latest_step() == job.steps,
          f"checkpoint at {trainer.ckpt.latest_step()}, not {job.steps}")
    log(f"train: ok, checkpoint saved at step {job.steps}")


def serve_phase() -> None:
    import jax
    import jax.numpy as jnp
    from repro.launch.serve import Server, ServeJob
    job = ServeJob(arch=ARCH, smoke=False, batch=8, prompt_len=128,
                   max_new_tokens=32)
    t0 = time.perf_counter()
    server = Server(job)
    jax.block_until_ready(server.params)
    log(f"serve: {server.cfg.num_layers} layers initialised in "
        f"{time.perf_counter() - t0:.1f}s (compile included)")
    V = server.cfg.vocab_size
    prompts = np.random.default_rng(0).integers(
        0, V, (job.batch, job.prompt_len)).astype(np.int32)
    t0 = time.perf_counter()
    out = server.generate(prompts)
    log(f"serve: generate {out.shape} in {time.perf_counter() - t0:.2f}s "
        f"(first call, compile included); prompt {server.stats['prefill_s']:.2f}s, "
        f"decode {server.stats['decode_s']:.2f}s")
    t0 = time.perf_counter()
    server.generate(prompts)
    log(f"serve: generate again in {time.perf_counter() - t0:.2f}s "
        f"({server.throughput():.1f} tok/s decode over both calls)")
    log(f"serve: peak_bytes_in_use {peak_gb(jax.devices()[0])}")
    check(out.shape == (job.batch, job.prompt_len + job.max_new_tokens),
          f"shape {out.shape}")
    check(bool((out[:, :job.prompt_len] == prompts).all()), "prompt changed")
    new = out[:, job.prompt_len:]
    check(bool(((new >= 0) & (new < V)).all()), "generated id out of range")

    with server.mesh:
        ref, _ = jax.jit(server.model.prefill)(server.params,
                                               {"tokens": jnp.asarray(prompts)})
    got = np.asarray(server.prompt_logits, np.float64)[:, :V]
    want = np.asarray(ref, np.float64)[:, :V]
    rel = float(np.linalg.norm(got - want) / np.linalg.norm(want))
    # bf16 weights and activations through 30 layers: the served path takes
    # the prompt through decode (one softmax over the cached K/V), prefill
    # through blockwise attention, so the two round in different orders.  At
    # reduced widths on CPU that differs by 1.4-1.5% relative L2 at 30 layers;
    # a wrong cache slot, position or layer differs by O(1).
    tol = 5e-2
    log(f"serve: prompt logits vs Model.prefill: relative L2 {rel:.5f} "
        f"(tolerance {tol}), argmax agreement "
        f"{(got.argmax(-1) == want.argmax(-1)).mean():.3f}")
    check(rel <= tol, f"prompt logits differ from prefill by {rel}")


def kernel_phase() -> None:
    import jax
    import jax.numpy as jnp
    from repro.kernels.decode_attention import decode_attention
    from repro.kernels.decode_attention.ref import ref_decode_attention
    from repro.kernels.flash_attention import flash_attention_fwd
    from repro.kernels.flash_attention.ref import ref_attention
    from repro.kernels.fused_preprocess.fused_preprocess import \
        fused_preprocess_fwd
    from repro.kernels.fused_preprocess.ref import ref_preprocess
    from repro.kernels.ssd_scan import ssd_fwd
    from repro.kernels.ssd_scan.ref import ref_ssd

    rng = np.random.default_rng(0)

    def arr(shape, dtype=jnp.bfloat16, scale=1.0):
        return jnp.asarray(rng.standard_normal(shape) * scale, dtype)

    def rel_l2(got, want):
        got = [np.asarray(g, np.float64) for g in jax.tree_util.tree_leaves(got)]
        want = [np.asarray(w, np.float64) for w in jax.tree_util.tree_leaves(want)]
        diff = np.sqrt(sum(np.sum((g - w) ** 2) for g, w in zip(got, want)))
        return float(diff / np.sqrt(sum(np.sum(w ** 2) for w in want)))

    # Relative L2 over all outputs, not the largest error: both sides round
    # to bf16, and one rounding flip at the largest element alone reads up to
    # 2^-7 there.  Tolerances, each a few times the v5e reading (PERF.md):
    # - attention reads 2.9e-5 to 6.4e-5 with f32 softmax and accumulation;
    #   probabilities rounded to bf16 before P@V read 2.1e-3 and a window
    #   one key too wide 2.7e-2 (CPU emulation at these widths);
    # - ssd_scan reads 2.2e-4; its chunk matmuls take bf16 operands by
    #   design;
    # - fused_preprocess reads 2.3e-7: f32 arithmetic on exact small
    #   integers.
    tol = {"flash_attention": 2e-4, "decode_attention": 2e-4,
           "ssd_scan": 1e-3, "fused_preprocess": 1e-6}
    errs = {}

    def compare(name, got, want):                 # name: kernel [case]
        errs[name] = rel_l2(got, want)
        log(f"kernel {name}: relative L2 {errs[name]:.3e} (tolerance "
            f"{tol[name.split()[0]]})")

    with jax.default_matmul_precision("highest"):
        B, S, H, Hkv, D = 2, 2048, 24, 2, 128
        q, k, v = arr((B, S, H, D)), arr((B, S, Hkv, D)), arr((B, S, Hkv, D))
        # starcoder2's window, then one shorter than S so its mask is run
        for w in (4096, 512):
            compare(f"flash_attention window {w}",
                    jax.jit(lambda q, k, v: flash_attention_fwd(
                        q, k, v, causal=True, window=w))(q, k, v),
                    jax.jit(lambda q, k, v: ref_attention(
                        q, k, v, causal=True, window=w))(q, k, v))

        B, T = 8, 4096
        q, ck, cv = arr((B, H, D)), arr((B, T, Hkv, D)), arr((B, T, Hkv, D))
        # a window cache is a ring of T = window slots: at pos >= window
        # every slot is valid, before it only the first pos + 1
        for pos in (3000, 5000):
            p = jnp.int32(pos)
            compare(f"decode_attention pos {pos}",
                    jax.jit(lambda q, k, v, p: decode_attention(
                        q, k, v, pos=p, window=4096))(q, ck, cv, p),
                    jax.jit(lambda q, k, v, p: ref_decode_attention(
                        q, k, v, pos=p, window=4096))(q, ck, cv, p))

        B, S, nh, P, G, N = 2, 2048, 64, 64, 1, 128
        x = arr((B, S, nh, P), scale=0.5)
        dt = jnp.asarray(rng.uniform(1e-3, 0.1, (B, S, nh)), jnp.float32)
        A = jnp.asarray(-rng.uniform(0.5, 4.0, (nh,)), jnp.float32)
        Bm, Cm = arr((B, S, G, N), scale=0.3), arr((B, S, G, N), scale=0.3)
        compare("ssd_scan",
                jax.jit(lambda *a: ssd_fwd(*a, chunk=256))(x, dt, A, Bm, Cm),
                jax.jit(ref_ssd)(x, dt, A, Bm, Cm))

        imgs = jnp.asarray(rng.integers(0, 256, (8, 256, 256, 3)), jnp.uint8)
        crop = (16, 16, 224, 224)
        mean, std = (0.485, 0.456, 0.406), (0.229, 0.224, 0.225)
        compare("fused_preprocess",
                jax.jit(lambda im: fused_preprocess_fwd(im, crop, mean, std))(
                    imgs),
                jax.jit(lambda im: ref_preprocess(im, crop, mean, std))(imgs))
    # every kernel is read before any check fails
    bad = {n: e for n, e in errs.items() if e > tol[n.split()[0]]}
    check(not bad, f"kernels differ from their references: {bad}")


def four_chip_phase(devices) -> None:
    import jax
    from repro.launch.mesh import make_local_mesh
    from repro.launch.train import Trainer, TrainJob
    check(len(devices) == 4, f"--chips 4 needs 4 devices, found {len(devices)}")
    # unshuffled, so all jobs see the same batches in the same order
    job = TrainJob(arch=ARCH, smoke=False, num_layers=4, steps=2,
                   global_batch=4, seq_len=1024, num_docs=8, shuffle=False,
                   checkpoint_every=2, log_every=1)
    # one step on the batch's first row alone: what a step that saw only one
    # device's quarter of the batch would read
    quarter = dataclasses.replace(job, global_batch=1, steps=1)
    runs = {}
    for name, j, devs in (("1 device", job, devices[:1]),
                          ("4 chips", job, devices),
                          ("quarter batch, 1 device", quarter, devices[:1])):
        trainer = Trainer(j, mesh=make_local_mesh(devices=devs))
        out = trainer.run(restore=False)
        runs[name] = [h["loss"] for h in out["history"]]
        log(f"4-chip phase, {name}: losses {runs[name]}; step seconds "
            f"{[round(h['sec'], 4) for h in out['history']]} (first incl. "
            f"compile)")
        if name == "4 chips":
            leaves = jax.tree_util.tree_leaves(out["state"])
            total = sum(leaf.nbytes for leaf in leaves)
            per_dev = {d: 0 for d in devs}
            for leaf in leaves:
                for shard in leaf.addressable_shards:
                    per_dev[shard.device] += shard.data.nbytes
            shares = [per_dev[d] / total for d in devs]
            log(f"4-chip phase: state {total / 1e9:.3f} GB; share held by "
                f"each device {[round(s, 4) for s in shares]}; peak_bytes_in_use "
                f"{[peak_gb(d) for d in devs]}")
            check(all(abs(s - 0.25) <= 0.01 for s in shares),
                  f"FSDP state is not split in quarters: {shares}")
        del trainer, out
        gc.collect()
    one, four = runs["1 device"], runs["4 chips"]
    # one and four devices differ only in reduction order and fusion: v5e
    # read 6.6e-6 and 3.8e-7 (PERF.md), so 5e-5 is ~8x the larger reading
    tol = 5e-5
    diffs = [abs(a - b) / abs(a) for a, b in zip(one, four)]
    part = abs(runs["quarter batch, 1 device"][0] - one[0]) / abs(one[0])
    log(f"4-chip phase: relative loss differences {diffs} (tolerance {tol}); "
        f"a quarter of the batch differs by {part}")
    check(len(one) == len(four) == 2 and max(diffs) <= tol,
          f"losses disagree: 1 device {one}, 4 chips {four}")
    check(part > 10 * tol, f"the tolerance {tol} would not tell a quarter "
          f"of the batch ({part}) from the whole")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args()

    devices = require_tpu()                                     # (a)
    import jax
    from repro.launch.compile_cache import use_compile_cache
    log(f"devices: {len(devices)}x {devices[0].device_kind}; compile cache "
        f"{use_compile_cache()}")
    phases = ([("4-chip training", lambda: four_chip_phase(devices))]
              if args.chips == 4 else
              [("lake -> train", train_phase), ("serve", serve_phase),
               ("kernels", kernel_phase)])
    for name, phase in phases:
        t0 = time.perf_counter()
        phase()
        gc.collect()
        log(f"phase {name}: passed in {time.perf_counter() - t0:.1f}s")
    dev = jax.devices()[0]
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
