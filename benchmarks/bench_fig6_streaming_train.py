"""Fig 6 reproduction: identical training, three data paths —

  (a) local            — data already on the machine
  (b) AWS File Mode    — one synchronous GET per sample from object storage
  (c) Fast File Mode   — threaded per-sample GETs (starts fast, no chunking)
  (d) Deep Lake stream — chunked columnar + parallel fetch + prefetch overlap

Workload mirrors the paper's: an image model (MLP classifier stands in for
the conv net; per-step compute ~tens of ms like a real accelerator step)
over 64x64 images.  Remote timing uses the SimulatedS3 cost model
(cross-region: 30ms TTFB, 50MB/s per connection); sim seconds are reported
at full scale.  Paper's claim to match: (d) ~= (a); (b) is several x slower.
"""

from __future__ import annotations

import concurrent.futures as cf
import time
from typing import List

import jax
import jax.numpy as jnp
import numpy as np

import repro.core as dl

from .common import (Timer, build_lake, file_store_read, file_store_write,
                     make_images, row)

N_IMAGES = 600
BATCH = 32
STEPS = 36
LAT, BW = 0.030, 50e6     # cross-region object store
TIME_SCALE = 0.0          # pure accounting; wall = compute, sim = IO

#: steady-state stall budget for the deep-lake section (smoke gate):
#: seconds the simulated per-connection IO may exceed compute — the scan
#: pipeline's cross-unit prefetch must keep the training step the
#: bottleneck, so the stall stays ~0 (§4.5, Fig 6's "(d) ~= (a)" claim)
STALL_BUDGET_S = 1.0


#: regression slack over the recorded baseline + an absolute noise floor
#: (compute wall time jitters between machines; stall ~0 makes a bare
#: multiplicative bound meaninglessly tight)
STALL_REGRESSION_SLACK = 1.25
STALL_NOISE_FLOOR_S = 0.25


def _baseline_stall(smoke: bool) -> float:
    """Newest recorded stall_seconds of a run with the SAME workload size
    (smoke vs full — their stalls are not comparable); inf when the
    history has no matching datapoint."""
    import json

    from . import io_report
    try:
        with open(io_report.PATH) as f:
            hist = json.load(f)["benches"]["fig6_streaming_train"]
        for entry in reversed(hist):
            stall = entry.get("stall", {})
            if stall.get("smoke") == int(smoke):
                return float(stall["stall_seconds"])
    except (OSError, KeyError, ValueError, TypeError):
        pass
    return float("inf")


def _train_step_fn():
    key = jax.random.PRNGKey(0)
    d, h, classes = 64 * 64 * 3, 1024, 10
    w1 = jax.random.normal(key, (d, h), jnp.float32) * 0.01
    w2 = jax.random.normal(key, (h, classes), jnp.float32) * 0.01
    params = {"w1": w1, "w2": w2}

    @jax.jit
    def step(params, x, y):
        def loss_fn(p):
            z = jnp.tanh(x.reshape(x.shape[0], -1) @ p["w1"]) @ p["w2"]
            lse = jax.nn.logsumexp(z, -1)
            return (lse - jnp.take_along_axis(z, y[:, None], 1)[:, 0]).mean()

        loss, g = jax.value_and_grad(loss_fn)(params)
        return {k: params[k] - 0.01 * g[k] for k in params}, loss

    return params, step


def _consume(params, step, batch_iter, steps=STEPS):
    compute = 0.0
    it = iter(batch_iter)
    for _ in range(steps):
        x, y = next(it)
        t0 = time.perf_counter()
        params, loss = step(params, jnp.asarray(x, jnp.float32) / 255.0,
                            jnp.asarray(y))
        jax.block_until_ready(loss)
        compute += time.perf_counter() - t0
    return compute


#: tracing-overhead gate (smoke): the traced deep-lake run's simulated IO
#: seconds must match the untraced run within this relative fraction (plus
#: an absolute floor — smoke sims are fractions of a second)
TRACE_OVERHEAD_TOL = 0.05
TRACE_OVERHEAD_FLOOR_S = 0.05


def _deeplake_run(images, steps: int):
    """One deep-lake streaming training run on a fresh simulated store.

    Returns ``(compute_seconds, provider, loader)`` — the provider's stats
    and the loader's stats are both still live snapshots of this run.
    """
    s3b = dl.SimulatedS3Provider(time_scale=TIME_SCALE, latency_s=LAT,
                                 bandwidth_bps=BW)
    build_lake(images, codec="quant8", storage=s3b, chunk_mb=2)
    s3b.reset_stats()
    dsr = dl.Dataset(dl.chain(dl.MemoryProvider(), s3b,
                              capacity_bytes=64 << 20))
    loader = dsr.dataloader(batch_size=BATCH, shuffle=True, num_workers=8,
                            drop_last=True)

    def lake_batches():
        while True:
            for b in loader:
                yield b["images"], b["labels"]

    params, step = _train_step_fn()
    compute = _consume(params, step, lake_batches(), steps=steps)
    return compute, s3b, loader


def main(smoke: bool = False) -> List[str]:
    n_images = 240 if smoke else N_IMAGES
    steps = 12 if smoke else STEPS
    lines = []
    images = make_images(n_images, (64, 64))
    labels = [i % 10 for i in range(n_images)]
    rng = np.random.default_rng(0)
    order = lambda: rng.permutation(n_images)

    # ---------------- (a) local
    params, step = _train_step_fn()
    imgs_arr = np.stack(images)
    labs_arr = np.asarray(labels)

    def local_batches():
        while True:
            idx = order()
            for i in range(0, n_images - BATCH, BATCH):
                sel = idx[i:i + BATCH]
                yield imgs_arr[sel], labs_arr[sel]

    compute = _consume(params, step, local_batches(), steps=steps)
    local_wall = compute
    lines.append(row("fig6_local", local_wall / steps * 1e6, "baseline"))

    # ---------------- (b) file mode: sequential GET per sample
    s3 = dl.SimulatedS3Provider(time_scale=TIME_SCALE, latency_s=LAT,
                                bandwidth_bps=BW)
    file_store_write(s3.base, images, labels)

    def filemode_batches():
        while True:
            idx = order()
            for i in range(0, n_images - BATCH, BATCH):
                sel = idx[i:i + BATCH]
                xs = np.stack([file_store_read(s3, int(j)) for j in sel])
                yield xs, labs_arr[sel]

    from . import io_report

    s3.reset_stats()
    params, step = _train_step_fn()
    compute = _consume(params, step, filemode_batches(), steps=steps)
    wall_b = compute + s3.stats["sim_seconds"]   # sequential: IO adds up
    # snapshot BEFORE the fast-file section resets the shared provider
    filemode_stats = io_report.provider_snapshot(s3)
    lines.append(row("fig6_s3_filemode", wall_b / steps * 1e6,
                     f"slowdown{wall_b / local_wall:.1f}x"))

    # ---------------- (c) fast file mode: threaded GETs, still per-sample
    s3.reset_stats()
    pool = cf.ThreadPoolExecutor(8)

    def fastfile_batches():
        while True:
            idx = order()
            for i in range(0, n_images - BATCH, BATCH):
                sel = idx[i:i + BATCH]
                xs = np.stack(list(pool.map(
                    lambda j: file_store_read(s3, int(j)), sel)))
                yield xs, labs_arr[sel]

    params, step = _train_step_fn()
    compute = _consume(params, step, fastfile_batches(), steps=steps)
    wall_c = compute + s3.stats["sim_seconds"] / 8   # 8-way overlapped IO
    # snapshot too (earlier revisions dropped this section's stats)
    fastfile_stats = io_report.provider_snapshot(s3)
    lines.append(row("fig6_s3_fastfile", wall_c / steps * 1e6,
                     f"slowdown{wall_c / local_wall:.1f}x"))

    # ---------------- (d) deep lake streaming
    compute, s3b, loader = _deeplake_run(images, steps)
    # chunked fetch overlaps compute through the prefetch queue: the critical
    # path is max(compute, per-connection IO), plus residual handoff
    wall_d = max(compute, s3b.stats["sim_seconds"] / 8) \
        + 0.1 * min(compute, s3b.stats["sim_seconds"] / 8)
    lines.append(row("fig6_deeplake_stream", wall_d / steps * 1e6,
                     f"slowdown{wall_d / local_wall:.2f}x_"
                     f"reqs{s3b.stats['requests']}_"
                     f"coal{s3b.stats['coalesced_requests']}_"
                     f"down{s3b.stats['bytes_down']}_"
                     f"sim{s3b.stats['sim_seconds']:.3f}"))

    # steady-state stall: seconds the per-connection simulated IO exceeds
    # compute — with the pipeline's cross-unit prefetch this must stay ~0
    # (training step remains the bottleneck).  The smoke gate enforces the
    # absolute budget AND no regression vs. the recorded same-size
    # baseline (slack + noise floor); it runs BEFORE record() so a failing
    # stall can never become the next run's baseline (no self-ratchet).
    stall_d = max(0.0, s3b.stats["sim_seconds"] / 8 - compute)
    baseline = _baseline_stall(smoke)
    lake_stats = io_report.provider_snapshot(s3b)
    lines.append(row("fig6_stall", stall_d * 1e6,
                     f"budget{STALL_BUDGET_S:.2f}s_prefhits"
                     f"{lake_stats.get('engine_prefetch_hits', 0)}_wasted"
                     f"{lake_stats.get('engine_prefetch_wasted_bytes', 0)}"))
    if smoke:
        limit = STALL_BUDGET_S
        if baseline != float("inf"):
            limit = min(limit, max(STALL_REGRESSION_SLACK * baseline,
                                   STALL_NOISE_FLOOR_S))
        assert stall_d <= limit, (
            f"steady-state stall {stall_d:.3f}s exceeds gate {limit:.3f}s "
            f"(budget {STALL_BUDGET_S}s, baseline {baseline})")

    # stall attribution: decompose the simulated stall into exhaustive,
    # non-overlapping causes from the provider's per-cause sim partition
    # (demand-fetch wait, retry/hedge/fault overhead, decode, prefetch
    # eviction).  The partition invariant and the causes-sum-to-total
    # invariant are both gated here in smoke AND re-checked structurally
    # by `io_report --validate`.
    from repro.core import telemetry

    sim_part = telemetry.sim_cause_partition(s3b.stats)
    part_sum = sum(sim_part.values())
    stall_attr = telemetry.attribute_stall(
        sim_part, compute, parallelism=8,
        decode_s=loader.stats.decode_seconds / 8)
    lines.append(row(
        "fig6_stall_attribution", stall_attr["total_s"] * 1e6,
        "_".join(f"{k[:-2]}{stall_attr[k]:.3f}"
                 for k in telemetry.STALL_CAUSE_KEYS)))
    if smoke:
        assert abs(part_sum - s3b.stats["sim_seconds"]) <= \
            0.01 * s3b.stats["sim_seconds"] + 1e-6, (
            f"sim cause partition {part_sum:.6f}s != "
            f"sim_seconds {s3b.stats['sim_seconds']:.6f}s")
        causes = sum(v for k, v in stall_attr.items() if k != "total_s")
        assert abs(causes - stall_attr["total_s"]) <= \
            0.05 * abs(stall_attr["total_s"]) + 1e-6, (
            f"stall causes sum {causes:.6f}s != total "
            f"{stall_attr['total_s']:.6f}s")

    # traced re-run: the tracing layer must not perturb the measured IO —
    # the traced run's simulated seconds must match the untraced run within
    # 5% (deterministic cost model; only the span bookkeeping differs).
    # Runs in smoke (gate).
    if smoke:
        with telemetry.tracing() as tr:
            compute_t, s3t, loader_t = _deeplake_run(images, steps)
        sim_u = s3b.stats["sim_seconds"]
        sim_t = s3t.stats["sim_seconds"]
        lines.append(row("fig6_trace_overhead", abs(sim_t - sim_u) * 1e6,
                         f"untraced{sim_u:.3f}s_traced{sim_t:.3f}s_"
                         f"spans{len(tr.events())}"))
        assert abs(sim_t - sim_u) <= max(TRACE_OVERHEAD_TOL * sim_u,
                                         TRACE_OVERHEAD_FLOOR_S), (
            f"traced sim {sim_t:.3f}s deviates from untraced "
            f"{sim_u:.3f}s beyond {TRACE_OVERHEAD_TOL:.0%}")
        assert tr.count("scan.group") > 0, \
            "traced run produced no scan.group spans"

    io_report.record("fig6_streaming_train", {
        "stall_attribution": stall_attr,
        "s3_filemode": filemode_stats,
        "s3_fastfile": fastfile_stats,
        "deeplake_stream": lake_stats,
        "walls": {"local_s": local_wall, "filemode_s": wall_b,
                  "fastfile_s": wall_c, "deeplake_s": wall_d},
        "stall": {"stall_seconds": stall_d, "budget_s": STALL_BUDGET_S,
                  "smoke": int(smoke)},
        "loader": {"io_requests": loader.stats.io_requests,
                   "bytes_fetched": loader.stats.bytes_fetched,
                   "samples": loader.stats.samples,
                   "wait_seconds": loader.stats.wait_seconds,
                   # consumer-side wait decomposition (sums to wait_seconds)
                   **{f"stall_{k}_s": v
                      for k, v in loader.stats.stall_by_cause.items()}},
    })
    return lines


if __name__ == "__main__":
    import sys

    print("\n".join(main(smoke="--smoke" in sys.argv[1:])))
