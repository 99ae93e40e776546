#!/usr/bin/env python3
"""Records ``data/v5e_scopes.xplane.pb``, the small chip trace that
``test_scopes.py`` reduces to known answers:

    python3 bench/tests/record_scopes.py <out.xplane.pb>

One jitted program, three calls: the gradient of a ``lax.scan`` over 4
rematerialized layers, each an ``attention`` scope of two 1024 x 1024
matmuls and an ``mlp`` scope of one, under a ``head`` scope's loss.
Between calls the dispatching thread sleeps 5 ms inside a program span
(``gen.token``), so the chip idles there.  The file keeps the device and
host planes, which ``bench/scopes.py`` reads; the others (``/host:metadata``
holds the program's HLO, most of the bytes) are left out.  Needs a TPU.
"""

from __future__ import annotations

import glob
import os
import shutil
import sys
import tempfile
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from bench import scopes, trace  # noqa: E402
from repro.core import telemetry  # noqa: E402

LAYERS, BATCH, WIDTH = 4, 2048, 1024
KEEP_PLANES = ("/device:TPU:0", "/host:CPU")


def layer(h, w):
    with jax.named_scope("attention"):
        h = jnp.tanh(jnp.tanh(h @ w["a"]) @ w["b"])
    with jax.named_scope("mlp"):
        return jnp.tanh(h @ w["m"])


def loss(w, x):
    def body(h, wl):
        return jax.checkpoint(layer)(h, wl), None
    h, _ = jax.lax.scan(body, x, w)
    with jax.named_scope("head"):
        return jnp.mean(jnp.square(h.astype(jnp.float32)))


def _varint(n: int) -> bytes:
    out = bytearray()
    while True:
        out.append((n & 0x7F) | (0x80 if n > 0x7F else 0))
        n >>= 7
        if not n:
            return bytes(out)


def keep_planes(raw: bytes, keep=KEEP_PLANES) -> bytes:
    """The serialized XSpace with only the planes named in ``keep``."""
    out = bytearray()
    for field, plane in scopes.fields(memoryview(raw)):
        if field != 1:                  # errors, warnings, host names
            continue
        name = next((bytes(v).decode() for f, v in scopes.fields(plane)
                     if f == 2), "")
        if name in keep:
            out += _varint(1 << 3 | 2) + _varint(len(plane)) + plane
    return bytes(out)


def main(out: str) -> None:
    if jax.devices()[0].platform != "tpu":
        raise SystemExit("record_scopes: needs a TPU")
    keys = jax.random.split(jax.random.PRNGKey(0), 4)
    w = {k: jax.random.normal(kk, (LAYERS, WIDTH, WIDTH), jnp.bfloat16)
         / WIDTH ** 0.5 for k, kk in zip("abm", keys)}
    x = jax.random.normal(keys[3], (BATCH, WIDTH), jnp.bfloat16)
    grad = jax.jit(jax.grad(loss))
    jax.block_until_ready(grad(w, x))            # compile outside the trace
    logdir = tempfile.mkdtemp(prefix="scopes_trace_")
    jax.profiler.start_trace(logdir)
    with telemetry.tracing(), jax.profiler.TraceAnnotation(trace.WINDOW_SPAN):
        for _ in range(3):
            jax.block_until_ready(grad(w, x))
            with telemetry.span("gen.token"):
                time.sleep(0.005)
    jax.profiler.stop_trace()
    (path,) = glob.glob(os.path.join(logdir, "**", "*.xplane.pb"),
                        recursive=True)
    raw = keep_planes(Path(path).read_bytes())
    Path(out).write_bytes(raw)
    shutil.rmtree(logdir, ignore_errors=True)
    print(f"{out}: {len(raw)} bytes")
    for line in scopes.lines(scopes.reduce_file(out, [0])):
        print(line)


if __name__ == "__main__":
    main(sys.argv[1])
