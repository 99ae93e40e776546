#!/usr/bin/env python3
"""Records ``data/v5e4_fsdp.xplane.pb``, the small four-chip trace that
``test_trace.py`` reduces to the collectives' share of the train step:

    python3 bench/tests/record_fsdp.py <out.xplane.pb>

One jitted ``train_step`` over four chips, FSDP as the program's trainer
shards it: the weights of a ``lax.scan`` over 2 layers (two 1024 x 1024
matmuls each) split along their input width over the chips, each layer's
weights gathered whole where it is used, the batch split by rows, and the
gradient reduced back to the weights' split; three calls inside the
``bench.window`` span.  The compiler makes of it synchronous all-gathers
and all-reduces and an asynchronous all-gather (``async-collective-start``
and ``-done``), as in the program's own step, which has collective-permutes
besides.  The file keeps the four device planes and the host plane, and
each event metadata only its id and its name cut to the opcode
(``%fusion.3 = fusion()``): the full HLO text and the stats are most of
the bytes.  Prints, per chip, the lines of its plane and the collective
operations on the ops line with their opcodes and seconds.  Needs four
TPU chips.
"""

from __future__ import annotations

import glob
import os
import shutil
import sys
import tempfile
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
from jax.profiler import ProfileData  # noqa: E402
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P  # noqa: E402

from bench import scopes, trace  # noqa: E402
from bench.tests.record_scopes import _varint, keep_planes  # noqa: E402

CHIPS, LAYERS, BATCH, WIDTH, CALLS = 4, 2, 2048, 1024, 3
KEEP = tuple(f"/device:TPU:{i}" for i in range(CHIPS)) + ("/host:CPU",)


def _fields(buf):
    """(field number, the field's bytes, its value) of a serialized
    message, with the value a memoryview for a length-delimited field."""
    pos = 0
    while pos < len(buf):
        start = pos
        key, pos = scopes._varint(buf, pos)
        kind = key & 7
        val = None
        if kind == 0:
            _, pos = scopes._varint(buf, pos)
        elif kind == 2:
            n, pos = scopes._varint(buf, pos)
            val, pos = buf[pos:pos + n], pos + n
        else:
            pos += 8 if kind == 1 else 4
        yield key >> 3, bytes(buf[start:pos]), val


def _message(field: int, payload: bytes) -> bytes:
    return _varint(field << 3 | 2) + _varint(len(payload)) + payload


def _short(name: str) -> str:
    op = trace.opcode(name)
    return f"{trace.short_name(name)} = {op}()" if op else name


def short_names(raw: bytes) -> bytes:
    """The serialized XSpace with each event metadata cut to its id and its
    name, an operation's name to ``%name = opcode()`` (XSpace.planes = 1,
    XPlane.event_metadata = 4, a map entry's value = 2, XEventMetadata.id
    = 1 and .name = 2; its display name and stats go)."""
    def metadata(buf) -> bytes:
        out = bytearray()
        for f, whole, val in _fields(buf):
            if f == 1:
                out += whole
            elif f == 2:
                out += _message(2, _short(bytes(val).decode()).encode())
        return bytes(out)

    def rewrite(buf, path) -> bytes:
        if not path:
            return metadata(buf)
        out = bytearray()
        for f, whole, val in _fields(buf):
            out += (_message(f, rewrite(val, path[1:])) if f == path[0]
                    else whole)
        return bytes(out)

    return rewrite(memoryview(raw), (1, 4, 2))


def main(out: str) -> None:
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < CHIPS:
        raise SystemExit(f"record_fsdp: needs {CHIPS} TPU chips")
    mesh = Mesh(np.asarray(devices[:CHIPS]), ("data",))
    split = NamedSharding(mesh, P(None, "data"))
    whole = NamedSharding(mesh, P())
    rows = NamedSharding(mesh, P("data"))

    def loss(w, x):
        def body(h, wl):
            wl = jax.lax.with_sharding_constraint(wl, whole)
            return jnp.tanh(jnp.tanh(h @ wl["a"]) @ wl["b"]), None
        h, _ = jax.lax.scan(body, x, w)
        return jnp.mean(jnp.square(h.astype(jnp.float32)))

    def train_step(w, x):
        value, g = jax.value_and_grad(loss)(w, x)
        return jax.tree.map(lambda p, d: p - 1e-3 * d, w, g), value

    keys = jax.random.split(jax.random.PRNGKey(0), 3)
    w = {k: jax.device_put(jax.random.normal(kk, (LAYERS, WIDTH, WIDTH),
                                             jnp.bfloat16) / WIDTH ** 0.5,
                           split) for k, kk in zip("ab", keys)}
    x = jax.device_put(jax.random.normal(keys[2], (BATCH, WIDTH),
                                         jnp.bfloat16), rows)
    step = jax.jit(train_step, out_shardings=({"a": split, "b": split},
                                              whole), donate_argnums=(0,))
    w, v = step(w, x)                               # compile outside
    jax.block_until_ready(v)
    logdir = tempfile.mkdtemp(prefix="fsdp_trace_")
    jax.profiler.start_trace(logdir)
    with jax.profiler.TraceAnnotation(trace.WINDOW_SPAN):
        for _ in range(CALLS):
            w, v = step(w, x)
            jax.block_until_ready(v)
    jax.profiler.stop_trace()
    (path,) = glob.glob(os.path.join(logdir, "**", "*.xplane.pb"),
                        recursive=True)
    raw = short_names(keep_planes(Path(path).read_bytes(), KEEP))
    Path(out).write_bytes(raw)
    shutil.rmtree(logdir, ignore_errors=True)
    print(f"{out}: {len(raw)} bytes")
    profile = ProfileData.from_file(out)
    for plane in profile.planes:
        if not plane.name.startswith(trace.DEVICE_PREFIX):
            continue
        print(plane.name, [(ln.name, len(list(ln.events)))
                           for ln in plane.lines])
        for ln in plane.lines:
            for e in ln.events:
                if trace.is_collective(e.name):
                    print(f"  {ln.name}: {trace.short_name(e.name)} "
                          f"[{trace.opcode(e.name)}] {e.duration_ns} ns")
    got = trace.reduce(profile, list(range(CHIPS)))
    print("per chip (program seconds, collective seconds):", got.chips)


if __name__ == "__main__":
    main(sys.argv[1])
