"""``bench/scopes.py``: device time by model scope and idle gaps by program
span, on synthetic traces with known answers and on a small trace recorded
on a v5e (``record_scopes.py``); and the readers of the metrics that read
the program's spans and counters."""

from __future__ import annotations

import importlib.util
import time
from pathlib import Path

import pytest
from jax.profiler import ProfileData

from bench import scopes, trace
from bench.harness import Record
from bench.tests.test_trace import HOST, xspace
from repro.core import telemetry

DATA = Path(__file__).resolve().parent / "data"
METRICS = Path(__file__).resolve().parents[1] / "metrics"


# ------------------------------------------------------------ name stacks
@pytest.mark.parametrize("stack,want", [
    ("jit(step)/jvp()/while/body/closed_call/attention/dot_general:",
     "fwd attention"),
    ("jit(step)/transpose(jvp())/while/body/closed_call/checkpoint/"
     "rematted_computation/attention/while/body/dynamic_update_slice:",
     "bwd attention"),
    ("jit(step)/jvp(head)/mul", "fwd head"),
    ("jit(step)/transpose(jvp(head))/mul", "bwd head"),
    ("jit(decode_step)/while/body/closed_call/attention/kv_write/"
     "dynamic_update_slice:", "fwd kv_write"),
    ("jit(decode_step)/while/body/dynamic_slice:", "fwd (no scope)"),
    ("jit(step)/transpose(jvp())/while/body/dynamic_update_slice",
     "bwd (no scope)"),
    ("jit(step)/optimizer/mul:", "fwd optimizer"),
    ("jit(step)/transpose(jvp(norm/x))/add_any", "bwd norm"),
    ("jit(attention_like)/mul", "fwd (no scope)"),
    ("", "(unknown)"),
    (None, "(unknown)"),
])
def test_an_op_takes_its_innermost_scope_and_direction(stack, want):
    assert scopes.scope_of(stack) == want


def _metadata_stats_space() -> bytes:
    """A serialized XSpace whose device plane's event metadata carry
    ``tf_op`` as a string and as a reference, and ``program_id``."""
    stats = ('stat_metadata { key: 1 value { id: 1 name: "tf_op" } } '
             'stat_metadata { key: 2 value { id: 2 name: "program_id" } } '
             'stat_metadata { key: 3 value { id: 3 name: '
             '"jit(f)/mlp/dot_general:" } } '
             'stat_metadata { key: 4 value { id: 4 name: "flops" } }')
    events = (
        'event_metadata { key: 1 value { id: 1 name: "%fusion.1 = f32[2]" '
        'stats { metadata_id: 1 str_value: "jit(f)/attention/exp:" } '
        'stats { metadata_id: 2 uint64_value: 77 } } } '
        'event_metadata { key: 2 value { id: 2 name: "%fusion.2 = f32[2]" '
        'stats { metadata_id: 4 int64_value: 9 } '
        'stats { metadata_id: 1 ref_value: 3 } } } '
        'event_metadata { key: 3 value { id: 3 name: "%copy.3 = f32[2]" '
        'stats { metadata_id: 2 uint64_value: 77 } } }')
    text = (f'planes {{ id: 1 name: "/host:CPU" {events} {stats} }} '
            f'planes {{ id: 2 name: "/device:TPU:0" {events} {stats} }}')
    return ProfileData.text_proto_to_serialized_xspace(text)


def test_name_stacks_are_read_from_event_metadata_stats():
    got = scopes.name_stacks(_metadata_stats_space())
    assert list(got) == ["/device:TPU:0"]          # device planes only
    assert got["/device:TPU:0"] == {
        (77, "%fusion.1 = f32[2]"): "jit(f)/attention/exp:",
        (0, "%fusion.2 = f32[2]"): "jit(f)/mlp/dot_general:",
    }


# -------------------------------------------------------- the scope table
TPU_OPS = {"XLA Ops": [("%fusion.a = f32[]", 0, 20), ("%fusion.b = f32[]", 20, 5),
                       ("%copy.c = f32[]", 25, 5), ("%while.w = f32[]", 50, 30),
                       ("%fusion.d = f32[]", 55, 10),
                       ("%fusion.e = f32[]", 90, 10)],
           "XLA Modules": [("jit_step(5)", 0, 30), ("jit_step(5)", 50, 30),
                           ("jit_other(6)", 90, 10)]}
STACKS = {"/device:TPU:0": {
    (5, "%fusion.a = f32[]"): "jit(step)/attention/dot_general:",
    (5, "%fusion.b = f32[]"): "jit(step)/transpose(jvp(mlp))/dot_general:",
    (5, "%fusion.d = f32[]"): "jit(step)/while/body/dynamic_slice:",
    (6, "%fusion.e = f32[]"): "jit(other)/mul:",
}}


def _profile():
    return xspace({"/host:CPU": HOST, "/device:TPU:0": TPU_OPS})


def _summary(stacks=STACKS):
    return scopes.reduce(_profile(), stacks)


def test_scope_table_splits_each_program_by_scope_and_direction():
    got = _summary()
    assert got.scopes["jit_step"] == {
        "fwd attention": pytest.approx(0.02),
        "bwd mlp": pytest.approx(0.005),
        # the copy has no name stack; the while keeps its time outside the
        # op nested in it, which sits under no scope
        "(unknown)": pytest.approx(0.005 + 0.02),
        "fwd (no scope)": pytest.approx(0.01),
    }
    assert got.scopes["jit_other"] == {"fwd (no scope)": pytest.approx(0.01)}
    # the table adds up to the operations' self time of the benchmark's
    # own reduction, and its programs' time to that reduction's modules
    total = sum(s for t in got.scopes.values() for s in t.values())
    plain = trace.reduce(_profile())
    assert total == pytest.approx(sum(s for _, s in plain.top_ops))
    assert got.modules == pytest.approx(plain.modules)
    # jit_step runs 60 ms: 10 under no scope, 20 of attention
    assert got.scope_share("jit_step", ("(no scope)", "kv_write")) == \
        pytest.approx(10 / 60 * 100)
    assert got.scope_share("jit_step", ("attention",)) == \
        pytest.approx(20 / 60 * 100)
    # no model scope anywhere in jit_other, no such program: nothing to read
    assert got.scope_share("jit_other", ("(no scope)",)) is None
    assert got.scope_share("jit_nothing", ("(no scope)",)) is None


def test_without_name_stacks_every_op_is_unknown():
    got = _summary(stacks={})
    assert got.scopes == {"jit_step": {"(unknown)": pytest.approx(0.06)},
                          "jit_other": {"(unknown)": pytest.approx(0.01)}}
    assert got.scope_share("jit_step", ("attention",)) is None


# ------------------------------------------------- idle gaps by program span
def test_idle_gaps_are_labelled_by_program_spans_of_the_dispatching_thread():
    host = {
        "python": [("bench.window", 0, 100)],
        # the dispatching thread: gen.generate holds gen.absorb and tokens
        "main": [("PjitFunction(step)", 1, 0.1), ("PjitFunction(step)", 41, 0.1),
                 ("PjitFunction(step)", 71, 0.1),
                 ("gen.generate", 0, 88), ("gen.absorb", 0, 40),
                 ("gen.token", 60, 4), ("gen.token", 66, 4),
                 ("bench.generate", 0, 95), ("$api.py:3097 block_until_ready",
                                             60, 1)],
        # other threads' spans never label a gap: the runtime's enqueue
        # thread, a feeder that calls one program
        "queue": [("DoEnqueueProgram", 1, 0.1), ("DoEnqueueProgram", 41, 0.1),
                  ("DoEnqueueProgram", 71, 0.1), ("gen.token", 0, 100)],
        "feeder": [("feed.h2d", 0, 100), ("PjitFunction(put)", 5, 0.1)],
    }
    dev = {"XLA Ops": [("a", 0, 30), ("b", 40, 20), ("c", 70, 10)]}
    profile = xspace({"/host:CPU": host, "/device:TPU:0": dev})
    got = scopes.reduce(profile, {})
    # gaps 30-40 (absorb), 60-70 (middle 65: the first token has ended,
    # the second not begun), 80-100 (middle 90: generate has ended)
    assert dict(got.idle_by_program_span) == {
        "gen.absorb": pytest.approx(0.01),
        "gen.generate": pytest.approx(0.01),
        "outside program spans": pytest.approx(0.02)}
    # the benchmark's bench.* labelling of the same gaps
    assert dict(trace.reduce(profile).idle_gaps) == {
        "bench.generate": pytest.approx(0.04)}


def test_innermost_span_of_nested_spans():
    spans = [("outer", 0, 10), ("a", 1, 2), ("b", 3, 6), ("c", 4, 5)]
    got = scopes.innermost(spans, [0.5, 1.5, 2.5, 4.5, 5.5, 7, 11], "none")
    assert got == ["outer", "a", "outer", "c", "b", "outer", "none"]


# ------------------------------------------------- a trace recorded on a v5e
def test_scopes_of_a_trace_recorded_on_a_v5e():
    """``record_scopes.py``: the gradient of a scan over 4 rematerialized
    layers, each an ``attention`` scope of two matmuls and an ``mlp``
    scope of one, three calls with 5 ms sleeps in ``gen.token`` between."""
    path = DATA / "v5e_scopes.xplane.pb"
    assert path.stat().st_size < 100_000
    got = scopes.reduce_file(str(path), [0])
    (prog,) = [p for p in got.scopes if p.startswith("jit_loss")]
    table = got.scopes[prog]
    seconds, calls = got.modules[prog]
    assert calls == 3
    for key in ("fwd attention", "fwd mlp", "bwd attention", "bwd mlp"):
        assert table.get(key, 0) > 0, key
    # nearly all of the program's time has a name stack
    assert table.get("(unknown)", 0) <= 0.05 * seconds
    assert sum(table.values()) == pytest.approx(seconds, rel=0.05)
    # two matmuls against one, forward; the backward's two products per
    # matmul and the recomputed forward weigh the same way
    assert 1.3 < table["fwd attention"] / table["fwd mlp"] < 4
    assert 1.3 < table["bwd attention"] / table["bwd mlp"] < 4
    assert table["bwd attention"] > table["fwd attention"]
    share = got.scope_share(prog, ("attention", "mlp"))
    assert 50 < share <= 100
    # the chip idles in the sleeps, inside gen.token on the calling thread
    idle = dict(got.idle_by_program_span)
    assert idle["gen.token"] >= 3 * 0.004
    assert idle["gen.token"] >= 0.9 * sum(idle.values())


def test_the_command_prints_the_table_and_the_idle_gaps(capsys):
    assert scopes.main([str(DATA / "v5e_scopes.xplane.pb")]) == 0
    out = capsys.readouterr().out.splitlines()
    assert out[0].startswith("scopes of jit_loss")
    assert "bwd attention" in out[0]
    assert out[-1].startswith("idle gaps by program span: gen.token ")


# ------------------------------------------------------------- the readers
def _reader(name):
    spec = importlib.util.spec_from_file_location(name, METRICS / f"{name}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def _record(layer):
    return Record(metrics={}, attempted=1, failed=0, memory_peak_bytes=0,
                  checks={}, layer=layer)


TRAIN = {"kind": "train", "steps": 10}
SERVE = {"kind": "serve", "rounds": 3}


@pytest.mark.parametrize("name,span", [
    ("pack_ms_per_step", "feed.pack"),
    ("h2d_ms_per_step", "feed.h2d"),
    ("feed_wait_ms_per_step", "feed.wait"),
])
def test_a_feed_reader_reads_the_window_spans_the_tracer_kept(name, span):
    with telemetry.tracing():
        with telemetry.span("feed.unrelated"):
            pass
        for _ in range(3):
            with telemetry.span(span):
                time.sleep(0.001)
    # the window has closed: the spans stay until the next window opens
    spans = telemetry.get_tracer().find(span)
    assert len(spans) == 3
    want = sum(e.dur for e in spans) / TRAIN["steps"] * 1e3
    assert want >= 0.3
    assert _reader(name)(_record(TRAIN)) == pytest.approx(want)
    assert _reader(name)(_record(SERVE)) is None


def test_decode_calls_per_round_reads_the_program_counter(monkeypatch):
    monkeypatch.setattr(telemetry, "_REGISTRY", telemetry.MetricsRegistry())
    telemetry.registry().counter("gen.decode_calls").inc(960)
    read = _reader("decode_calls_per_round")
    assert read(_record(SERVE)) == pytest.approx(320.0)
    assert read(_record(TRAIN)) is None


@pytest.mark.parametrize("name", [
    "pack_ms_per_step", "h2d_ms_per_step", "feed_wait_ms_per_step",
    "decode_calls_per_round"])
def test_each_reader_reads_nothing_where_the_run_holds_nothing(
        name, monkeypatch):
    # a program without the spans or the counter (its window traced)
    monkeypatch.setattr(telemetry, "_REGISTRY", telemetry.MetricsRegistry())
    with telemetry.tracing():
        with telemetry.span("loader.stall"):
            pass
    read = _reader(name)
    for layer in (TRAIN, SERVE, {"kind": "train", "steps": 0},
                  {"kind": "serve", "rounds": 0}):
        assert read(_record(layer)) is None
