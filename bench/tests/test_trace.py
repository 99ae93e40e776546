"""The trace reduction on small traces with known answers."""

from __future__ import annotations

import re
from pathlib import Path

import pytest
from jax.profiler import ProfileData

from bench import trace

DATA = Path(__file__).resolve().parent / "data"

MS = 1_000_000_000           # picoseconds in a millisecond


def xspace(planes):
    """An XSpace text proto from {plane: {line: [(name, start_ms, dur_ms[,
    {int stat: value}])]}}."""
    out = []
    for pid, (pname, lines) in enumerate(planes.items(), 1):
        names = sorted({ev[0] for evs in lines.values() for ev in evs})
        meta = {n: i for i, n in enumerate(names, 1)}
        stat_names = sorted({k for evs in lines.values() for ev in evs
                             if len(ev) > 3 for k in ev[3]})
        smeta = {n: i for i, n in enumerate(stat_names, 100)}
        body = []
        for lid, (lname, evs) in enumerate(lines.items(), 1):
            ev = []
            for n, a, d, *st in evs:
                stats = " ".join(
                    f"stats {{ metadata_id: {smeta[k]} int64_value: {v} }}"
                    for k, v in (st[0] if st else {}).items())
                ev.append(f"events {{ metadata_id: {meta[n]} offset_ps: "
                          f"{int(a * MS)} duration_ps: {int(d * MS)} "
                          f"{stats} }}")
            body.append(f'lines {{ id: {lid} name: "{lname}" '
                        f'timestamp_ns: 0 {" ".join(ev)} }}')
        md = " ".join(f'event_metadata {{ key: {i} value {{ id: {i} '
                      f'name: "{n}" }} }}' for n, i in meta.items())
        md += " " + " ".join(f'stat_metadata {{ key: {i} value {{ id: {i} '
                             f'name: "{n}" }} }}' for n, i in smeta.items())
        out.append(f'planes {{ id: {pid} name: "{pname}" {" ".join(body)} '
                   f'{md} }}')
    return ProfileData.from_text_proto("\n".join(out))


HOST = {"python": [("bench.window", 0, 100), ("bench.step", 0, 30),
                   ("bench.input_wait", 30, 20), ("bench.step", 50, 30),
                   ("bench.loss_fetch", 80, 20), ("other", 0, 100)]}
TPU0 = {"XLA Ops": [("fusion.a", 0, 20), ("fusion.b", 10, 20),
                    ("dot", 50, 30), ("late", 90, 20), ("after", 150, 10)],
        "XLA Modules": [("jit_step(3)", 0, 30), ("jit_step(3)", 50, 30),
                        ("jit_late(4)", 90, 20)]}


def test_busy_idle_ops_and_gaps_of_one_chip():
    got = trace.reduce(xspace({"/host:CPU": HOST, "/device:TPU:0": TPU0}))
    assert got.window_s == pytest.approx(0.1)
    # 0-30, 50-80 and the part of 'late' inside the window, 90-100
    assert got.busy_s == pytest.approx(0.07)
    assert got.idle_share == pytest.approx(0.3)
    assert got.top_ops[0] == ("dot", pytest.approx(0.03))
    assert dict(got.top_ops)["late"] == pytest.approx(0.01)
    assert "after" not in dict(got.top_ops)
    assert got.module("jit_step") == (pytest.approx(0.06), pytest.approx(2))
    assert dict(got.idle_gaps) == {"bench.input_wait": pytest.approx(0.02),
                                   "bench.loss_fetch": pytest.approx(0.01)}
    bd = got.breakdown()
    assert bd["device_ops"][0][0] == "dot" and len(bd["idle_gaps"]) == 2


def test_chips_are_averaged():
    tpu1 = {"XLA Ops": [("all-gather", 0, 100)]}
    got = trace.reduce(xspace({"/host:CPU": HOST, "/device:TPU:0": TPU0,
                               "/device:TPU:1": tpu1}), device_ids=[0, 1])
    assert got.busy_s == pytest.approx((0.07 + 0.1) / 2)
    only0 = trace.reduce(xspace({"/host:CPU": HOST, "/device:TPU:0": TPU0,
                                 "/device:TPU:1": tpu1}), device_ids=[0])
    assert only0.busy_s == pytest.approx(0.07)


def test_a_trace_without_its_window_or_chip_is_refused():
    with pytest.raises(ValueError, match="bench.window"):
        trace.reduce(xspace({"/host:CPU": {"python": [("x", 0, 1)]},
                             "/device:TPU:0": TPU0}))
    with pytest.raises(ValueError, match="device planes"):
        trace.reduce(xspace({"/host:CPU": HOST}))


def test_interval_helpers():
    assert trace.union([(3, 4), (0, 2), (1, 3)]) == [(0, 4)]
    assert trace.gaps([(1, 2), (3, 4)], 0, 5) == [(0, 1), (2, 3), (4, 5)]


def test_nested_operations_keep_their_self_time():
    got = trace.self_times([("while", 0, 10), ("a", 1, 3), ("b", 4, 5),
                            ("c", 12, 13)])
    assert got == {"while": 7, "a": 2, "b": 1, "c": 1}


def test_device_times_move_onto_the_host_clock():
    """A program that seems to start 2 ms before the host enqueued it moves
    2 ms later, and the idle gap before it is labelled by the span open
    then; one that starts after its enqueue moves nothing."""
    def reduce(enqueue_ms):
        host = {"python": [("bench.window", 0, 100),
                           ("bench.input_wait", 0, 39),
                           ("bench.step", 39, 61)],
                "main": [("DoEnqueueProgram", enqueue_ms, 0.1, {"run_id": 8})]}
        dev = {"XLA Ops": [("%fusion.1 = bf16[2] fusion()", 38, 20)],
               "XLA Modules": [("jit_step(1)", 38, 20, {"run_id": 8})]}
        return trace.reduce(xspace({"/host:CPU": host,
                                    "/device:TPU:0": dev}))

    late = reduce(40)
    assert late.module("jit_step")[0] == pytest.approx(0.02)
    # the program ran 40-60 ms on the host clock: idle 0-40 in the wait
    assert dict(late.idle_gaps)["bench.input_wait"] == pytest.approx(0.04)
    assert late.top_ops == [("%fusion.1", pytest.approx(0.02))]
    on_time = dict(reduce(37).idle_gaps)
    assert on_time["bench.input_wait"] == pytest.approx(0.038)


def test_a_trace_recorded_on_a_v5e():
    """Three rounds of a matmul program and a loop program on one v5e
    chip, each round: ``bench.step`` dispatches both, ``bench.loss_fetch``
    waits for them, ``bench.input_wait`` sleeps 5 ms."""
    got = trace.reduce(ProfileData.from_file(
        str(DATA / "v5e_small.xplane.pb")), device_ids=[0])
    seconds, calls = got.module("jit__lambda")
    assert calls == 6
    # the programs are all the chip ran, and the sleeps are its idle time
    assert got.busy_s == pytest.approx(seconds, rel=1e-3)
    assert sum(s for _, s in got.idle_gaps) == pytest.approx(
        got.window_s - got.busy_s)
    assert got.idle_gaps[0][0] == "bench.input_wait"
    assert got.idle_gaps[0][1] >= 0.015
    assert all(name.startswith("%") for name, _ in got.top_ops)
    assert sum(s for _, s in got.top_ops) == pytest.approx(got.busy_s,
                                                           rel=1e-3)


def _hlo(name, opcode, shape="f32[8]{0}"):
    return f"%{name} = {shape} {opcode}({shape} %x), metadata={{}}"


def test_an_opcode_is_read_after_the_shape():
    tuple_shape = ("(bf16[2048,2048]{1,0:T(8,128)(2,1)S(1)}, "
                   "u32[]{:S(2)})")
    assert trace.opcode(_hlo("copy-start", "copy-start", tuple_shape)) \
        == "copy-start"
    assert trace.opcode(_hlo("fusion.3", "fusion")) == "fusion"
    assert trace.opcode("jit_step") == ""
    # a collective by its opcode or its own name, not by its operands'
    assert trace.is_collective(_hlo("ag.1", "all-gather-start"))
    assert trace.is_collective(_hlo("all-reduce-fusion.2", "fusion"))
    assert trace.is_collective(_hlo("async-collective-done", "fusion"))
    assert not trace.is_collective(
        "%copy.4 = f32[8]{0} copy(f32[8]{0} %all-gather-done.1)")


COLL_TPU0 = {
    "XLA Ops": [
        (_hlo("while.1", "while", "(s32[])"), 0, 60),
        (_hlo("all-gather-start.1", "all-gather-start"), 0, 5),
        (_hlo("fusion.2", "fusion"), 5, 30),
        (_hlo("all-gather-done.1", "all-gather-done"), 35, 5),
        (_hlo("reduce-scatter.3", "reduce-scatter"), 40, 10),
        (_hlo("all-reduce-fusion.1", "fusion"), 70, 10),
        ("%copy.4 = f32[8]{0} copy(f32[8]{0} %all-gather-done.1)", 80, 10),
        (_hlo("fusion.5", "fusion"), 90, 10),
        (_hlo("collective-permute.5", "collective-permute"), 150, 10)],
    "XLA Modules": [("jit_train_step(1)", 0, 60), ("jit_train_step(1)", 70, 20),
                    ("jit_other(2)", 90, 10), ("jit_train_step(1)", 150, 10)]}
COLL_TPU1 = {
    "XLA Ops": [(_hlo("all-to-all.1", "all-to-all"), 10, 20),
                (_hlo("fusion.9", "fusion"), 30, 20)],
    "XLA Modules": [("jit_train_step(1)", 0, 50)]}


def test_collectives_are_summed_per_chip_and_program():
    """Chip 0: the step runs 0-60 and 70-90 ms; inside it the chip spends
    5 + 5 + 10 + 10 ms in collectives (the loop that holds three of them
    keeps only its own time, a copy of a gathered array is no collective,
    and one after the window does not count).  Chip 1: 20 of 50 ms."""
    got = trace.reduce(xspace({"/host:CPU": HOST, "/device:TPU:0": COLL_TPU0,
                               "/device:TPU:1": COLL_TPU1}), [0, 1])
    assert got.chips[0]["jit_train_step"] == (pytest.approx(0.08),
                                              pytest.approx(0.03))
    assert got.chips[0]["jit_other"] == (pytest.approx(0.01), 0.0)
    assert got.chips[1] == {"jit_train_step": (pytest.approx(0.05),
                                               pytest.approx(0.02))}
    from bench import harness
    read = harness.load_module(harness.ROOT / "bench" / "metrics"
                               / "fsdp_exposed_collective_share.py").read
    rec = harness.Record(metrics={}, attempted=1, failed=0,
                         memory_peak_bytes=0, checks={},
                         layer={"kind": "train"}, trace=got)
    assert read(rec) == pytest.approx((0.03 / 0.08 + 0.02 / 0.05) / 2 * 100)
    assert read(harness.Record(metrics={}, attempted=1, failed=0,
                               memory_peak_bytes=0, checks={},
                               layer={"kind": "serve"}, trace=got)) is None


def test_one_chip_has_no_collectives():
    got = trace.reduce(ProfileData.from_file(
        str(DATA / "v5e_small.xplane.pb")), device_ids=[0])
    assert got.chips[0]["jit__lambda"][0] > 0
    assert all(c == 0.0 for _, c in got.chips[0].values())


def test_the_collectives_of_a_four_chip_v5e_trace_by_hand():
    """The FSDP step of ``record_fsdp.py`` on a v5e-4: per chip, the
    durations of the collective operations on the ops line (none holds
    another) over those of the step's calls, every one of them inside the
    window, averaged over the chips."""
    from bench import harness
    profile = ProfileData.from_file(str(DATA / "v5e4_fsdp.xplane.pb"))
    shares, kinds = [], set()
    for i in range(4):
        (plane,) = [p for p in profile.planes
                    if p.name == f"/device:TPU:{i}"]
        lines = {ln.name: ln for ln in plane.lines}
        step = sum(e.duration_ns for e in lines["XLA Modules"].events
                   if e.name.startswith("jit_train_step"))
        coll = [e for e in lines["XLA Ops"].events
                if re.match(r"%(all-gather|all-reduce|reduce-scatter"
                            r"|collective-permute|all-to-all"
                            r"|async-collective)", e.name)]
        kinds |= {trace.opcode(e.name) for e in coll}
        shares.append(sum(e.duration_ns for e in coll) / step)
    got = trace.reduce(profile, [0, 1, 2, 3])
    read = harness.load_module(harness.ROOT / "bench" / "metrics"
                               / "fsdp_exposed_collective_share.py").read
    share = read(harness.Record(metrics={}, attempted=1, failed=0,
                                memory_peak_bytes=0, checks={},
                                layer={"kind": "train"}, trace=got))
    assert share == pytest.approx(sum(shares) / 4 * 100, rel=1e-9)
    assert 0 < share < 100
    # synchronous gathers and reductions, and an asynchronous gather
    assert {"all-gather", "all-reduce", "fusion"} <= kinds
