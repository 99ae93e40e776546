"""End-to-end telemetry (core/telemetry.py): span tracing, unified
metrics registry, IO-cause stall attribution.

Covers nested cause-tagged spans with a shared no-op disabled path, their
mirror into a ``jax.profiler`` trace, the process-wide metrics registry
mirroring commit/waste counters, the provider's per-cause ``sim_s_*``
partition invariant, the fig6 stall decomposition summing exactly to its
total, traced chaos runs containing the fault-recovery spans
(``fetch.retry``, ``fetch.hedge``, ``commit.rebase``), and the spans and
counter of the accelerator path's feed (``feed.*``) and generation
(``gen.*``, ``gen.decode_calls``).
"""

import os
import subprocess
import sys
import threading
import time
from pathlib import Path

import numpy as np
import pytest

import repro.core as dl
from repro.core import telemetry
from repro.core.fetch import FetchEngine, RetryPolicy
from repro.core.telemetry import (attribute_stall, get_tracer, null_span,
                                  registry, sim_cause_partition, tracing)


# ------------------------------------------------------------ span basics
def test_disabled_path_is_shared_noop():
    """When tracing is off, every span call returns the SAME no-op object
    (no allocation) and nothing is recorded."""
    tr = get_tracer()
    assert not telemetry.enabled()
    tr.clear()
    s1 = telemetry.span("query.plan", x=1)
    s2 = telemetry.gspan(3, "fetch")
    assert s1 is s2 is null_span()
    with s1:
        s1.set(anything=1)  # no-op, chainable
    assert tr.events() == []


def test_lake_half_imports_and_traces_without_jax():
    """``repro.core`` and its tracer never import JAX: a lake-only process
    records spans, and the profiler mirror waits until JAX is imported."""
    code = ("import sys\n"
            "from repro.core import telemetry\n"
            "with telemetry.tracing() as tr:\n"
            "    with telemetry.span('query.plan'):\n"
            "        pass\n"
            "assert tr.count('query.plan') == 1\n"
            "assert telemetry.span('x') is telemetry.null_span()\n"
            "assert not [m for m in sys.modules if m.split('.')[0] in "
            "('jax', 'jaxlib')], 'repro.core imported jax'\n")
    src = str(Path(telemetry.__file__).resolve().parents[2])
    subprocess.run([sys.executable, "-c", code], check=True,
                   env=dict(os.environ, PYTHONPATH=src))


def test_span_nesting_parent_depth_and_ordering():
    with tracing() as tr:
        with telemetry.span("query.plan"):
            with telemetry.gspan(0, "fetch", rows=8):
                pass
            with telemetry.gspan(1, "decode"):
                pass
    evs = tr.events()
    # children record at exit, before the parent
    assert [e.name for e in evs] == [
        "scan.group[0].fetch", "scan.group[1].decode", "query.plan"]
    by = {e.name: e for e in evs}
    assert by["query.plan"].depth == 0 and by["query.plan"].parent is None
    for child in ("scan.group[0].fetch", "scan.group[1].decode"):
        assert by[child].depth == 1
        assert by[child].parent == "query.plan"
    assert by["scan.group[0].fetch"].args["rows"] == 8
    # timestamps are epoch-relative and non-negative; durations sane
    assert all(e.ts >= 0 and e.dur >= 0 for e in evs)


def test_span_records_error_arg_on_exception():
    with tracing() as tr:
        with pytest.raises(ValueError):
            with telemetry.span("commit.publish"):
                raise ValueError("boom")
    (ev,) = tr.events()
    assert ev.args["error"] == "ValueError"


def test_report_normalises_group_indices():
    with tracing() as tr:
        for i in range(5):
            with telemetry.gspan(i, "fetch"):
                pass
    rep = tr.report()
    assert rep["scan.group[*].fetch"]["count"] == 5
    assert rep["scan.group[*].fetch"]["total_s"] >= 0.0


def test_traced_span_lands_on_the_profiler_host_plane(tmp_path):
    """A span opened while tracing, inside a ``jax.profiler`` trace, is an
    event of the same name on the trace's host plane; one opened with
    tracing off is not."""
    import jax
    from jax.profiler import ProfileData

    jax.profiler.start_trace(str(tmp_path))
    try:
        with telemetry.span("feed.wait"):       # tracing off: no event
            pass
        with tracing() as tr:
            with telemetry.span("feed.pack"):
                with telemetry.gspan(2, "deliver", rows=4):
                    time.sleep(0.001)
    finally:
        jax.profiler.stop_trace()
    (path,) = tmp_path.rglob("*.xplane.pb")
    host = [e for p in ProfileData.from_file(str(path)).planes
            if not p.name.startswith("/device:")
            for line in p.lines for e in line.events]
    names = {e.name for e in host}
    assert {"feed.pack", "scan.group[2].deliver"} <= names
    assert "feed.wait" not in names
    inner = next(e for e in host if e.name == "scan.group[2].deliver")
    assert inner.duration_ns >= 1e6
    assert tr.count("feed.pack") == 1           # the buffer records it too


def test_tracer_thread_safety_and_per_thread_stacks():
    """Spans on different threads keep independent nesting stacks."""
    with tracing() as tr:
        def work(i):
            with telemetry.span(f"outer[{i}].a"):
                with telemetry.span(f"inner[{i}].b"):
                    pass
        ts = [threading.Thread(target=work, args=(i,)) for i in range(8)]
        for t in ts:
            t.start()
        for t in ts:
            t.join()
    evs = tr.events()
    assert len(evs) == 16
    for e in evs:
        if e.name.startswith("inner"):
            i = e.name.split("[")[1].split("]")[0]
            assert e.depth == 1 and e.parent == f"outer[{i}].a"
        else:
            assert e.depth == 0 and e.parent is None


# ------------------------------------------------------------ registry
def test_registry_counter_gauge_histogram_snapshot_delta():
    reg = telemetry.MetricsRegistry()
    reg.counter("commit.rebases").inc()
    reg.counter("commit.rebases").inc(2)
    reg.gauge("loader.inflight").set(7.5)
    h = reg.histogram("fetch.wall_s")
    h.observe(0.25)
    h.observe(0.75)
    snap = reg.snapshot()
    assert snap["commit_rebases"] == 3
    assert snap["loader_inflight"] == 7.5
    assert snap["fetch_wall_s_count"] == 2
    assert snap["fetch_wall_s_sum"] == pytest.approx(1.0)
    assert snap["fetch_wall_s_min"] == 0.25
    assert snap["fetch_wall_s_max"] == 0.75
    reg.counter("commit.rebases").inc(4)
    d = reg.delta(snap)
    assert d["commit_rebases"] == 4
    with pytest.raises(TypeError):
        reg.gauge("commit.rebases")       # name already bound to a Counter


def test_provider_snapshot_merges_engine_stats():
    s3 = dl.SimulatedS3Provider(time_scale=0)
    s3.put("k", b"x" * 100)
    eng = dl.engine_for(s3)
    eng.fetch_full("k")
    snap = telemetry.provider_snapshot(s3)
    assert snap["requests"] >= 1
    assert "sim_s_demand" in snap
    assert "engine_requests" in snap and "engine_retries" in snap
    assert all(isinstance(v, (int, float)) for v in snap.values())


# ------------------------------------------------- sim-cause partition
def test_sim_partition_covers_all_charges_clean_and_faulted():
    s3 = dl.SimulatedS3Provider(time_scale=0)
    s3.put("a", b"x" * 1000)            # write charge
    s3.exists("a")                      # meta charge
    s3.get("a")                         # demand
    with telemetry.io_cause("prefetch"):
        s3.get("a")                     # prefetch
    part = sim_cause_partition(s3.stats)
    assert part["write"] > 0 and part["meta"] > 0
    assert part["demand"] > 0 and part["prefetch"] > 0
    assert sum(part.values()) == pytest.approx(s3.stats["sim_seconds"])

    # injected faults charge their overtime to the fault bucket and the
    # partition stays exhaustive
    s3.fault_policy = dl.FaultPolicy(timeout_rate=1.0, seed=1,
                                     max_consecutive_per_key=2)
    eng = FetchEngine(s3)
    eng.fetch_full("a")
    part = sim_cause_partition(s3.stats)
    assert part["fault"] > 0
    assert part["retry"] > 0            # retried attempts re-tag their IO
    assert sum(part.values()) == pytest.approx(s3.stats["sim_seconds"])


def test_reset_stats_clears_cause_buckets():
    s3 = dl.SimulatedS3Provider(time_scale=0)
    s3.put("a", b"x" * 10)
    s3.reset_stats()
    assert all(v == 0 for v in sim_cause_partition(s3.stats).values())
    assert s3.stats["sim_seconds"] == 0


# ------------------------------------------------- stall attribution
def test_attribute_stall_priority_and_exact_total():
    out = attribute_stall({"demand": 8.0, "retry": 1.0}, compute_s=0.5)
    # pure overhead (retry) absorbs stall first, then demand fetch
    assert out["retry_hedge_s"] == pytest.approx(1.0)
    assert out["demand_fetch_s"] == pytest.approx(7.5)
    assert out["unattributed_s"] == pytest.approx(0.0)
    assert out["total_s"] == pytest.approx(8.5)
    causes = sum(v for k, v in out.items() if k != "total_s")
    assert causes == pytest.approx(out["total_s"])


def test_attribute_stall_no_stall_and_parallelism():
    # IO fully hidden by compute -> zero everywhere
    out = attribute_stall({"demand": 1.0}, compute_s=5.0, parallelism=8)
    assert out["total_s"] == 0.0
    assert all(v == 0.0 for v in out.values())
    # parallelism divides the raw sim seconds; decode folds in
    out = attribute_stall({"demand": 8.0}, compute_s=0.0, parallelism=8,
                          decode_s=0.5)
    assert out["total_s"] == pytest.approx(1.5)
    assert out["demand_fetch_s"] == pytest.approx(1.0)
    assert out["decode_s"] == pytest.approx(0.5)


def test_attribute_stall_unknown_cause_lands_unattributed():
    out = attribute_stall({"mystery": 2.0}, compute_s=0.0)
    assert out["unattributed_s"] == pytest.approx(2.0)
    assert out["total_s"] == pytest.approx(2.0)


# ------------------------------------------------- traced fault recovery
def test_traced_chaos_run_contains_retry_spans():
    s3 = dl.SimulatedS3Provider(time_scale=0)
    s3.put("k", b"y" * 500)
    s3.fault_policy = dl.FaultPolicy(timeout_rate=1.0, seed=1,
                                     max_consecutive_per_key=2)
    eng = FetchEngine(s3)
    with tracing() as tr:
        blob = eng.fetch_full("k")
    assert blob == b"y" * 500
    retries = tr.find("fetch.retry")
    assert retries, "faulted fetch recorded no fetch.retry spans"
    assert retries[0].args["key"] == "k"
    assert retries[0].args["attempt"] >= 1


def test_traced_straggler_produces_hedge_span():
    s3 = dl.SimulatedS3Provider(time_scale=0)
    for i in range(10):
        s3.put(f"b{i}", b"z" * 100)
    s3.put("slow", b"z" * 100)
    eng = FetchEngine(s3, retry=RetryPolicy(hedge_min_s=0.05))
    for i in range(10):                 # establish the clean-wall baseline
        eng.fetch_full(f"b{i}")
    assert eng.detector.baseline is not None
    # every read now straggles (real 0.2s sleep) far past the 50ms floor
    s3.fault_policy = dl.FaultPolicy(straggle_rate=1.0, straggle_sleep_s=0.2,
                                     seed=3, max_consecutive_per_key=2)
    with tracing() as tr:
        blob = eng.fetch_full("slow")
    assert blob == b"z" * 100
    assert eng.stats_snapshot()["hedges"] >= 1
    hedges = tr.find("fetch.hedge")
    assert hedges, "straggling fetch recorded no fetch.hedge span"
    assert hedges[0].args["key"] == "slow"


def test_traced_contended_commit_produces_rebase_span_and_counters():
    store = dl.MemoryProvider()
    ds0 = dl.Dataset(store)
    for t in ("a", "b"):
        ds0.create_tensor(t, dtype="float32", min_chunk_size=1 << 11,
                          max_chunk_size=1 << 12)
    ds0.commit("init")
    wa, wb = dl.Dataset(store), dl.Dataset(store)
    for i in range(4):
        wa["a"].append(np.full(16, i, np.float32))
        wb["b"].append(np.full(16, 100 + i, np.float32))
    reg0 = registry().snapshot()
    with tracing() as tr:
        wa.commit("writer a")
        wb.commit("writer b")           # loses the CAS race -> rebase
    rebases = tr.find("commit.rebase")
    assert rebases, "contended commit recorded no commit.rebase span"
    assert rebases[0].args["shape"] in ("adopt", "relocate")
    assert tr.count("commit.publish") >= 2
    regd = registry().delta(reg0)
    assert regd["commit_commits"] == 2
    assert regd["commit_rebases"] == wb.vc.commit_stats["rebases"] >= 1
    assert regd.get("commit_relocations", 0) == \
        wb.vc.commit_stats["relocations"]
    assert regd.get("commit_adoptions", 0) == wb.vc.commit_stats["adoptions"]


# ------------------------------------------------- loader + pipeline spans
def _image_ds(n=96):
    ds = dl.Dataset(dl.MemoryProvider())
    ds.create_tensor("images", htype="image", dtype="uint8",
                     sample_compression="zlib", min_chunk_size=16 << 10,
                     max_chunk_size=32 << 10)
    ds.create_tensor("labels", htype="class_label")
    rng = np.random.default_rng(5)
    for i in range(n):
        ds.append({"images": rng.integers(0, 255, (24, 24, 3), np.uint8),
                   "labels": np.int64(i)})
    ds.commit("data")
    return ds


def test_traced_loader_emits_scan_spans_and_stall_causes_sum():
    ds = _image_ds()
    loader = ds.dataloader(batch_size=16, shuffle=False, num_workers=2,
                           seed=0)
    with tracing() as tr:
        n = sum(len(b["labels"]) for b in loader)
    assert n == 96
    assert tr.count("scan.group") > 0          # fetch/decode worker spans
    rep = tr.report()
    assert any(k.startswith("scan.group[*]") for k in rep)
    st = loader.stats
    assert st.wait_seconds == pytest.approx(
        sum(st.stall_by_cause.values())), \
        "stall_by_cause must partition wait_seconds exactly"
    assert set(st.stall_by_cause) <= {"fetch", "decode", "buffer_full"}
    stalls = tr.find("loader.stall")
    for e in stalls:
        assert e.args["cause"] in ("fetch", "decode", "buffer_full")


def test_disabled_tracing_adds_no_events_on_hot_paths():
    """The whole read pipeline under disabled tracing must leave the
    global tracer buffer empty — no span leaks from the wired call sites."""
    tr = get_tracer()
    tr.clear()
    assert not telemetry.enabled()
    ds = _image_ds(n=48)
    loader = ds.dataloader(batch_size=16, shuffle=False, num_workers=2,
                           seed=0)
    assert sum(len(b["labels"]) for b in loader) == 48
    assert tr.events() == []


def test_tql_query_spans():
    ds = _image_ds(n=64)
    with tracing() as tr:
        view = ds.query("SELECT * FROM dataset WHERE labels < 10",
                        engine="numpy")
        assert len(view.indices) == 10
    assert tr.count("query.plan") == 1
    assert tr.count("query.where") == 1


# ------------------------------------- accelerator path: feed and generate
def _token_feed(num_docs=10):
    """A TokenBatcher over a small token lake, and DeviceFeeder shardings
    for its batches on the local mesh."""
    from jax.sharding import NamedSharding, PartitionSpec

    from repro.core.views import DatasetView
    from repro.data import TokenBatcher, build_token_dataset
    from repro.launch.mesh import make_local_mesh

    ds = build_token_dataset(dl.Dataset(dl.MemoryProvider()),
                             num_docs=num_docs, doc_len=48, vocab_size=97,
                             seed=3)
    batcher = TokenBatcher(DatasetView.full(ds), batch_size=2, seq_len=16,
                           shuffle=False, num_workers=1)
    rep = NamedSharding(make_local_mesh(), PartitionSpec())
    return batcher, {k: rep for k in ("tokens", "targets", "loss_mask")}


def test_token_feed_records_pack_h2d_and_wait_spans():
    """One ``feed.pack`` and one ``feed.h2d`` per batch, one ``feed.wait``
    per ``next()`` on the feeder (the last one receives its end)."""
    from repro.data import DeviceFeeder

    batcher, shardings = _token_feed()
    with tracing() as tr:
        it = iter(DeviceFeeder(iter(batcher), shardings))
        got, calls = [], 0
        while True:
            calls += 1
            try:
                got.append(next(it))
            except StopIteration:
                break
    assert len(got) >= 3
    assert tr.count("feed.pack") == len(got)
    assert tr.count("feed.h2d") == len(got)
    assert tr.count("feed.wait") == calls == len(got) + 1
    # three (2, 16) arrays of 4-byte elements a batch
    assert {e.args["bytes"] for e in tr.find("feed.h2d")} == {3 * 2 * 16 * 4}
    # packing and transfer run on the producer thread, the wait on ours
    me = threading.get_ident()
    assert {e.tid for e in tr.find("feed.wait")} == {me}
    assert me not in {e.tid for e in tr.find("feed.pack") + tr.find("feed.h2d")}


@pytest.fixture(scope="module")
def smoke_server():
    from repro.launch.serve import Server, ServeJob
    return Server(ServeJob(arch="starcoder2-3b", batch=2, prompt_len=5,
                           max_new_tokens=3))


def test_generate_records_its_spans_and_decode_calls(smoke_server):
    """One ``gen.generate`` and one ``gen.absorb`` a call, one
    ``gen.token`` per generated token, and ``gen.decode_calls`` grows by
    prompt length plus new tokens."""
    prompts = np.arange(10, dtype=np.int32).reshape(2, 5)
    reg0 = registry().snapshot()
    with tracing() as tr:
        out = smoke_server.generate(prompts)
    assert out.shape == (2, 8)
    assert registry().delta(reg0)["gen_decode_calls"] == 5 + 3
    (gen,) = tr.find("gen.generate")
    assert gen.args == {"batch": 2, "prompt_len": 5, "new": 3}
    (absorb,) = tr.find("gen.absorb")
    tokens = tr.find("gen.token")
    assert len(tokens) == 3
    for e in [absorb] + tokens:
        assert e.parent == "gen.generate" and e.depth == 1
        assert gen.ts <= e.ts and e.ts + e.dur <= gen.ts + gen.dur
    assert absorb.ts + absorb.dur <= min(e.ts for e in tokens)


def test_disabled_tracing_leaves_feed_and_generate_unrecorded(smoke_server):
    """With tracing off the feeder and ``Server.generate`` record no span;
    the decode-call counter still counts (counters are always on)."""
    from repro.data import DeviceFeeder

    tr = get_tracer()
    tr.clear()
    assert not telemetry.enabled()
    batcher, shardings = _token_feed()
    assert sum(1 for _ in DeviceFeeder(iter(batcher), shardings)) >= 3
    reg0 = registry().snapshot()
    smoke_server.generate(np.ones((2, 5), np.int32))
    assert registry().delta(reg0)["gen_decode_calls"] == 5 + 3
    assert tr.events() == []
