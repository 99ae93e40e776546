"""Sharded query serving benchmark (PR-9): N concurrent clients on one
:class:`~repro.core.serving.QueryService` over simulated S3.

Four gates, all under ``--smoke`` in ``scripts/check.sh``:

(a) **same-query storm** — 8 concurrent clients issuing one committed
    query cost at most 2x a single client's provider requests
    (single-flight + versioned result cache collapse the storm);
(b) **distinct-query storm** — aggregate requests for 8 different
    queries on one shared service stay sublinear vs. 8 cold
    single-client runs (shared engine residency + one manifest open);
(c) **shard parity** — the shard-parallel scan's results are
    byte-identical to the ``stream=False`` legacy path;
(d) **cache hit** — a repeat query performs zero planner work
    (``tql.plans`` counter frozen) and zero storage requests.

Under ``--smoke`` a traced re-run must keep simulated IO seconds within
5% of the untraced run and record ``serve.*`` spans.  Each run records a
``serving`` datapoint in ``BENCH_io.json``.
"""

from __future__ import annotations

import threading
import time
from typing import Dict, List

import numpy as np

import repro.core as dl
from repro.core import telemetry
from repro.core.serving import QueryService
from repro.core.storage import MemoryProvider, SimulatedS3Provider

from . import io_report
from .common import Timer, row

N_CLIENTS = 8
Q_SAME = ("SELECT * FROM dataset WHERE MIN(val) > 1450 "
          "ORDER BY MEAN(val) DESC LIMIT 64")
#: distinct per-client thresholds with heavy chunk overlap: low-threshold
#: clients rescan the high-threshold clients' bands
Q_DISTINCT = [f"SELECT * FROM dataset WHERE MIN(val) > {100 * k}"
              for k in range(N_CLIENTS)]


def _build_base() -> MemoryProvider:
    """Clustered 4000-row fixture (same shape as the pushdown bench)."""
    rng = np.random.default_rng(7)
    base = MemoryProvider()
    ds = dl.Dataset(base)
    ds.create_tensor("val", dtype="float32", min_chunk_size=1 << 12,
                     max_chunk_size=1 << 13)
    for i in range(4000):
        band = i // 250
        ds.append({"val": (rng.standard_normal(16).astype(np.float32)
                           + np.float32(100 * band))})
    ds.commit("serving bench")
    return base


def _storm(svc: QueryService, queries: List[str]) -> tuple:
    """Run one query per thread; returns (results, per-client wall s)."""
    res: List = [None] * len(queries)
    lat = [0.0] * len(queries)
    errs: List[Exception] = []

    def client(i: int) -> None:
        t0 = time.perf_counter()
        try:
            res[i] = svc.query(queries[i], tenant=f"client{i}")
        except Exception as e:  # pragma: no cover - diagnostic
            errs.append(e)
        lat[i] = time.perf_counter() - t0

    threads = [threading.Thread(target=client, args=(i,))
               for i in range(len(queries))]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=60)
    if errs:
        raise errs[0]
    return res, lat


def main(smoke: bool = False) -> List[str]:
    lines: List[str] = []
    base = _build_base()
    datapoint: Dict[str, Dict[str, float]] = {}

    # ---------------------------------------------- (c) shard parity
    s3 = SimulatedS3Provider(base, time_scale=0.0)
    remote = dl.Dataset(s3)
    legacy = remote.query(Q_SAME, engine="numpy", stream=False)
    sharded = remote.query(Q_SAME, engine="numpy", shards=4)
    assert sharded.indices.tolist() == legacy.indices.tolist(), \
        "shard-parallel scan is not byte-identical to the legacy path"
    assert (sharded.topk_plan or {}).get("shards") == 4, \
        "sharded top-k plan missing"
    lines.append(row("serving_shard_parity", 0.0,
                     f"rows{len(sharded)}_shards4"))

    # ------------------------------------- single-client request baseline
    s3a = SimulatedS3Provider(base, time_scale=0.0)
    svc_a = QueryService(dl.Dataset(s3a), max_concurrent=N_CLIENTS, shards=2)
    s3a.reset_stats()
    with Timer() as t:
        one = svc_a.query(Q_SAME)
    req_one = s3a.stats["requests"]
    assert req_one > 0, "cold single-client query issued no requests"
    assert one.indices.tolist() == legacy.indices.tolist()
    lines.append(row("serving_single_client", t.elapsed * 1e6,
                     f"req{req_one}_sim{s3a.stats['sim_seconds']:.3f}"))
    datapoint["single_client"] = {"requests": req_one,
                                  "sim_seconds": s3a.stats["sim_seconds"]}

    # ------------------------------------------ (a) same-query storm x8
    s3b = SimulatedS3Provider(base, time_scale=0.0)
    svc_b = QueryService(dl.Dataset(s3b), max_concurrent=4, shards=2)
    s3b.reset_stats()
    with Timer() as t:
        res, lat = _storm(svc_b, [Q_SAME] * N_CLIENTS)
    for r in res:
        assert r.indices.tolist() == legacy.indices.tolist(), \
            "storm client diverged from the serial result"
    req_storm = s3b.stats["requests"]
    assert req_storm <= 2 * req_one, \
        (f"same-query storm cost {req_storm} requests "
         f"(> 2x single client's {req_one})")
    st = svc_b.stats()
    assert st["cache_misses"] == 1, "single-flight did not collapse the storm"
    lines.append(row(
        "serving_storm8_same", t.elapsed * 1e6,
        f"req{req_storm}_vs1client{req_one}_hits{st['cache_hits']}"
        f"_lat_mean_us{int(np.mean(lat) * 1e6)}"
        f"_lat_max_us{int(np.max(lat) * 1e6)}"))
    datapoint["storm8_same"] = {
        "clients": N_CLIENTS, "requests": req_storm,
        "cache_hits": st["cache_hits"], "cache_misses": st["cache_misses"],
        "latency_mean_s": float(np.mean(lat)),
        "latency_max_s": float(np.max(lat)),
        "sim_seconds": s3b.stats["sim_seconds"]}

    # ------------------------------------ (b) distinct-query storm x8
    # cold per-client baseline: each query on its own provider + service
    solo_total = 0
    expects = []
    for q in Q_DISTINCT:
        s3i = SimulatedS3Provider(base, time_scale=0.0)
        svc_i = QueryService(dl.Dataset(s3i))
        s3i.reset_stats()
        expects.append(svc_i.query(q).indices.tolist())
        solo_total += s3i.stats["requests"]
    s3c = SimulatedS3Provider(base, time_scale=0.0)
    svc_c = QueryService(dl.Dataset(s3c), max_concurrent=4, shards=2)
    s3c.reset_stats()
    with Timer() as t:
        res, lat = _storm(svc_c, Q_DISTINCT)
    for r, exp in zip(res, expects):
        assert r.indices.tolist() == exp, "distinct-storm client diverged"
    req_distinct = s3c.stats["requests"]
    assert req_distinct < solo_total, \
        (f"distinct-query storm is not sublinear: {req_distinct} shared "
         f"vs {solo_total} across cold single clients")
    lines.append(row(
        "serving_storm8_distinct", t.elapsed * 1e6,
        f"req{req_distinct}_vs_solo{solo_total}"
        f"_lat_mean_us{int(np.mean(lat) * 1e6)}"))
    datapoint["storm8_distinct"] = {
        "clients": N_CLIENTS, "requests": req_distinct,
        "solo_total_requests": solo_total,
        "latency_mean_s": float(np.mean(lat)),
        "sim_seconds": s3c.stats["sim_seconds"]}

    # ------------------------------------------------ (d) cache hit
    plans0 = telemetry.registry().snapshot().get("tql_plans", 0)
    s3b.reset_stats()
    with Timer() as t:
        again = svc_b.query(Q_SAME)
    assert again.indices.tolist() == legacy.indices.tolist()
    assert s3b.stats["requests"] == 0, \
        "repeat-query cache hit touched storage"
    assert telemetry.registry().snapshot().get("tql_plans", 0) == plans0, \
        "repeat-query cache hit re-ran the planner"
    lines.append(row("serving_cache_hit", t.elapsed * 1e6, "req0_plans0"))
    datapoint["cache_hit"] = {"requests": 0,
                              "latency_s": float(t.elapsed)}

    # ----------------------------------- tracing overhead + serve.* spans
    if smoke:
        def traced_workload(provider) -> None:
            svc = QueryService(dl.Dataset(provider), max_concurrent=4,
                               shards=2)
            _storm(svc, [Q_SAME] * 4)
            # a full (stats-off) streamed WHERE guarantees the sharded
            # scan actually runs and emits serve.shard spans
            svc.query("SELECT * FROM dataset WHERE MIN(val) > 700",
                      use_stats=False)

        s3u = SimulatedS3Provider(base, time_scale=0.0)
        traced_workload(s3u)
        sim_u = s3u.stats["sim_seconds"]
        s3t = SimulatedS3Provider(base, time_scale=0.0)
        with telemetry.tracing() as tr:
            traced_workload(s3t)
        sim_t = s3t.stats["sim_seconds"]
        lines.append(row("serving_trace_overhead", abs(sim_t - sim_u) * 1e6,
                         f"untraced{sim_u:.3f}s_traced{sim_t:.3f}s"))
        assert abs(sim_t - sim_u) <= 0.05 * sim_u + 1e-6, (
            f"tracing perturbed serving IO: traced {sim_t:.6f}s vs "
            f"untraced {sim_u:.6f}s")
        for prefix in ("serve.admit", "serve.shard["):
            assert tr.count(prefix) > 0, \
                f"traced serving run produced no {prefix} spans"
        datapoint["trace"] = {
            "sim_untraced_s": sim_u, "sim_traced_s": sim_t,
            "serve_admit_spans": tr.count("serve.admit"),
            "serve_shard_spans": tr.count("serve.shard[")}

    io_report.record("serving", datapoint)
    return lines


if __name__ == "__main__":
    import sys

    print("\n".join(main(smoke="--smoke" in sys.argv[1:])))
