"""Writes a corpus into a Deep Lake dataset on the store a traffic mix names.

``storage.kind``:

- ``memory``: a ``MemoryProvider``; the lake costs nothing to read.
- ``s3``: a ``SimulatedS3Provider`` (real sleeps at ``time_scale`` 1)
  behind an LRU cache of ``lru_fraction`` of the corpus's bytes.  The corpus
  is written with the cost model off, as a lake that already holds it, and
  the counters are reset before it is opened again through the cache.
"""

from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import numpy as np

from repro.core.dataset import Dataset
from repro.core.storage import (LRUCacheProvider, MemoryProvider,
                                SimulatedS3Provider)

from .corpus import Corpus


def _write(ds: Dataset, corpus: Corpus) -> None:
    ds.create_tensor("tokens", htype="tokens", dtype="int32",
                     sample_compression="zlib",
                     min_chunk_size=256 << 10, max_chunk_size=1 << 20)
    ds.create_tensor("lang", htype="class_label")
    for i in range(len(corpus)):
        ds.append({"tokens": corpus.doc(i), "lang": np.int64(corpus.lang[i])})
    ds.commit(f"corpus x{len(corpus)}")


def build_store(corpus: Corpus, storage: Dict[str, Any]
                ) -> Tuple[Dataset, Optional[SimulatedS3Provider]]:
    """(dataset to read, the simulated S3 under it or None)."""
    kind = storage["kind"]
    if kind == "memory":
        ds = Dataset(MemoryProvider())
        _write(ds, corpus)
        return ds, None
    if kind != "s3":
        raise ValueError(f"unknown storage kind {kind!r}")
    s3 = SimulatedS3Provider(MemoryProvider(), latency_s=storage["latency_s"],
                             bandwidth_bps=storage["bandwidth_bps"],
                             time_scale=0.0)
    _write(Dataset(s3), corpus)
    s3.time_scale = float(storage.get("time_scale", 1.0))
    s3.reset_stats()
    cache = LRUCacheProvider(s3, capacity_bytes=int(
        storage["lru_fraction"] * corpus.tokens.nbytes))
    return Dataset(cache), s3
