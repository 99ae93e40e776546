"""Deep Lake -> JAX training integration (the paper's C5 meeting pjit).

``TokenBatcher`` packs ragged documents from a Deep Lake view into fixed
(B, S+1) token blocks (targets = inputs shifted).  ``DeviceFeeder`` turns a
host batch iterator into sharded global device arrays with DOUBLE BUFFERING:
the next batch's device_put overlaps the current train step, so at steady
state the accelerator never waits on H2D — the Fig 6/7 property, carried to
the device boundary.

Multi-host note: each host feeds only its addressable shard of the global
batch (`host_slice`); in this single-process container that slice is the
whole batch, but the code path (slice -> device_put with NamedSharding) is
the production one.
"""

from __future__ import annotations

import queue
import threading
from typing import Any, Callable, Dict, Iterator, Optional

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding

from repro.core.dataloader import DeepLakeLoader
from repro.core.views import DatasetView


class TokenBatcher:
    """Streams (tokens, targets, loss_mask) host batches from a token view."""

    def __init__(self, view: DatasetView, *, batch_size: int, seq_len: int,
                 shuffle: bool = True, num_workers: int = 4, seed: int = 0,
                 pad_id: int = 0, num_codebooks: int = 0) -> None:
        self.view = view
        self.batch_size = batch_size
        self.seq_len = seq_len
        self.num_codebooks = num_codebooks
        self.pad_id = pad_id
        self.loader = DeepLakeLoader(view, batch_size=1, shuffle=shuffle,
                                     num_workers=num_workers, seed=seed,
                                     tensors=["tokens"], collate="list")
        self._buf = np.zeros((0,), np.int32)

    def __iter__(self) -> Iterator[Dict[str, np.ndarray]]:
        B, S = self.batch_size, self.seq_len
        need = B * (S + 1)
        self._buf = np.zeros((0,), np.int32)
        for batch in self.loader:
            doc = np.asarray(batch["tokens"][0], np.int32).reshape(-1)
            self._buf = np.concatenate([self._buf, doc])
            while len(self._buf) >= need:
                block = self._buf[:need].reshape(B, S + 1)
                self._buf = self._buf[need:]
                out = {"tokens": block[:, :-1],
                       "targets": block[:, 1:],
                       "loss_mask": np.ones((B, S), np.float32)}
                if self.num_codebooks:
                    k = self.num_codebooks
                    out["tokens"] = np.stack([block[:, :-1]] * k, axis=1)
                    out["targets"] = np.stack([block[:, 1:]] * k, axis=1)
                yield out


class DeviceFeeder:
    """Double-buffered host->device feeder with per-batch NamedShardings."""

    def __init__(self, host_iter: Iterator[Dict[str, np.ndarray]],
                 shardings: Dict[str, NamedSharding], *,
                 prefetch: int = 2) -> None:
        self.host_iter = host_iter
        self.shardings = shardings
        self.prefetch = max(1, prefetch)

    def _put(self, batch: Dict[str, np.ndarray]) -> Dict[str, jax.Array]:
        missing = sorted(set(batch) - set(self.shardings))
        if missing:
            raise KeyError(f"batch keys {missing} have no sharding; a key "
                           f"without one would land on a single device")
        return {k: jax.device_put(v, self.shardings[k])
                for k, v in batch.items()}

    def __iter__(self) -> Iterator[Dict[str, jax.Array]]:
        q: "queue.Queue" = queue.Queue(maxsize=self.prefetch)
        DONE = object()
        err: list = []

        def producer():
            try:
                for batch in self.host_iter:
                    q.put(self._put(batch))  # device_put overlaps consumer step
            except BaseException as e:
                err.append(e)
            finally:
                q.put(DONE)

        t = threading.Thread(target=producer, daemon=True)
        t.start()
        while True:
            item = q.get()
            if item is DONE:
                if err:
                    raise err[0]
                return
            yield item


def host_slice(batch: Dict[str, np.ndarray], process_index: int,
               process_count: int) -> Dict[str, np.ndarray]:
    """Each host contributes its contiguous slice of the global batch."""
    out = {}
    for k, v in batch.items():
        per = v.shape[0] // process_count
        out[k] = v[process_index * per:(process_index + 1) * per]
    return out
