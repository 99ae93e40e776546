"""Serving driver: batched prefill + decode with a KV/SSM cache.

Requests are batched (continuous batching would slot-swap; here the batch is
fixed-size with left-aligned prompts, the shape the decode_* dry-run cells
lower).  Greedy or temperature sampling; prompts stream from a Deep Lake
view when --from-lake is set (inference is one of the paper's §3.5 access
patterns).

CLI:  python -m repro.launch.serve --arch gemma-2b --smoke --tokens 16
      python -m repro.launch.serve --arch starcoder2-3b --full --batch 8 \
          --prompt-len 128 --tokens 32
"""

from __future__ import annotations

import argparse
import functools
import time
from dataclasses import dataclass
from typing import Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import ARCHS, job_config
from repro.distributed import make_rules, make_shard_fn, sharding_for_specs
from repro.launch.compile_cache import use_compile_cache
from repro.launch.mesh import make_local_mesh
from repro.models.model import build_model


@dataclass
class ServeJob:
    arch: str = "gemma-2b"
    smoke: bool = True
    num_layers: Optional[int] = None  # depth cut of the published config
    batch: int = 4
    prompt_len: int = 32
    max_new_tokens: int = 16
    temperature: float = 0.0
    seed: int = 0
    model_axis: int = 1


class Server:
    def __init__(self, job: ServeJob, params=None) -> None:
        self.job = job
        cfg = job_config(job.arch, smoke=job.smoke, num_layers=job.num_layers)
        self.cfg = cfg
        self.mesh = make_local_mesh(model_axis=job.model_axis)
        self.rules = make_rules("decode")
        self.model = build_model(cfg, shard_fn=make_shard_fn(self.mesh,
                                                             self.rules))
        if params is None:
            # built directly in its shardings: nothing lands on one device
            shardings = sharding_for_specs(self.model.param_specs(),
                                           self.mesh, self.rules)
            params = jax.jit(self.model.init, out_shardings=shardings)(
                jax.random.PRNGKey(job.seed))
        self.params = params
        self._decode = jax.jit(self.model.decode_step, donate_argnums=(1,))
        #: jitted cache init per (batch, max_len), so it compiles once
        self._cache_inits: Dict[Tuple[int, int], Callable] = {}
        self.stats = {"prefill_s": 0.0, "decode_s": 0.0, "tokens": 0}
        #: logits after the last prompt token of the latest ``generate``
        self.prompt_logits: Optional[jax.Array] = None

    def _init_cache(self, batch: int, max_len: int):
        init = self._cache_inits.get((batch, max_len))
        if init is None:
            shardings = sharding_for_specs(
                self.model.cache_specs(batch, max_len), self.mesh, self.rules)
            init = jax.jit(functools.partial(self.model.init_cache, batch,
                                             max_len), out_shardings=shardings)
            self._cache_inits[(batch, max_len)] = init
        return init()

    def generate(self, prompts: np.ndarray, max_new_tokens: Optional[int] = None
                 ) -> np.ndarray:
        """prompts (B, P) int32 -> (B, P + new) generated ids (greedy/sampled)."""
        job = self.job
        new = max_new_tokens or job.max_new_tokens
        B, P = prompts.shape
        total = P + new
        cache = self._init_cache(B, total)
        rng = jax.random.PRNGKey(job.seed)
        out = np.zeros((B, total), np.int32)
        out[:, :P] = prompts
        t0 = time.perf_counter()
        with self.mesh:
            # prompt absorption token-by-token through the decode path (the
            # cache layout then matches decode exactly); prefill-step lowering
            # is exercised separately by the dry-run prefill cells.
            logits = None
            for t in range(P):
                logits, cache = self._decode(self.params, cache,
                                             jnp.asarray(out[:, t]),
                                             jnp.int32(t))
            self.prompt_logits = jax.block_until_ready(logits)
            self.stats["prefill_s"] += time.perf_counter() - t0
            t0 = time.perf_counter()
            for t in range(P, total):
                nxt = self._sample(logits, rng, t)
                out[:, t] = np.asarray(nxt)
                logits, cache = self._decode(self.params, cache,
                                             jnp.asarray(out[:, t]),
                                             jnp.int32(t))
            self.stats["decode_s"] += time.perf_counter() - t0
            self.stats["tokens"] += B * new
        return out

    def _sample(self, logits, rng, t):
        logits = logits[..., : self.cfg.vocab_size]
        if self.job.temperature <= 0:
            return jnp.argmax(logits, axis=-1).astype(jnp.int32)
        key = jax.random.fold_in(rng, t)
        return jax.random.categorical(
            key, logits / self.job.temperature, axis=-1).astype(jnp.int32)

    def throughput(self) -> float:
        return self.stats["tokens"] / self.stats["decode_s"] \
            if self.stats["decode_s"] else 0.0


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="gemma-2b", choices=sorted(ARCHS))
    ap.add_argument("--smoke", action="store_true", default=True)
    ap.add_argument("--full", dest="smoke", action="store_false")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--tokens", type=int, default=16)
    ap.add_argument("--temperature", type=float, default=0.0)
    args = ap.parse_args()
    job = ServeJob(arch=args.arch, smoke=args.smoke, batch=args.batch,
                   prompt_len=args.prompt_len, max_new_tokens=args.tokens,
                   temperature=args.temperature)
    use_compile_cache()
    server = Server(job)
    rng = np.random.default_rng(0)
    if job.smoke and server.cfg.num_codebooks:
        raise SystemExit("serve CLI demo targets text archs; musicgen decode "
                         "is covered by tests/dry-run")
    prompts = rng.integers(0, server.cfg.vocab_size,
                           (job.batch, job.prompt_len)).astype(np.int32)
    out = server.generate(prompts)
    dev = jax.devices()[0]
    print(f"generated {out.shape} | decode throughput "
          f"{server.throughput():.1f} tok/s "
          f"(batch {job.batch}, {server.cfg.num_layers} layers, "
          f"{'smoke' if job.smoke else 'published'} widths, "
          f"{len(jax.devices())}x {dev.platform} {dev.device_kind})")
    print("sample ids:", out[0, job.prompt_len:job.prompt_len + 12].tolist())


if __name__ == "__main__":
    main()
