"""Mamba2 SSD chunked-scan kernel (TPU Pallas).

The SSD decomposition (DESIGN.md §6, arXiv:2405.21060) maps perfectly onto
the TPU: the intra-chunk quadratic part is three (Q×Q)/(Q×N)/(Q×P) matmuls
(MXU), and the inter-chunk recurrence is a sequential state pass that lives
in VMEM scratch across the innermost grid axis.

Grid (B, n_heads, n_chunks), chunks innermost: for each (batch, head) a core
walks the chunks left-to-right, carrying the (N, P) state in scratch — the
HBM traffic is exactly one read of x/dt/B/C and one write of y (+ one final
state write), vs the XLA path's materialized (nc, N, P) inter-chunk states.

Cumulative sums inside the kernel use a triangular ones matmul
(MXU-friendly; avoids relying on mosaic scan lowering).

Layout: the wrapper puts heads before the sequence, x as (B, nh, S, P) and
B/C as (B, G, S, N), so every block's last two dims are a (Q, P) or (Q, N)
tile.  dt comes in both as rows (B, nh, 1, S) and as columns (B, nh, S, 1),
so the kernel needs no in-register transposes; A is read from SMEM.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _ssd_kernel(a_ref, x_ref, dtr_ref, dtc_ref, b_ref, c_ref, y_ref,
                state_ref, state_acc, *, chunk: int, n_chunks: int):
    h = pl.program_id(1)
    ci = pl.program_id(2)

    @pl.when(ci == 0)
    def _init():
        state_acc[...] = jnp.zeros_like(state_acc)

    x = x_ref[...].astype(jnp.float32)                   # (Q, P)
    dt_row = dtr_ref[...]                                # (1, Q)
    dt_col = dtc_ref[...]                                # (Q, 1)
    A = a_ref[0, h]                                      # scalar (negative)
    Bm = b_ref[...].astype(jnp.float32)                  # (Q, N)
    Cm = c_ref[...].astype(jnp.float32)                  # (Q, N)

    ii = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    jj = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    # inclusive cumsums of the log-decays, as a column and as a row
    a_cum_col = jax.lax.dot_general((ii >= jj).astype(jnp.float32),
                                    dt_col * A, (((1,), (0,)), ((), ())),
                                    preferred_element_type=jnp.float32)
    a_cum_row = jax.lax.dot_general(dt_row * A,
                                    (ii <= jj).astype(jnp.float32),
                                    (((1,), (0,)), ((), ())),
                                    preferred_element_type=jnp.float32)
    a_tot = jnp.sum(dt_row * A, axis=1, keepdims=True)   # (1, 1)

    # intra-chunk: masked-decay attention-like matmuls
    seg = a_cum_col - a_cum_row                          # sum over (j, i]
    L = jnp.where(ii >= jj, jnp.exp(seg), 0.0)
    scores = jax.lax.dot_general(Cm, Bm, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
    M = scores * L * dt_row
    y_intra = jax.lax.dot_general(M, x, (((1,), (0,)), ((), ())),
                                  preferred_element_type=jnp.float32)

    # inter-chunk: contribution of carried state, then state update
    state = state_acc[...]                               # (N, P)
    y_inter = jax.lax.dot_general(Cm, state, (((1,), (0,)), ((), ())),
                                  preferred_element_type=jnp.float32)
    y_inter = y_inter * jnp.exp(a_cum_col)
    y_ref[...] = (y_intra + y_inter).astype(y_ref.dtype)

    wts = dt_col * jnp.exp(a_tot - a_cum_col)            # (Q, 1)
    upd = jax.lax.dot_general(Bm, x * wts, (((0,), (0,)), ((), ())),
                              preferred_element_type=jnp.float32)
    state_acc[...] = state * jnp.exp(a_tot) + upd

    @pl.when(ci == n_chunks - 1)
    def _emit_state():
        state_ref[...] = state_acc[...]


def ssd_fwd(x, dt, A, Bm, Cm, *, chunk: int = 256, interpret: bool = False):
    """x (B,S,nh,P), dt (B,S,nh), A (nh,), Bm/Cm (B,S,G,N)
    -> y (B,S,nh,P), final_state (B,nh,N,P)."""
    B, S, nh, P = x.shape
    G, N = Bm.shape[2], Bm.shape[3]
    hg = nh // G
    Q = min(chunk, S)
    assert S % Q == 0, (S, Q)
    nc = S // Q
    dt_hs = jnp.moveaxis(dt.astype(jnp.float32), 2, 1)  # (B, nh, S)

    kernel = functools.partial(_ssd_kernel, chunk=Q, n_chunks=nc)
    y, state = pl.pallas_call(
        kernel,
        grid=(B, nh, nc),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((None, None, Q, P), lambda b, h, c: (b, h, c, 0)),
            pl.BlockSpec((None, None, 1, Q), lambda b, h, c: (b, h, 0, c)),
            pl.BlockSpec((None, None, Q, 1), lambda b, h, c: (b, h, c, 0)),
            pl.BlockSpec((None, None, Q, N), lambda b, h, c: (b, h // hg, c, 0)),
            pl.BlockSpec((None, None, Q, N), lambda b, h, c: (b, h // hg, c, 0)),
        ],
        out_specs=[
            pl.BlockSpec((None, None, Q, P), lambda b, h, c: (b, h, c, 0)),
            pl.BlockSpec((None, None, N, P), lambda b, h, c: (b, h, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, nh, S, P), x.dtype),
            jax.ShapeDtypeStruct((B, nh, N, P), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((N, P), jnp.float32)],
        interpret=interpret,
    )(A.reshape(1, nh).astype(jnp.float32), jnp.moveaxis(x, 2, 1),
      dt_hs[:, :, None, :], dt_hs[..., None], jnp.moveaxis(Bm, 2, 1),
      jnp.moveaxis(Cm, 2, 1))
    return jnp.moveaxis(y, 1, 2), state
