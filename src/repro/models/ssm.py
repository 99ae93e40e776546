"""Mamba2 layer via SSD (state-space duality, arXiv:2405.21060).

Recurrence (per head h, scalar decay a_t = exp(dt_t * A_h)):

    H_t = a_t * H_{t-1} + dt_t * B_t ⊗ x_t          H ∈ R^{N×P}
    y_t = C_t · H_t + D_h * x_t

Training uses the chunked SSD decomposition: the sequence is split into
chunks of Q tokens; within a chunk the recurrence is a (Q×Q) masked-decay
matmul (MXU work), across chunks a length-S/Q scan carries the (N×P) state.
The same decomposition is what the Pallas kernel (kernels/ssd_scan) tiles
into VMEM; this module is the XLA path and the oracle's structure.

Decode is the O(1) recurrence step on a carried (nh, P, N) state plus a
(K-1)-deep causal-conv cache — why SSM archs run the long_500k cell.
"""

from __future__ import annotations

from typing import Dict, Tuple

import jax
import jax.numpy as jnp

from .param import ParamSpec


# ------------------------------------------------------------------- specs
def ssm_specs(cfg, stack: Tuple[int, ...] = ()) -> Dict[str, ParamSpec]:
    ax = (None,) * len(stack)
    s = cfg.ssm
    d = cfg.d_model
    d_in = cfg.expand_dim
    nh = cfg.ssm_heads
    G, N = s.n_groups, s.d_state
    conv_dim = d_in + 2 * G * N
    proj_out = 2 * d_in + 2 * G * N + nh   # z, x, B, C, dt
    return {
        "in_proj": ParamSpec(stack + (d, proj_out), ax + ("fsdp", "model"),
                             dtype=cfg.dtype),
        "conv_w": ParamSpec(stack + (s.conv_kernel, conv_dim),
                            ax + (None, "model"), init="normal", dtype=cfg.dtype),
        "conv_b": ParamSpec(stack + (conv_dim,), ax + ("model",), init="zeros",
                            dtype=cfg.dtype),
        "A_log": ParamSpec(stack + (nh,), ax + ("model",), init="ssm_a",
                           dtype="float32"),
        "D": ParamSpec(stack + (nh,), ax + ("model",), init="ones", dtype="float32"),
        "dt_bias": ParamSpec(stack + (nh,), ax + ("model",), init="ssm_dt",
                             dtype="float32"),
        "norm": ParamSpec(stack + (d_in,), ax + ("model",), init="ones",
                          dtype="float32"),
        "out_proj": ParamSpec(stack + (d_in, d), ax + ("model", "fsdp"),
                              dtype=cfg.dtype),
    }


def _split_proj(zxbcdt: jax.Array, cfg):
    s = cfg.ssm
    d_in, G, N, nh = cfg.expand_dim, s.n_groups, s.d_state, cfg.ssm_heads
    z = zxbcdt[..., :d_in]
    x = zxbcdt[..., d_in:2 * d_in]
    Bm = zxbcdt[..., 2 * d_in:2 * d_in + G * N]
    Cm = zxbcdt[..., 2 * d_in + G * N:2 * d_in + 2 * G * N]
    dt = zxbcdt[..., 2 * d_in + 2 * G * N:]
    return z, x, Bm, Cm, dt


def _causal_conv(xbc: jax.Array, w: jax.Array, b: jax.Array) -> jax.Array:
    """Depthwise causal conv via shifts (kernel K small). xbc (B,S,C)."""
    K = w.shape[0]
    out = xbc * w[K - 1]
    for i in range(1, K):
        shifted = jnp.pad(xbc, ((0, 0), (i, 0), (0, 0)))[:, :-i]
        out = out + shifted * w[K - 1 - i]
    return jax.nn.silu(out + b)


def _gated_norm(scale: jax.Array, y: jax.Array, z: jax.Array,
                eps: float) -> jax.Array:
    out_dtype = z.dtype  # z comes straight from the (bf16) projection
    yf = y.astype(jnp.float32) * jax.nn.silu(z.astype(jnp.float32))
    var = jnp.mean(jnp.square(yf), axis=-1, keepdims=True)
    return (yf * jax.lax.rsqrt(var + eps) * scale).astype(out_dtype)


def _segsum(a: jax.Array) -> jax.Array:
    """L[i, j] = sum_{j < m <= i} a_m for i >= j else -inf.  a (..., Q)."""
    Q = a.shape[-1]
    cs = jnp.cumsum(a, axis=-1)
    diff = cs[..., :, None] - cs[..., None, :]          # (..., i, j): sum (j, i]
    ii = jnp.arange(Q)
    mask = ii[:, None] >= ii[None, :]
    return jnp.where(mask, diff, -jnp.inf)


# ---------------------------------------------------------------- SSD core
def ssd_chunked(x, dt, A, Bm, Cm, chunk: int, init_state=None):
    """SSD over chunks.

    x  (B, S, nh, P)    dt (B, S, nh)    A (nh,) negative
    Bm (B, S, G, N)     Cm (B, S, G, N)
    -> y (B, S, nh, P), final_state (B, nh, N, P)
    """
    Bsz, S, nh, P = x.shape
    G, N = Bm.shape[2], Bm.shape[3]
    Q = min(chunk, S)
    assert S % Q == 0, (S, Q)
    nc = S // Q
    hg = nh // G                                        # heads per group
    xc = x.reshape(Bsz, nc, Q, nh, P)
    dtc = dt.reshape(Bsz, nc, Q, nh)
    Bc = Bm.reshape(Bsz, nc, Q, G, N)
    Cc = Cm.reshape(Bsz, nc, Q, G, N)

    a = dtc * A                                          # (B,nc,Q,nh) decay logs (<=0)
    a_h = jnp.moveaxis(a, -1, 2)                         # (B,nc,nh,Q)
    L = jnp.exp(_segsum(a_h))                            # (B,nc,nh,Q,Q)

    # intra-chunk (the quadratic-but-tiny part; MXU matmuls)
    scores_g = jnp.einsum("bcign,bcjgn->bcgij", Cc, Bc)  # (B,nc,G,Q,Q)
    scores = jnp.repeat(scores_g, hg, axis=2)            # (B,nc,nh,Q,Q)
    M = scores * L * jnp.moveaxis(dtc, -1, 2)[:, :, :, None, :]
    y_intra = jnp.einsum("bchij,bcjhp->bcihp", M, xc)

    # per-chunk summarized state:  states[c] = Σ_j exp(a_sum - cumsum_j) dt_j B_j ⊗ x_j
    a_cum = jnp.cumsum(a_h, axis=-1)                      # (B,nc,nh,Q)
    a_tot = a_cum[..., -1]                                # (B,nc,nh)
    decay_out = jnp.exp(a_tot[..., None] - a_cum)         # (B,nc,nh,Q)
    wts = decay_out * jnp.moveaxis(dtc, -1, 2)            # (B,nc,nh,Q)
    Bh = jnp.repeat(Bc, hg, axis=3)                       # (B,nc,Q,nh,N)
    states = jnp.einsum("bchj,bcjhn,bcjhp->bchnp", wts, Bh, xc)

    # inter-chunk state scan
    h0 = (jnp.zeros((Bsz, nh, N, P), jnp.float32) if init_state is None
          else init_state.astype(jnp.float32))

    def scan_body(h, inp):
        st, atot = inp                                    # (B,nh,N,P), (B,nh)
        h_new = h * jnp.exp(atot)[..., None, None] + st.astype(jnp.float32)
        return h_new, h                                   # emit state BEFORE chunk

    (h_final, h_prevs) = jax.lax.scan(
        scan_body, h0, (jnp.moveaxis(states, 1, 0), jnp.moveaxis(a_tot, 1, 0)))
    h_prev = jnp.moveaxis(h_prevs, 0, 1)                  # (B,nc,nh,N,P)

    # inter-chunk contribution:  y_inter[i] = exp(a_cum_i) * C_i · h_prev
    decay_in = jnp.exp(a_cum)                             # (B,nc,nh,Q)
    Ch = jnp.repeat(Cc, hg, axis=3)                       # (B,nc,Q,nh,N)
    y_inter = jnp.einsum("bcihn,bchnp,bchi->bcihp", Ch,
                         h_prev.astype(Ch.dtype),
                         decay_in.astype(Ch.dtype))
    y = (y_intra + y_inter).reshape(Bsz, S, nh, P)
    return y.astype(x.dtype), h_final


def ssd_reference(x, dt, A, Bm, Cm, init_state=None):
    """Naive per-token scan oracle (tests compare chunked + kernel to this)."""
    Bsz, S, nh, P = x.shape
    G, N = Bm.shape[2], Bm.shape[3]
    hg = nh // G
    Bh = jnp.repeat(Bm, hg, axis=2)
    Ch = jnp.repeat(Cm, hg, axis=2)
    h0 = (jnp.zeros((Bsz, nh, N, P), jnp.float32) if init_state is None
          else init_state.astype(jnp.float32))

    def step(h, inp):
        xt, dtt, bt, ct = inp                              # (B,nh,P),(B,nh),(B,nh,N)x2
        decay = jnp.exp(dtt * A)                           # (B,nh)
        h = h * decay[..., None, None] + jnp.einsum(
            "bhn,bhp,bh->bhnp", bt, xt, dtt).astype(jnp.float32)
        y = jnp.einsum("bhn,bhnp->bhp", ct, h.astype(ct.dtype))
        return h, y

    h_final, ys = jax.lax.scan(
        step, h0, (jnp.moveaxis(x, 1, 0), jnp.moveaxis(dt, 1, 0),
                   jnp.moveaxis(Bh, 1, 0), jnp.moveaxis(Ch, 1, 0)))
    return jnp.moveaxis(ys, 0, 1).astype(x.dtype), h_final


# ------------------------------------------------------------- layer fwd
@jax.named_scope("ssm")
def mamba2_forward(params, u: jax.Array, cfg, *, impl: str = "xla",
                   init_state=None, return_state: bool = False):
    """Full Mamba2 layer: in_proj -> conv -> SSD -> gated norm -> out_proj."""
    s = cfg.ssm
    zxbcdt = u @ params["in_proj"]
    z, x, Bm, Cm, dt = _split_proj(zxbcdt, cfg)
    xbc = jnp.concatenate([x, Bm, Cm], axis=-1)
    xbc = _causal_conv(xbc, params["conv_w"], params["conv_b"])
    d_in = cfg.expand_dim
    G, N, nh = s.n_groups, s.d_state, cfg.ssm_heads
    x = xbc[..., :d_in].reshape(*u.shape[:2], nh, s.head_dim)
    Bm = xbc[..., d_in:d_in + G * N].reshape(*u.shape[:2], G, N)
    Cm = xbc[..., d_in + G * N:].reshape(*u.shape[:2], G, N)
    dt = jax.nn.softplus(dt.astype(jnp.float32) + params["dt_bias"])
    A = -jnp.exp(params["A_log"])
    if impl in ("pallas", "pallas_interpret"):
        from repro.kernels.ssd_scan import ops as ssd_ops
        y, h_final = ssd_ops.ssd(x, dt, A, Bm, Cm, chunk=s.chunk_size,
                                 interpret=(impl == "pallas_interpret"))
    else:
        y, h_final = ssd_chunked(x, dt, A, Bm, Cm, chunk=s.chunk_size,
                                 init_state=init_state)
    y = y + x * params["D"][:, None].astype(x.dtype)
    y = y.reshape(*u.shape[:2], d_in)
    y = _gated_norm(params["norm"], y, z, cfg.norm_eps)
    out = y @ params["out_proj"]
    if return_state:
        return out, h_final
    return out


@jax.named_scope("ssm")
def mamba2_decode_step(params, u: jax.Array, ssm_state: jax.Array,
                       conv_state: jax.Array, cfg):
    """One-token decode. u (B,1,d); ssm_state (B,nh,N,P);
    conv_state (B,K-1,conv_dim). Returns (out, new_ssm_state, new_conv_state)."""
    s = cfg.ssm
    zxbcdt = u @ params["in_proj"]
    z, x, Bm, Cm, dt = _split_proj(zxbcdt, cfg)
    xbc = jnp.concatenate([x, Bm, Cm], axis=-1)            # (B,1,conv_dim)
    window = jnp.concatenate([conv_state, xbc], axis=1)    # (B,K,conv_dim)
    conv_out = jnp.einsum("bkc,kc->bc", window, params["conv_w"]) + params["conv_b"]
    conv_out = jax.nn.silu(conv_out)[:, None]              # (B,1,conv_dim)
    new_conv_state = window[:, 1:]
    d_in, G, N, nh = cfg.expand_dim, s.n_groups, s.d_state, cfg.ssm_heads
    xt = conv_out[..., :d_in].reshape(-1, nh, s.head_dim)
    Bt = conv_out[..., d_in:d_in + G * N].reshape(-1, G, N)
    Ct = conv_out[..., d_in + G * N:].reshape(-1, G, N)
    hg = nh // G
    Bt = jnp.repeat(Bt, hg, axis=1)
    Ct = jnp.repeat(Ct, hg, axis=1)
    dtt = jax.nn.softplus(dt.astype(jnp.float32) + params["dt_bias"])[:, 0]
    A = -jnp.exp(params["A_log"])
    decay = jnp.exp(dtt * A)                                # (B,nh)
    new_state = ssm_state * decay[..., None, None] + jnp.einsum(
        "bhn,bhp,bh->bhnp", Bt, xt, dtt.astype(xt.dtype)).astype(ssm_state.dtype)
    y = jnp.einsum("bhn,bhnp->bhp", Ct, new_state.astype(Ct.dtype))
    y = y + xt * params["D"][:, None].astype(xt.dtype)
    y = y.reshape(-1, 1, d_in)
    y = _gated_norm(params["norm"], y, z, cfg.norm_eps)
    return y @ params["out_proj"], new_state, new_conv_state
