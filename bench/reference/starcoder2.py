"""Plain float32 reference of the starcoder2 decoder as the program defines
it, for the benchmark's correctness checks.  It imports nothing of the
program and takes nothing the program made: it builds its own weights from
the seed, by the recipe the configuration states.

Sizes come from a configuration file (``bench/configs/*.json``).  Weights
are stored in the configuration's dtype (bfloat16) and every computation
runs in float32, matrix products at ``Precision.HIGHEST``.  The loss, its
gradient and the logits are computed layer by layer, with attention and the
loss in blocks of queries, so that a whole step at the timed widths fits on
one chip beside nothing else.  Handed several devices, ``train_steps``
shards its weights, gradient and AdamW moments over them as FSDP does
(``Layout``) and gathers each layer's weights where it is used; the
mathematics is the same, and on one device the arrays are placed as they
always were.

Where this departs from the hf description of starcoder2
(``bigcode/starcoder2-3b``), it follows the program's model instead, since
that is the configuration the benchmark runs (listed in ``PERF.md``):

- RMSNorm with a scale only, eps from the file, where hf has LayerNorm
  with a bias (eps 1e-5);
- biases on the q, k and v projections only; hf has them on every linear
  layer (``use_bias``);
- token embeddings scaled by sqrt(hidden_size); hf does not scale them;
- a separate output head; hf ties it to the embedding;
- rope theta 999999.0 where hf has 999999.4420358813.

Weight recipe, per leaf in sorted-key order of the stacked layout, with
``keys = split(PRNGKey(seed), 14)``: normal(key, f32) / sqrt(fan_in), cast
to the stored dtype; q/k/v/o biases zero; norm scales one (float32).
"""

from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import Any, Callable, Dict, List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec

HI = jax.lax.Precision.HIGHEST
F32 = jnp.float32
NEG_INF = -1e30


@dataclass(frozen=True)
class Sizes:
    d: int
    H: int
    Hkv: int
    hd: int
    ff: int
    V: int
    Vp: int
    L: int
    window: int
    theta: float
    eps: float
    dtype: str

    @classmethod
    def of(cls, cfg: Dict[str, Any]) -> "Sizes":
        V = int(cfg["vocab_size"])
        m = int(cfg.get("vocab_pad_multiple", 256))
        return cls(d=cfg["hidden_size"], H=cfg["num_attention_heads"],
                   Hkv=cfg["num_key_value_heads"], hd=cfg["head_dim"],
                   ff=cfg["intermediate_size"], V=V, Vp=-(-V // m) * m,
                   L=cfg["num_hidden_layers"],
                   window=int(cfg.get("sliding_window") or 0),
                   theta=float(cfg["rope_theta"]),
                   eps=float(cfg["norm_epsilon"]), dtype=cfg["dtype"])


# ------------------------------------------------------------------ layout
class Layout:
    """Where the reference's arrays live.  On one device (or none named):
    the default device, every call as it is.  On several: a mesh over
    them, each weight, gradient and moment split along its first axis that
    the device count divides (replicated where none does), as FSDP shards
    them, each batch split by rows, and each layer's weights gathered whole
    where the layer uses them."""

    AXIS = "fsdp"

    def __init__(self, devices: Sequence[Any] = None) -> None:
        devices = list(devices or [])
        self.mesh = (Mesh(np.asarray(devices), (self.AXIS,))
                     if len(devices) > 1 else None)

    def split(self, shape: Sequence[int]):
        """The sharding of an array of ``shape`` split along its first
        axis the device count divides, or None on one device."""
        if self.mesh is None:
            return None
        n = self.mesh.size
        for i, dim in enumerate(shape):
            if dim % n == 0:
                return NamedSharding(self.mesh, PartitionSpec(
                    *([None] * i), self.AXIS))
        return self.whole()

    def whole(self):
        return (None if self.mesh is None
                else NamedSharding(self.mesh, PartitionSpec()))

    def rows(self, shape: Sequence[int]):
        """A batch's sharding: split by rows where they divide."""
        if self.mesh is None:
            return None
        if shape[0] % self.mesh.size:
            return self.whole()
        return NamedSharding(self.mesh, PartitionSpec(self.AXIS))

    def gather(self, x):
        """``x`` whole on every device, inside a traced function."""
        if self.mesh is None:
            return x
        return jax.lax.with_sharding_constraint(x, self.whole())

    def by_rows(self, x):
        """Activations split by rows, inside a traced function."""
        if self.mesh is None:
            return x
        return jax.lax.with_sharding_constraint(x, self.rows(x.shape))

    def put(self, x, sharding=None):
        """A host array on the device(s): split by rows unless ``sharding``
        is given."""
        if self.mesh is None:
            return jnp.asarray(x)
        return jax.device_put(x, sharding or self.rows(np.shape(x)))

    def jit(self, fn, out_shardings=None, **kw):
        """``jax.jit``, with ``out_shardings`` on several devices only."""
        if self.mesh is not None and out_shardings is not None:
            kw["out_shardings"] = out_shardings
        return jax.jit(fn, **kw)


ONE = Layout()


# ------------------------------------------------------------------ weights
def leaf_specs(s: Sizes) -> List[Tuple[str, Tuple[int, ...], str, int]]:
    """(name, shape, init, fan_in) in the sorted-key order of the tree."""
    L, d, H, Hkv, hd, ff = s.L, s.d, s.H, s.Hkv, s.hd, s.ff
    return [
        ("blocks/attn/bk", (L, Hkv, hd), "zeros", 0),
        ("blocks/attn/bq", (L, H, hd), "zeros", 0),
        ("blocks/attn/bv", (L, Hkv, hd), "zeros", 0),
        ("blocks/attn/wk", (L, d, Hkv, hd), "normal", d),
        ("blocks/attn/wo", (L, H, hd, d), "normal", H * hd),
        ("blocks/attn/wq", (L, d, H, hd), "normal", d),
        ("blocks/attn/wv", (L, d, Hkv, hd), "normal", d),
        ("blocks/ln1", (L, d), "ones", 0),
        ("blocks/ln2", (L, d), "ones", 0),
        ("blocks/mlp/wi", (L, d, ff), "normal", d),
        ("blocks/mlp/wo", (L, ff, d), "normal", ff),
        ("embed", (s.Vp, d), "normal", d),
        ("final_ln", (d,), "ones", 0),
        ("head", (d, s.Vp), "normal", d),
    ]


def param_shardings(s: Sizes, lay: Layout) -> Dict[str, Any]:
    return {name: lay.split(shape) for name, shape, _, _ in leaf_specs(s)}


def init_params(s: Sizes, seed: int, lay: Layout = ONE
                ) -> Dict[str, jax.Array]:
    """The configuration's weights for ``seed``, built on the device(s)."""
    specs = leaf_specs(s)

    def build(key):
        keys = jax.random.split(key, len(specs))
        out = {}
        for (name, shape, init, fan_in), k in zip(specs, keys):
            if init == "zeros":
                out[name] = jnp.zeros(shape, s.dtype)
            elif init == "ones":
                out[name] = jnp.ones(shape, F32)
            else:
                std = 1.0 / np.sqrt(fan_in)
                out[name] = (jax.random.normal(k, shape, F32) * std
                             ).astype(s.dtype)
        return out

    return lay.jit(build, param_shardings(s, lay))(jax.random.PRNGKey(seed))


def layer_leaves(params: Dict[str, jax.Array]) -> Dict[str, jax.Array]:
    return {k[len("blocks/"):]: v for k, v in params.items()
            if k.startswith("blocks/")}


# ----------------------------------------------------------------- products
def dot_f32(x, w):
    """x (..., K) @ w (K, N) in float32 at full precision."""
    return jnp.dot(x.astype(F32), w.astype(F32), precision=HI,
                   preferred_element_type=F32)


def _fp8(x):
    scale = jnp.max(jnp.abs(x)) / 448.0          # e4m3's largest finite
    scale = jnp.where(scale == 0, 1.0, scale)
    q = (x / scale).astype(jnp.float8_e4m3fn).astype(F32) * scale
    return x + jax.lax.stop_gradient(q - x)


def dot_fp8(x, w):
    """The control: float8 (e4m3) operands, each tensor scaled to e4m3's
    range, with float32 accumulation: the precision below the
    configuration's bfloat16."""
    return dot_f32(_fp8(x.astype(F32)), _fp8(w.astype(F32)))


#: the control: the precision below the configuration's bfloat16
CONTROLS = {"fp8": dot_fp8}


# ------------------------------------------------------------------ layers
def rmsnorm(x, scale, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * scale.astype(F32)


def rope(x, positions, theta):
    """x (B, S, n, hd); rotates the two halves of each head."""
    half = x.shape[-1] // 2
    freqs = 1.0 / (theta ** (jnp.arange(0, half, dtype=F32) / half))
    ang = positions[..., None].astype(F32) * freqs
    cos, sin = jnp.cos(ang)[..., None, :], jnp.sin(ang)[..., None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def attention(q, k, v, s: Sizes, q_block: int):
    """Causal attention within the window; q (B,S,H,hd), k/v (B,S,Hkv,hd).
    Query head h reads key/value head h // (H / Hkv).  Computed in blocks
    of queries, each recomputed in the backward pass."""
    B, S, H, hd = q.shape
    G = H // s.Hkv
    qb = min(q_block, S)
    nb = S // qb
    kpos = jnp.arange(S)

    @jax.checkpoint
    def one(args):
        i, qc = args                                    # qc (B,qb,Hkv,G,hd)
        sc = jnp.einsum("bqkgd,btkd->bkgqt", qc, k, precision=HI) / np.sqrt(hd)
        diff = (i * qb + jnp.arange(qb))[:, None] - kpos[None, :]
        ok = diff >= 0
        if s.window:
            ok = ok & (diff < s.window)
        p = jax.nn.softmax(jnp.where(ok, sc, NEG_INF), axis=-1)
        return jnp.einsum("bkgqt,btkd->bqkgd", p, v, precision=HI)

    qs = q.reshape(B, nb, qb, s.Hkv, G, hd).swapaxes(0, 1)
    out = jax.lax.map(one, (jnp.arange(nb), qs))        # (nb,B,qb,Hkv,G,hd)
    return out.swapaxes(0, 1).reshape(B, S, H, hd)


def block(p, h, positions, s: Sizes, dot: Callable, q_block: int):
    B, S, d = h.shape
    x = rmsnorm(h, p["ln1"], s.eps)

    def proj(w, b, n):
        y = dot(x, w.reshape(d, n * s.hd)).reshape(B, S, n, s.hd)
        return y + b.astype(F32)

    q = rope(proj(p["attn/wq"], p["attn/bq"], s.H), positions, s.theta)
    k = rope(proj(p["attn/wk"], p["attn/bk"], s.Hkv), positions, s.theta)
    v = proj(p["attn/wv"], p["attn/bv"], s.Hkv)
    a = attention(q, k, v, s, q_block).reshape(B, S, s.H * s.hd)
    h = h + dot(a, p["attn/wo"].reshape(s.H * s.hd, d))
    x = rmsnorm(h, p["ln2"], s.eps)
    u = jax.nn.gelu(dot(x, p["mlp/wi"]), approximate=True)
    return h + dot(u, p["mlp/wo"])


def hidden(params, tokens, s: Sizes, dot: Callable, q_block: int = 512,
           lay: Layout = ONE):
    """Final normed hidden states (B, S, d), float32."""
    B, S = tokens.shape
    positions = jnp.broadcast_to(jnp.arange(S), (B, S))
    h = lay.by_rows(lay.gather(params["embed"]).astype(F32)[tokens]
                    * np.sqrt(s.d))

    def body(h, p):
        return lay.by_rows(block(lay.gather(p), h, positions, s, dot,
                                 q_block)), None

    h, _ = jax.lax.scan(jax.checkpoint(body), h, layer_leaves(params))
    return rmsnorm(h, lay.gather(params["final_ln"]), s.eps)


def logits_of(params, h, s: Sizes, dot: Callable):
    return dot(h, params["head"])[..., :s.V]


def loss(params, batch, s: Sizes, dot: Callable = dot_f32,
         chunk: int = 512, lay: Layout = ONE):
    """Token-mean cross entropy over ``loss_mask``, in blocks of positions."""
    h = hidden(params, batch["tokens"], s, dot, lay=lay)
    B, S, d = h.shape
    c = min(chunk, S)
    head = {"head": lay.gather(params["head"])}

    @jax.checkpoint
    def nll(args):
        hc, tc = args
        lg = logits_of(head, hc, s, dot)
        gold = jnp.take_along_axis(lg, tc[..., None], axis=-1)[..., 0]
        return jax.nn.logsumexp(lg, axis=-1) - gold

    hs = h.reshape(B, S // c, c, d).swapaxes(0, 1)
    ts = batch["targets"].reshape(B, S // c, c).swapaxes(0, 1)
    per = jax.lax.map(nll, (hs, ts)).swapaxes(0, 1).reshape(B, S)
    m = batch["loss_mask"].astype(F32)
    return jnp.sum(per * m) / jnp.maximum(jnp.sum(m), 1.0)


# -------------------------------------------------------------- training
def lr_at(opt: Dict[str, Any], step):
    """Cosine with linear warm-up, as the configuration states."""
    peak, warm = opt["lr"], max(opt["warmup"], 1)
    total, floor = opt["schedule_steps"], opt["lr_floor"]
    s = step.astype(F32)
    prog = jnp.clip((s - opt["warmup"]) / max(total - opt["warmup"], 1),
                    0.0, 1.0)
    cos = peak * (floor + (1 - floor) * 0.5 * (1 + jnp.cos(jnp.pi * prog)))
    return jnp.where(s < opt["warmup"], peak * s / warm, cos)


def clip_scale(opt: Dict[str, Any], g) -> jax.Array:
    gnorm = jnp.sqrt(sum(jnp.sum(jnp.square(x)) for x in g.values()))
    return jnp.minimum(1.0, opt["clip_norm"] / jnp.maximum(gnorm, 1e-9))


def train_steps(s: Sizes, opt: Dict[str, Any], seed: int,
                batches: Sequence[Dict[str, np.ndarray]],
                dot: Callable = dot_f32, against=None,
                keep_grad: bool = False,
                devices: Sequence[Any] = None) -> Dict[str, Any]:
    """AdamW from the seed's weights over ``batches``, one step each.

    Returns the loss of every step, the per-leaf norm of the first step's
    gradient as AdamW takes it (after clipping) and the per-leaf norm of the
    weights' change after the last step, as floats.  With ``against`` (a
    first gradient as AdamW took it, per leaf, on the host) also the
    per-leaf norm of its difference from this one; with ``keep_grad`` this
    first gradient itself, on the host.  Weights, gradient and moments live
    sharded over ``devices`` (``Layout``)."""
    lay = Layout(devices)
    params = init_params(s, seed, lay)
    names = sorted(params)
    shard = param_shardings(s, lay)
    norms_sh = {k: lay.whole() for k in names}
    vg = lay.jit(jax.value_and_grad(functools.partial(loss, s=s, dot=dot,
                                                      lay=lay)),
                 (lay.whole(), shard))
    scale_of = jax.jit(functools.partial(clip_scale, opt))
    diff = jax.jit(lambda a, g, sc: jnp.sqrt(jnp.sum(jnp.square(a - g * sc))))

    @functools.partial(lay.jit, out_shardings=(shard, shard, shard, norms_sh),
                       donate_argnums=(0, 1, 2))
    def update(params, m, v, g, step):
        scale = clip_scale(opt, g)
        b1, b2, t = opt["b1"], opt["b2"], step.astype(F32)
        lr = lr_at(opt, step)
        new_p, new_m, new_v, gn = {}, {}, {}, {}
        for k in names:
            gs = g[k] * scale
            gn[k] = jnp.sqrt(jnp.sum(jnp.square(gs)))
            new_m[k] = b1 * m[k] + (1 - b1) * gs
            new_v[k] = b2 * v[k] + (1 - b2) * gs * gs
            delta = (new_m[k] / (1 - b1 ** t)) / (
                jnp.sqrt(new_v[k] / (1 - b2 ** t)) + opt["eps"])
            p = params[k]
            if p.ndim >= opt["decay_min_rank"]:
                delta = delta + opt["weight_decay"] * p.astype(F32)
            u = (-lr * delta).astype(p.dtype)
            new_p[k] = (p.astype(F32) + u.astype(F32)).astype(p.dtype)
        return new_p, new_m, new_v, gn

    zeros = lay.jit(lambda p: {k: jnp.zeros(x.shape, F32)
                               for k, x in p.items()}, shard)
    m, v = zeros(params), zeros(params)
    out: Dict[str, Any] = {"losses": []}
    for i, b in enumerate(batches):
        batch = {k: lay.put(b[k]) for k in ("tokens", "targets",
                                             "loss_mask")}
        lv, g = vg(params, batch)
        if i == 0:
            sc = scale_of(g)
            if against is not None:
                out["grad_diff_norms"] = {
                    k: float(diff(lay.put(against[k], shard[k]), g[k], sc))
                    for k in names}
            if keep_grad:
                out["grad"] = {k: np.asarray(g[k] * sc) for k in names}
        params, m, v, gn = update(params, m, v, g, jnp.int32(i + 1))
        out["losses"].append(float(lv))
        if i == 0:
            out["grad_norms"] = {k: float(x) for k, x in gn.items()}
        del g
    # the start weights are built again rather than kept beside the
    # optimizer state, which leaves room for deeper cuts on one chip
    del m, v
    p0 = init_params(s, seed, lay)
    change = jax.jit(lambda a, b: {k: jnp.sqrt(jnp.sum(jnp.square(
        a[k].astype(F32) - b[k].astype(F32)))) for k in names})(params, p0)
    out["change_norms"] = {k: float(x) for k, x in change.items()}
    return out


# ----------------------------------------------------------------- serving
def served_gaps(s: Sizes, seed: int, seqs: np.ndarray, prompt_len: int,
                dot: Callable = dot_f32, judge: Callable = dot_f32,
                rows: int = 4) -> np.ndarray:
    """For sequences (n, P + new) of prompt and served tokens: at each served
    position, how far the reference's logit of the token chosen lies below
    the reference's best.  The served token is ``seqs`` itself where
    ``dot`` is the reference's own product; another ``dot`` (the control)
    chooses its own argmax at each position instead.  Returns (n, new)."""
    params = init_params(s, seed)

    @jax.jit
    def gaps(params, tokens):
        h = hidden(params, tokens[:, :-1], s, judge)[:, prompt_len - 1:]
        ref = logits_of(params, h, s, judge)
        if dot is judge:
            pick = tokens[:, prompt_len:]
        else:
            hc = hidden(params, tokens[:, :-1], s, dot)[:, prompt_len - 1:]
            pick = jnp.argmax(logits_of(params, hc, s, dot), axis=-1)
        chosen = jnp.take_along_axis(ref, pick[..., None], axis=-1)[..., 0]
        return jnp.max(ref, axis=-1) - chosen

    out = [np.asarray(gaps(params, jnp.asarray(seqs[i:i + rows])))
           for i in range(0, len(seqs), rows)]
    return np.concatenate(out).astype(np.float64)
