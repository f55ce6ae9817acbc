"""Plain Qwen3 decoder: float32 at full matmul precision, no kernels.

Follows Qwen3's published layer (RMSNorm, grouped-query attention with a
per-head RMSNorm on queries and keys before rotary embedding, SwiGLU MLP,
tied embeddings). Departures, both of layout only: norm scales are stored as
offsets from 1 (``scale = 1 + w``), and weights are addressed by the leaf
names the benchmark's weight rule uses (``g0/s0/wq`` at a layer).

``prec="fp8"`` is the control: every matmul operand is rounded through
float8 e4m3 (one scale per tensor) before the float32 product, and the
gradient reaching each operand through float8 e5m2.
"""
from __future__ import annotations

from typing import Callable, Dict, List, Sequence, Tuple

import jax
import jax.numpy as jnp

HI = jax.lax.Precision.HIGHEST
LAYER = "g0/s0/"


def shapes(cfg: Dict) -> Tuple[Dict[str, Tuple], Dict[str, Tuple]]:
    D, H, KV, hd = (cfg["hidden_size"], cfg["num_attention_heads"],
                    cfg["num_key_value_heads"], cfg["head_dim"])
    F, V = cfg["intermediate_size"], cfg["vocab_size"]
    glob = {"embed/tok": (V, D), "final_norm": (D,)}
    layer = {"ln1": (D,), "wq": (D, H * hd), "wk": (D, KV * hd),
             "wv": (D, KV * hd), "wo": (H * hd, D), "q_norm": (hd,),
             "k_norm": (hd,), "ln2": (D,), "w_gate": (D, F), "w_up": (D, F),
             "w_down": (F, D)}
    return glob, layer


def _round(a: jax.Array, dtype, top: float) -> jax.Array:
    s = jnp.maximum(jnp.max(jnp.abs(a)), 1e-30) / top
    return (a / s).astype(dtype).astype(jnp.float32) * s


@jax.custom_vjp
def q8(a: jax.Array) -> jax.Array:
    """``a`` rounded through float8 e4m3 (one scale per tensor). Its
    gradient passes straight through, rounded through float8 e5m2."""
    return _round(a, jnp.float8_e4m3fn, 448.0)


q8.defvjp(lambda a: (q8(a), None),
          lambda _, g: (_round(g, jnp.float8_e5m2, 57344.0),))


def mm(x, w, prec: str):
    if prec == "fp8":
        x, w = q8(x), q8(w)
    return jnp.matmul(x, w, precision=HI)


def rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * (1 + w)


def rope(x, pos, theta):
    hd = x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = pos[:, None].astype(jnp.float32) * inv[None, :]
    sin, cos = jnp.sin(ang)[:, None, :], jnp.cos(ang)[:, None, :]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def layer(p: Dict, x: jax.Array, cfg: Dict, prec: str) -> jax.Array:
    """One decoder layer over x [B, S, D] at positions 0..S-1, causal."""
    B, S, D = x.shape
    H, KV, hd = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                 cfg["head_dim"])
    eps, pos = cfg["rms_norm_eps"], jnp.arange(S)
    h = rms(x, p["ln1"], eps)
    q = rms(mm(h, p["wq"], prec).reshape(B, S, H, hd), p["q_norm"], eps)
    k = rms(mm(h, p["wk"], prec).reshape(B, S, KV, hd), p["k_norm"], eps)
    v = mm(h, p["wv"], prec).reshape(B, S, KV, hd)
    q, k = rope(q, pos, cfg["rope_theta"]), rope(k, pos, cfg["rope_theta"])
    k, v = (jnp.repeat(a, H // KV, axis=2) for a in (k, v))
    if prec == "fp8":
        q, k, v = q8(q), q8(k), q8(v)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k, precision=HI) / jnp.sqrt(
        jnp.float32(hd))
    s = jnp.where(jnp.tril(jnp.ones((S, S), bool)), s, -jnp.inf)
    a = jax.nn.softmax(s, axis=-1)
    if prec == "fp8":
        a = q8(a)
    o = jnp.einsum("bhqk,bkhd->bqhd", a, v, precision=HI)
    x = x + mm(o.reshape(B, S, H * hd), p["wo"], prec)
    h = rms(x, p["ln2"], eps)
    up = jax.nn.silu(mm(h, p["w_gate"], prec)) * mm(h, p["w_up"], prec)
    return x + mm(up, p["w_down"], prec)


def head(params_glob: Dict, x: jax.Array, cfg: Dict, prec: str) -> jax.Array:
    x = rms(x, params_glob["final_norm"], cfg["rms_norm_eps"])
    return mm(x, params_glob["embed/tok"].T, prec)


# --------------------------------------------------------------------------- #
# Training: the whole model at once                                            #
# --------------------------------------------------------------------------- #
def init(get: Callable, cfg: Dict) -> Dict:
    glob, lay = shapes(cfg)
    return {"glob": {k: get(k, 0, s) for k, s in glob.items()},
            "layers": [{k: get(LAYER + k, l, s) for k, s in lay.items()}
                       for l in range(cfg["num_hidden_layers"])]}


def keys(params: Dict) -> Dict[str, jax.Array]:
    """Leaf key (``path`` or ``path@layer``) -> array, as the program's
    stacked leaves split per layer."""
    out = dict(params["glob"])
    for l, lp in enumerate(params["layers"]):
        out.update({f"{LAYER}{k}@{l}": v for k, v in lp.items()})
    return out


def loss(params: Dict, tokens: jax.Array, labels: jax.Array, cfg: Dict,
         prec: str) -> jax.Array:
    x = jnp.take(params["glob"]["embed/tok"], tokens, axis=0)
    for lp in params["layers"]:
        x = jax.checkpoint(lambda p, x: layer(p, x, cfg, prec))(lp, x)
    logits = head(params["glob"], x, cfg, prec)
    lse = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels[..., None], -1)[..., 0]
    return jnp.mean(lse - gold)


# --------------------------------------------------------------------------- #
# Serving: layer by layer, so that 36 float32 layers need not fit at once      #
# --------------------------------------------------------------------------- #
def served_logits(get: Callable, cfg: Dict, seqs: Sequence[jax.Array],
                  rows: Sequence[Tuple[int, int]], prec: str, ctx: int
                  ) -> List[jax.Array]:
    """Logits [n_i, vocab] at positions ``rows[i] = (start, n_i)`` of each
    token sequence, each padded to ``ctx`` (causal: padding is never seen)."""
    glob, lay = shapes(cfg)
    toks = jnp.stack([jnp.pad(s, (0, ctx - s.shape[0])) for s in seqs])
    embed = get("embed/tok", 0, glob["embed/tok"])
    x = jnp.take(embed, toks, axis=0)
    step = jax.jit(lambda p, x: layer(p, x, cfg, prec))
    for l in range(cfg["num_hidden_layers"]):
        x = step({k: get(LAYER + k, l, s) for k, s in lay.items()}, x)
    g = {"embed/tok": embed, "final_norm": get("final_norm", 0, glob["final_norm"])}
    out_fn = jax.jit(lambda g, h: head(g, h, cfg, prec))
    return [out_fn(g, x[i, a:a + n]) for i, (a, n) in enumerate(rows)]
