"""Plain RWKV-6 ("Finch", arXiv:2404.05892): float32 at full matmul
precision, the WKV recurrence as a sequential scan, no kernels.

Per layer: time mixing with data-dependent token shift (five low-rank
lerps sharing one down-projection) and data-dependent decay
``w_t = exp(-exp(w0 + tanh(x_w A) B))``, the WKV state update

    y_t = (S + (u * k_t) v_t^T)^T r_t,    S <- diag(w_t) S + k_t v_t^T,

a per-head norm of y, the SiLU gate and the output projection; then
channel mixing (squared ReLU under a sigmoid receptance gate). Departures
from the paper, as in the program under test: RMSNorm (offset-from-1
scales) in place of LayerNorm, and the per-head group norm is an RMSNorm.

``prec="fp8"`` is the control: matmul operands rounded through float8
e4m3, their gradients through e5m2 (``qwen3.q8``).
"""
from __future__ import annotations

from typing import Callable, Dict, Tuple

import jax
import jax.numpy as jnp

from qwen3 import HI, mm, q8, rms  # noqa: F401  (shared plain helpers)

LAYER = "g0/s0/"
NAMES = ("r", "k", "v", "g", "w")


def shapes(cfg: Dict) -> Tuple[Dict[str, Tuple], Dict[str, Tuple]]:
    D, F, V = cfg["hidden_size"], cfg["intermediate_size"], cfg["vocab_size"]
    Dr = cfg["num_attention_heads"] * cfg["head_size"]
    mix, dec = cfg["time_mix_extra_dim"], cfg["time_decay_extra_dim"]
    glob = {"embed/tok": (V, D), "lm_head": (D, V), "final_norm": (D,)}
    layer = {"ln1": (D,), "dd_a": (D, mix), "w_r": (D, Dr), "w_k": (D, Dr),
             "w_v": (D, Dr), "w_g": (D, Dr), "w0": (Dr,), "wd_a": (D, dec),
             "wd_b": (dec, Dr), "u": (Dr,), "ln_x": (Dr,), "w_o": (Dr, D),
             "ln2": (D,), "mu_k2": (D,), "mu_r2": (D,), "w_in": (D, F),
             "w_out": (F, D), "w_rgate": (D, D)}
    for n in NAMES:
        layer[f"mu_{n}"] = (D,)
        layer[f"dd_b_{n}"] = (mix, D)
    return glob, layer


def _shift(x):
    return jnp.concatenate([jnp.zeros_like(x[:, :1]), x[:, :-1]], axis=1)


def wkv(r, k, v, w, u):
    """r, k, v, w: [B, T, H, hd]; u: [H, hd] -> y [B, T, H, hd]."""
    B, T, H, hd = r.shape

    def step(s, inp):
        rt, kt, vt, wt = inp
        kv = kt[..., :, None] * vt[..., None, :]
        y = jnp.einsum("bhkv,bhk->bhv", s + u[..., :, None] * kv, rt,
                       precision=HI)
        return wt[..., :, None] * s + kv, y

    s0 = jnp.zeros((B, H, hd, hd), jnp.float32)
    xs = tuple(a.transpose(1, 0, 2, 3) for a in (r, k, v, w))
    _, ys = jax.lax.scan(step, s0, xs)
    return ys.transpose(1, 0, 2, 3)


def layer(p: Dict, x: jax.Array, cfg: Dict, prec: str) -> jax.Array:
    B, T, D = x.shape
    H, hd = cfg["num_attention_heads"], cfg["head_size"]
    eps = cfg["rms_norm_eps"]
    h = rms(x, p["ln1"], eps)
    hp = _shift(h)
    mixed = {}
    for n in NAMES:
        base = hp + (h - hp) * p[f"mu_{n}"]
        mix = p[f"mu_{n}"] + mm(jnp.tanh(mm(base, p["dd_a"], prec)),
                                p[f"dd_b_{n}"], prec)
        mixed[n] = hp + (h - hp) * mix
    r = mm(mixed["r"], p["w_r"], prec).reshape(B, T, H, hd)
    k = mm(mixed["k"], p["w_k"], prec).reshape(B, T, H, hd)
    v = mm(mixed["v"], p["w_v"], prec).reshape(B, T, H, hd)
    g = jax.nn.silu(mm(mixed["g"], p["w_g"], prec))
    w_raw = p["w0"] + mm(jnp.tanh(mm(mixed["w"], p["wd_a"], prec)),
                         p["wd_b"], prec)
    w = jnp.exp(-jnp.exp(w_raw)).reshape(B, T, H, hd)
    y = wkv(r, k, v, w, p["u"].reshape(H, hd))
    y = rms(y, p["ln_x"].reshape(H, hd), 1e-5).reshape(B, T, H * hd) * g
    x = x + mm(y, p["w_o"], prec)
    h = rms(x, p["ln2"], eps)
    hp = _shift(h)
    xk = hp + (h - hp) * p["mu_k2"]
    xr = hp + (h - hp) * p["mu_r2"]
    gate = jax.nn.sigmoid(mm(xr, p["w_rgate"], prec))
    hidden = jnp.square(jax.nn.relu(mm(xk, p["w_in"], prec)))
    return x + gate * mm(hidden, p["w_out"], prec)


def init(get: Callable, cfg: Dict) -> Dict:
    glob, lay = shapes(cfg)
    return {"glob": {k: get(k, 0, s) for k, s in glob.items()},
            "layers": [{k: get(LAYER + k, l, s) for k, s in lay.items()}
                       for l in range(cfg["num_hidden_layers"])]}


def keys(params: Dict) -> Dict[str, jax.Array]:
    out = dict(params["glob"])
    for l, lp in enumerate(params["layers"]):
        out.update({f"{LAYER}{k}@{l}": v for k, v in lp.items()})
    return out


def loss(params: Dict, tokens: jax.Array, labels: jax.Array, cfg: Dict,
         prec: str) -> jax.Array:
    x = jnp.take(params["glob"]["embed/tok"], tokens, axis=0)
    for lp in params["layers"]:
        x = jax.checkpoint(lambda p, x: layer(p, x, cfg, prec))(lp, x)
    x = rms(x, params["glob"]["final_norm"], cfg["rms_norm_eps"])
    logits = mm(x, params["glob"]["lm_head"], prec)
    lse = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels[..., None], -1)[..., 0]
    return jnp.mean(lse - gold)
