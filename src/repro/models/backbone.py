"""The shared decoder backbone: one substrate, ten architectures.

Every architecture is a stack of *segments* (``LayerGroup``); each segment is
``lax.scan``-ned over its repeat axis with stacked parameters, so the HLO is
small and compile times stay flat in depth. Heterogeneous patterns (gemma2's
local/global alternation, recurrentgemma's rec-rec-local blocks, whisper's
enc/dec split) are homogeneous *within* a scan body by construction.

Entry points:

* ``loss_fn(params, batch)``      — training loss (causal LM / enc-dec LM)
* ``forward(params, batch)``      — logits at every position (+ MoE aux)
* ``prefill(params, batch)``      — run the context, return last-token logits
  plus a filled decode cache
* ``decode_step(params, cache, tokens)`` — one token with a KV/state cache

The backbone is mesh-agnostic: distribution enters only through the
``sharder`` callback (activation sharding constraints) and the
:class:`~repro.models.partition.PartitionPlan` (TP padding/replication).
"""
from __future__ import annotations

import functools
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from . import rglru as rg
from . import rwkv6 as rw
from .attention import flash_attention_jnp
from .common import (dense_init, embed_init, rms_norm, softcap,
                     stable_cross_entropy)
from .config import LayerGroup, ModelConfig
from .ffn import gated_mlp, moe_mlp
from .partition import IDENTITY_PLAN, PartitionPlan

Params = Dict[str, Any]
AUX_COEF = 0.01
_RWKV_LORA = 64

#: What a checkpointed layer keeps for the backward, by name. "dots": the
#: outputs of its matmuls with no batch dims (its projections) that the
#: backward reads, so it recomputes none of them; "full": only the layer's
#: input, so the backward recomputes the whole layer. The trainer tries them
#: in this order and keeps the first whose compiled step fits the device
#: (``repro.runtime.steps.compile_train_step``).
REMAT_POLICIES = {
    "dots": jax.checkpoint_policies.dots_with_no_batch_dims_saveable,
    "full": None,
}


def _no_shard(x: jax.Array, name: str) -> jax.Array:
    return x


def _ring_fill(vals: jax.Array, C: int, axis: int, fill) -> jax.Array:
    """A ring buffer of ``C`` slots along ``axis`` holding the last
    ``min(C, S)`` of ``vals``' ``S`` positions, position ``p`` in slot
    ``p % C`` and ``fill`` elsewhere. Built by pad and roll, not a scatter:
    the TPU compiler aborts on the scatter pair it would fuse for k and v."""
    S = vals.shape[axis]
    n = min(C, S)
    vals = jax.lax.slice_in_dim(vals, S - n, S, axis=axis)
    pad = [(0, 0)] * vals.ndim
    pad[axis] = (0, C - n)
    return jnp.roll(jnp.pad(vals, pad, constant_values=fill), (S - n) % C,
                    axis=axis)


class Backbone:
    def __init__(self, cfg: ModelConfig, plan: PartitionPlan = IDENTITY_PLAN,
                 *, compute_dtype=jnp.bfloat16, param_dtype=jnp.float32,
                 remat: bool = True,
                 sharder: Callable[[jax.Array, str], jax.Array] = _no_shard,
                 param_gather: Optional[Callable[[Params], Params]] = None,
                 moe_impl: str = "gspmd",
                 mesh=None, dp_axes: Tuple[str, ...] = ()):
        plan.check(cfg)
        self.cfg = cfg
        self.plan = plan
        self.compute_dtype = compute_dtype
        self.param_dtype = param_dtype
        self.remat = remat
        self.shard = sharder
        self.param_gather = param_gather
        self.moe_impl = moe_impl
        self.mesh = mesh
        self.dp_axes = dp_axes
        if moe_impl == "ep" and cfg.ffn_kind == "moe":
            from .moe_ep import virtualization
            self.moe_V, self.moe_split = virtualization(cfg, plan.tp)
        else:
            self.moe_V, self.moe_split = cfg.n_experts, 1
        self.H = plan.eff_heads(cfg)
        self.KV = plan.eff_kv_heads(cfg)
        self.hd = cfg.hd
        self.Vp = plan.eff_vocab(cfg)
        self.rwkv_H = plan.eff_rwkv_heads(cfg)
        self.W = cfg.rglru_width or cfg.d_model

    # ------------------------------------------------------------------ #
    # Parameter construction                                             #
    # ------------------------------------------------------------------ #
    def _leaf_specs(self, kind: str) -> Dict[str, Tuple[Tuple[int, ...], str]]:
        cfg = self.cfg
        D, F = cfg.d_model, cfg.d_ff
        H, KV, hd = self.H, self.KV, self.hd
        specs: Dict[str, Tuple[Tuple[int, ...], str]] = {}

        def attn(prefix: str = "") -> None:
            specs[f"{prefix}wq"] = ((D, H * hd), "dense")
            specs[f"{prefix}wk"] = ((D, KV * hd), "dense")
            specs[f"{prefix}wv"] = ((D, KV * hd), "dense")
            specs[f"{prefix}wo"] = ((H * hd, D), "dense")
            if cfg.qkv_bias:
                specs[f"{prefix}bq"] = ((H * hd,), "zero")
                specs[f"{prefix}bk"] = ((KV * hd,), "zero")
                specs[f"{prefix}bv"] = ((KV * hd,), "zero")
            if cfg.qk_norm:
                specs[f"{prefix}q_norm"] = ((hd,), "zero")
                specs[f"{prefix}k_norm"] = ((hd,), "zero")

        def dense_ffn() -> None:
            specs["ln2"] = ((D,), "zero")
            if cfg.ffn_kind in ("swiglu", "geglu"):
                specs["w_gate"] = ((D, F), "dense")
                specs["w_up"] = ((D, F), "dense")
                specs["w_down"] = ((F, D), "dense")
            else:  # gelu (whisper)
                specs["w_gate"] = ((D, F), "dense")
                specs["b_gate"] = ((F,), "zero")
                specs["w_down"] = ((F, D), "dense")
                specs["b_down"] = ((D,), "zero")

        def moe_ffn() -> None:
            E, Fe = cfg.n_experts, cfg.moe_d_ff or F
            # EP path stores VIRTUALIZED experts [V, D, Fe/split] (an exact
            # column split; see moe_ep.py) so the expert dim always shards
            V, split = self.moe_V, self.moe_split
            Fv = Fe // split
            specs["ln2"] = ((D,), "zero")
            specs["router"] = ((D, E), "dense")
            specs["w_gate"] = ((V, D, Fv), "dense")
            specs["w_up"] = ((V, D, Fv), "dense")
            specs["w_down"] = ((V, Fv, D), "dense")

        if kind in ("attn", "local", "enc"):
            specs["ln1"] = ((D,), "zero")
            attn()
            moe_ffn() if cfg.ffn_kind == "moe" else dense_ffn()
        elif kind == "dec":
            specs["ln1"] = ((D,), "zero")
            attn()
            specs["ln_cross"] = ((D,), "zero")
            attn("c_")
            dense_ffn()
        elif kind == "rwkv":
            Hr, hdr = self.rwkv_H, cfg.rwkv_head_dim
            Dr = Hr * hdr
            r = _RWKV_LORA
            specs["ln1"] = ((D,), "zero")
            for n in ("r", "k", "v", "g", "w"):
                specs[f"mu_{n}"] = ((D,), "zero")
                specs[f"dd_b_{n}"] = ((32, D), "zero")
            specs["dd_a"] = ((D, 32), "dense")
            specs["w_r"] = ((D, Dr), "dense")
            specs["w_k"] = ((D, Dr), "dense")
            specs["w_v"] = ((D, Dr), "dense")
            specs["w_g"] = ((D, Dr), "dense")
            specs["w0"] = ((Dr,), "zero")
            specs["wd_a"] = ((D, r), "dense")
            specs["wd_b"] = ((r, Dr), "zero")
            specs["u"] = ((Dr,), "zero")
            specs["ln_x"] = ((Dr,), "zero")
            specs["w_o"] = ((Dr, D), "dense")
            specs["ln2"] = ((D,), "zero")
            specs["mu_k2"] = ((D,), "zero")
            specs["mu_r2"] = ((D,), "zero")
            specs["w_in"] = ((D, F), "dense")
            specs["w_out"] = ((F, D), "dense")
            specs["w_rgate"] = ((D, D), "dense")
        elif kind == "rec":
            W = self.W
            NB = cfg.n_heads  # gate blocks
            wb = W // NB
            specs["ln1"] = ((D,), "zero")
            specs["w_in"] = ((D, W), "dense")
            specs["w_gate_branch"] = ((D, W), "dense")
            specs["conv_w"] = ((cfg.conv1d_width, W), "dense")
            specs["conv_b"] = ((W,), "zero")
            specs["gw_a"] = ((NB, wb, wb), "dense")
            specs["gb_a"] = ((W,), "zero")
            specs["gw_x"] = ((NB, wb, wb), "dense")
            specs["gb_x"] = ((W,), "zero")
            specs["a_log"] = ((W,), "lru")
            specs["w_out"] = ((W, D), "dense")
            dense_ffn()
        else:  # pragma: no cover
            raise ValueError(f"unknown layer kind {kind!r}")
        return specs

    def _init_leaf(self, key, shape, kind_init):
        if kind_init == "zero":
            return jnp.zeros(shape, self.param_dtype)
        if kind_init == "lru":
            # Λ init so decay a ∈ (0.9, 0.999) roughly
            import numpy as np
            u = jax.random.uniform(key, shape, jnp.float32, 0.05, 0.6)
            return jnp.log(jnp.expm1(u)).astype(self.param_dtype)  # inv-softplus
        return dense_init(key, shape, dtype=self.param_dtype)

    def init(self, key: jax.Array) -> Params:
        cfg = self.cfg
        params: Params = {}
        key, ek = jax.random.split(key)
        params["embed"] = {"tok": embed_init(ek, (self.Vp, cfg.d_model),
                                             self.param_dtype)}
        if cfg.is_enc_dec:
            key, pk = jax.random.split(key)
            params["embed"]["enc_pos"] = embed_init(
                pk, (cfg.enc_seq, cfg.d_model), self.param_dtype)
        if not cfg.tie_embeddings:
            key, hk = jax.random.split(key)
            params["lm_head"] = dense_init(hk, (cfg.d_model, self.Vp),
                                           dtype=self.param_dtype)
        params["final_norm"] = jnp.zeros((cfg.d_model,), self.param_dtype)
        for gi, group in enumerate(cfg.groups):
            gp: Dict[str, Any] = {}
            for si, kind in enumerate(group.pattern):
                sub: Dict[str, Any] = {}
                for name, (shape, init_kind) in self._leaf_specs(kind).items():
                    key, lk = jax.random.split(key)
                    sub[name] = self._init_leaf(lk, (group.repeat,) + shape,
                                                init_kind)
                gp[f"s{si}"] = sub
            params[f"g{gi}"] = gp
        return params

    def param_specs(self) -> Params:
        """ShapeDtypeStruct tree (no allocation) for AOT lowering."""
        return jax.eval_shape(lambda k: self.init(k), jax.random.PRNGKey(0))

    # ------------------------------------------------------------------ #
    # Sublayer forward functions                                          #
    # ------------------------------------------------------------------ #
    def _qkv(self, p, h, prefix: str = ""):
        cfg = self.cfg
        B, S, _ = h.shape
        q = h @ p[f"{prefix}wq"]
        k = h @ p[f"{prefix}wk"]
        v = h @ p[f"{prefix}wv"]
        if cfg.qkv_bias:
            q = q + p[f"{prefix}bq"]
            k = k + p[f"{prefix}bk"]
            v = v + p[f"{prefix}bv"]
        q = self.shard(q, "act_heads").reshape(B, S, self.H, self.hd)
        k = k.reshape(B, S, self.KV, self.hd)
        v = v.reshape(B, S, self.KV, self.hd)
        if cfg.qk_norm:
            q = rms_norm(q, p[f"{prefix}q_norm"], cfg.norm_eps)
            k = rms_norm(k, p[f"{prefix}k_norm"], cfg.norm_eps)
        return q, k, v

    def _attn_sublayer(self, p, x, kind: str, positions) -> jax.Array:
        """Self-attention residual branch (train/prefill path)."""
        cfg = self.cfg
        B, S, D = x.shape
        h = rms_norm(x, p["ln1"], cfg.norm_eps)
        q, k, v = self._qkv(p, h)
        if kind != "enc":
            from .common import apply_rope
            q = apply_rope(q, positions, cfg.rope_theta, cfg.rotary_pct)
            k = apply_rope(k, positions, cfg.rope_theta, cfg.rotary_pct)
        window = cfg.attn_window if kind == "local" else None
        o = flash_attention_jnp(
            q, k, v, causal=(kind != "enc"), window=window,
            logit_cap=cfg.attn_logit_softcap,
            q_positions=positions, kv_positions=positions)
        o = o.reshape(B, S, self.H * self.hd) @ p["wo"]
        return self.shard(o, "act_hidden")

    def _cross_sublayer(self, p, x, enc_kv) -> jax.Array:
        cfg = self.cfg
        B, S, D = x.shape
        h = rms_norm(x, p["ln_cross"], cfg.norm_eps)
        q = (h @ p["c_wq"])
        if cfg.qkv_bias:
            q = q + p["c_bq"]
        q = q.reshape(B, S, self.H, self.hd)
        if cfg.qk_norm:
            q = rms_norm(q, p["c_q_norm"], cfg.norm_eps)
        ck, cv = enc_kv
        o = flash_attention_jnp(q, ck, cv, causal=False)
        return o.reshape(B, S, self.H * self.hd) @ p["c_wo"]

    def _ffn_sublayer(self, p, x) -> Tuple[jax.Array, jax.Array]:
        cfg = self.cfg
        h = rms_norm(x, p["ln2"], cfg.norm_eps)
        if cfg.ffn_kind == "moe":
            if self.moe_impl == "ep":
                from .moe_ep import moe_mlp_ep
                y, aux = moe_mlp_ep(p, h, cfg, self.mesh, self.dp_axes)
            else:
                y, aux = moe_mlp(p, h, cfg, self.shard)
        else:
            y, aux = gated_mlp(p, h, cfg.ffn_kind), jnp.zeros((), jnp.float32)
        return self.shard(y, "act_hidden"), aux

    # -- full layer bodies (train/prefill) -------------------------------------
    def _layer_fwd(self, p, x, kind: str, positions, enc_kv=None
                   ) -> Tuple[jax.Array, jax.Array]:
        """Returns (x, aux_loss). Stateless path (no cache)."""
        cfg = self.cfg
        if kind == "rwkv":
            B, _, D = x.shape
            h = rms_norm(x, p["ln1"], cfg.norm_eps)
            shift0 = jnp.zeros((B, D), x.dtype)
            wkv0 = jnp.zeros((B, self.rwkv_H, cfg.rwkv_head_dim,
                              cfg.rwkv_head_dim), jnp.float32)
            y, _, _ = rw.time_mix(p, h, shift0, wkv0, self.rwkv_H,
                                  cfg.rwkv_head_dim)
            x = x + y.astype(x.dtype)
            h = rms_norm(x, p["ln2"], cfg.norm_eps)
            y, _ = rw.channel_mix(
                {"mu_k": p["mu_k2"], "mu_r": p["mu_r2"], "w_in": p["w_in"],
                 "w_out": p["w_out"], "w_rgate": p["w_rgate"]},
                h, jnp.zeros((B, D), x.dtype))
            return x + y, jnp.zeros((), jnp.float32)
        if kind == "rec":
            B, _, D = x.shape
            h = rms_norm(x, p["ln1"], cfg.norm_eps)
            conv0 = jnp.zeros((B, cfg.conv1d_width - 1, self.W), x.dtype)
            h0 = jnp.zeros((B, self.W), jnp.float32)
            y, _, _ = self._rglru_apply(p, h, conv0, h0)
            x = x + y.astype(x.dtype)
            y, aux = self._ffn_sublayer(p, x)
            return x + y, aux
        # attention-family kinds
        x = x + self._attn_sublayer(p, x, kind, positions)
        if kind == "dec":
            x = x + self._cross_sublayer(p, x, enc_kv)
        y, aux = self._ffn_sublayer(p, x)
        return x + y, aux

    def _rglru_apply(self, p, h, conv_state, h_state):
        """Griffin recurrent block with block-diagonal gates."""
        cfg = self.cfg
        NB = cfg.n_heads
        W = self.W
        wb = W // NB
        branch = h @ p["w_in"]
        gate = jax.nn.gelu(h @ p["w_gate_branch"])
        branch, conv_state = rg.causal_conv1d(p, branch, conv_state)
        bb = branch.reshape(*branch.shape[:-1], NB, wb)
        r = jax.nn.sigmoid(
            jnp.einsum("...nw,nwv->...nv", bb, p["gw_a"]).reshape(branch.shape)
            + p["gb_a"])
        i = jax.nn.sigmoid(
            jnp.einsum("...nw,nwv->...nv", bb, p["gw_x"]).reshape(branch.shape)
            + p["gb_x"])
        from repro.kernels import ops as kops
        y, h_state = kops.rglru_scan(branch, p["a_log"], r, i, h_state)
        y = y.astype(h.dtype) * gate
        return y @ p["w_out"], conv_state, h_state

    # ------------------------------------------------------------------ #
    # Training forward / loss                                             #
    # ------------------------------------------------------------------ #
    def _embed_tokens(self, params, tokens) -> jax.Array:
        cfg = self.cfg
        x = jnp.take(params["embed"]["tok"], tokens, axis=0)
        x = x.astype(self.compute_dtype)
        return x * jnp.sqrt(jnp.asarray(cfg.d_model, self.compute_dtype)) \
            if cfg.embed_scale else x

    def _logits(self, params, x) -> jax.Array:
        cfg = self.cfg
        x = rms_norm(x, params["final_norm"].astype(self.compute_dtype),
                     cfg.norm_eps)
        head = (params["embed"]["tok"].T if cfg.tie_embeddings
                else params["lm_head"]).astype(self.compute_dtype)
        logits = x @ head
        logits = self.shard(logits, "logits")
        if self.Vp != cfg.vocab:  # mask padded vocab columns
            mask = jnp.arange(self.Vp) < cfg.vocab
            logits = jnp.where(mask, logits, -1e30)
        return logits

    def _cast_group(self, gp):
        out = jax.tree_util.tree_map(
            lambda a: a.astype(self.compute_dtype)
            if a.dtype in (jnp.float32, jnp.bfloat16, jnp.float16) else a, gp)
        if self.param_gather is not None:
            # per-layer weight all-gather (prefetch / early-release schedule)
            out = self.param_gather(out)
        return out

    def _checkpoint(self, fn, policy: str = "full"):
        """Wrap a scan body in jax.checkpoint under ``policy``, a name in
        :data:`REMAT_POLICIES`."""
        if not self.remat:
            return fn
        return jax.checkpoint(fn, policy=REMAT_POLICIES[policy])

    def _run_groups(self, params, x, positions, remat_policy, enc_kv=None):
        """Scan every segment; returns (x, total_aux)."""
        total_aux = jnp.zeros((), jnp.float32)
        for gi, group in enumerate(self.cfg.groups):
            gp = params[f"g{gi}"]

            def body(carry, layer_params, _kinds=group.pattern):
                h, aux = carry
                lp = self._cast_group(layer_params)
                for si, kind in enumerate(_kinds):
                    h, a = self._layer_fwd(lp[f"s{si}"], h, kind, positions,
                                           enc_kv)
                    aux = aux + a
                return (h, aux), None

            scan_body = self._checkpoint(body, remat_policy)
            (x, total_aux), _ = jax.lax.scan(
                scan_body, (x, total_aux), gp)
        return x, total_aux

    def _encode(self, params, frames, remat_policy) -> jax.Array:
        """Whisper encoder over precomputed (stub-frontend) frames."""
        cfg = self.cfg
        x = frames.astype(self.compute_dtype)
        x = x + params["embed"]["enc_pos"].astype(self.compute_dtype)
        positions = jnp.arange(x.shape[1], dtype=jnp.int32)
        total_aux = jnp.zeros((), jnp.float32)
        for gi, group in enumerate(cfg.groups):
            if "enc" not in group.pattern:
                continue
            gp = params[f"g{gi}"]

            def body(carry, layer_params, _kinds=group.pattern):
                h, aux = carry
                lp = self._cast_group(layer_params)
                for si, kind in enumerate(_kinds):
                    h, a = self._layer_fwd(lp[f"s{si}"], h, kind, positions)
                    aux = aux + a
                return (h, aux), None

            scan_body = self._checkpoint(body, remat_policy)
            (x, total_aux), _ = jax.lax.scan(scan_body, (x, total_aux), gp)
        return x

    def _decoder_groups(self):
        return [(gi, g) for gi, g in enumerate(self.cfg.groups)
                if "enc" not in g.pattern]

    def _run_decoder(self, params, x, positions, remat_policy,
                     enc_out=None):
        total_aux = jnp.zeros((), jnp.float32)
        enc_kv = None
        if enc_out is not None:
            enc_kv = enc_out  # per-layer cross kv computed inside sublayer
        for gi, group in self._decoder_groups():
            gp = params[f"g{gi}"]

            def body(carry, layer_params, _kinds=group.pattern):
                h, aux = carry
                lp = self._cast_group(layer_params)
                for si, kind in enumerate(_kinds):
                    ekv = None
                    if kind == "dec":
                        B, Se, D = enc_kv.shape
                        ck = (enc_kv @ lp[f"s{si}"]["c_wk"]).reshape(
                            B, Se, self.KV, self.hd)
                        cv = (enc_kv @ lp[f"s{si}"]["c_wv"]).reshape(
                            B, Se, self.KV, self.hd)
                        ekv = (ck, cv)
                    h, a = self._layer_fwd(lp[f"s{si}"], h, kind, positions,
                                           ekv)
                    aux = aux + a
                return (h, aux), None

            scan_body = self._checkpoint(body, remat_policy)
            (x, total_aux), _ = jax.lax.scan(scan_body, (x, total_aux), gp)
        return x, total_aux

    def loss_fn(self, params: Params, batch: Dict[str, jax.Array],
                remat_policy: str = "full") -> jax.Array:
        """The loss; with ``remat``, each layer is checkpointed under
        ``remat_policy``, a name in :data:`REMAT_POLICIES`."""
        logits, aux = self.forward(params, batch, remat_policy)
        loss = stable_cross_entropy(logits, batch["labels"],
                                    self.cfg.final_logit_softcap)
        return loss + AUX_COEF * aux

    def forward(self, params: Params, batch: Dict[str, jax.Array],
                remat_policy: str = "full") -> Tuple[jax.Array, jax.Array]:
        """Logits at every position [B, S, Vp] and the MoE aux loss."""
        cfg = self.cfg
        tokens = batch["tokens"]
        x = self._embed_tokens(params, tokens)
        x = self.shard(x, "act_hidden")
        positions = jnp.arange(tokens.shape[1], dtype=jnp.int32)
        if cfg.is_enc_dec:
            enc_out = self._encode(params, batch["enc_frames"], remat_policy)
            x, aux = self._run_decoder(params, x, positions, remat_policy,
                                       enc_out)
        else:
            x, aux = self._run_groups(params, x, positions, remat_policy)
        return self._logits(params, x), aux

    # ------------------------------------------------------------------ #
    # Serving: prefill + decode                                           #
    # ------------------------------------------------------------------ #
    def cache_len(self, kind: str, ctx: int) -> int:
        if kind == "local":
            return min(self.cfg.attn_window or ctx, ctx)
        return ctx

    def init_cache(self, B: int, ctx: int, dtype=None) -> Params:
        cfg = self.cfg
        dtype = dtype or self.compute_dtype
        cache: Params = {"pos": jnp.zeros((), jnp.int32)}
        for gi, group in self._decoder_groups():
            gc: Dict[str, Any] = {}
            R = group.repeat
            for si, kind in enumerate(group.pattern):
                if kind in ("attn", "local", "dec"):
                    C = self.cache_len(kind, ctx)
                    sub = {
                        "k": jnp.zeros((R, B, C, self.KV, self.hd), dtype),
                        "v": jnp.zeros((R, B, C, self.KV, self.hd), dtype),
                        "kpos": jnp.full((R, C), -1, jnp.int32),
                    }
                    if kind == "dec":
                        sub["ck"] = jnp.zeros((R, B, cfg.enc_seq, self.KV,
                                               self.hd), dtype)
                        sub["cv"] = jnp.zeros((R, B, cfg.enc_seq, self.KV,
                                               self.hd), dtype)
                elif kind == "rwkv":
                    sub = {
                        "shift1": jnp.zeros((R, B, cfg.d_model), dtype),
                        "wkv": jnp.zeros((R, B, self.rwkv_H,
                                          cfg.rwkv_head_dim,
                                          cfg.rwkv_head_dim), jnp.float32),
                        "shift2": jnp.zeros((R, B, cfg.d_model), dtype),
                    }
                elif kind == "rec":
                    sub = {
                        "conv": jnp.zeros((R, B, cfg.conv1d_width - 1, self.W),
                                          dtype),
                        "h": jnp.zeros((R, B, self.W), jnp.float32),
                    }
                else:
                    sub = {}
                gc[f"s{si}"] = sub
            cache[f"g{gi}"] = gc
        return cache

    def _layer_decode(self, p, x, kind: str, sub_cache, pos):
        """One-token step. x: [B,1,D]. Returns (x, new_sub_cache)."""
        cfg = self.cfg
        B = x.shape[0]
        if kind == "rwkv":
            h = rms_norm(x, p["ln1"], cfg.norm_eps)
            y, s1, wkv = rw.time_mix(p, h, sub_cache["shift1"],
                                     sub_cache["wkv"], self.rwkv_H,
                                     cfg.rwkv_head_dim)
            x = x + y.astype(x.dtype)
            h = rms_norm(x, p["ln2"], cfg.norm_eps)
            y, s2 = rw.channel_mix(
                {"mu_k": p["mu_k2"], "mu_r": p["mu_r2"], "w_in": p["w_in"],
                 "w_out": p["w_out"], "w_rgate": p["w_rgate"]},
                h, sub_cache["shift2"])
            x = x + y
            return x, {"shift1": s1, "wkv": wkv, "shift2": s2.astype(
                sub_cache["shift2"].dtype)}
        if kind == "rec":
            h = rms_norm(x, p["ln1"], cfg.norm_eps)
            y, conv, hs = self._rglru_apply(p, h, sub_cache["conv"],
                                            sub_cache["h"])
            x = x + y.astype(x.dtype)
            y, _ = self._ffn_sublayer(p, x)
            return x + y, {"conv": conv.astype(sub_cache["conv"].dtype),
                           "h": hs}
        # attention family
        from .common import apply_rope
        h = rms_norm(x, p["ln1"], cfg.norm_eps)
        q, k, v = self._qkv(p, h)
        posv = pos[None].astype(jnp.int32) if pos.ndim == 0 else pos
        q = apply_rope(q, posv, cfg.rope_theta, cfg.rotary_pct)
        k = apply_rope(k, posv, cfg.rope_theta, cfg.rotary_pct)
        C = sub_cache["k"].shape[1]  # [B, C, KV, hd] after scan slicing
        slot = pos % C
        ck = sub_cache["k"].astype(x.dtype).at[:, slot].set(k[:, 0])
        cv = sub_cache["v"].astype(x.dtype).at[:, slot].set(v[:, 0])
        kpos = sub_cache["kpos"].at[slot].set(pos.astype(jnp.int32))
        window = cfg.attn_window if kind == "local" else None
        o = flash_attention_jnp(
            q, ck, cv, causal=True, window=window,
            logit_cap=cfg.attn_logit_softcap,
            q_positions=posv, kv_positions=kpos,
            q_chunk=1, kv_chunk=max(1024, min(4096, C)))
        o = o.reshape(B, 1, self.H * self.hd) @ p["wo"]
        x = x + self.shard(o, "act_hidden")
        new_sub = {"k": ck.astype(sub_cache["k"].dtype),
                   "v": cv.astype(sub_cache["v"].dtype), "kpos": kpos}
        if kind == "dec":
            h = rms_norm(x, p["ln_cross"], cfg.norm_eps)
            q = (h @ p["c_wq"]).reshape(B, 1, self.H, self.hd)
            if cfg.qk_norm:
                q = rms_norm(q, p["c_q_norm"], cfg.norm_eps)
            o = flash_attention_jnp(q, sub_cache["ck"].astype(x.dtype),
                                    sub_cache["cv"].astype(x.dtype),
                                    causal=False, q_chunk=1)
            x = x + (o.reshape(B, 1, self.H * self.hd) @ p["c_wo"])
            new_sub["ck"] = sub_cache["ck"]
            new_sub["cv"] = sub_cache["cv"]
        y, _ = self._ffn_sublayer(p, x)
        return x + y, new_sub

    def decode_step(self, params: Params, cache: Params, tokens: jax.Array
                    ) -> Tuple[jax.Array, Params]:
        """tokens: [B, 1] -> (logits [B, 1, Vp], new cache)."""
        pos = cache["pos"]
        x = self._embed_tokens(params, tokens)
        new_cache: Params = {"pos": pos + 1}
        for gi, group in self._decoder_groups():
            gp = params[f"g{gi}"]
            gc = cache[f"g{gi}"]

            def body(carry, xs, _kinds=group.pattern):
                h = carry
                layer_params, layer_cache = xs
                lp = self._cast_group(layer_params)
                new_lc = {}
                for si, kind in enumerate(_kinds):
                    h, nc = self._layer_decode(lp[f"s{si}"], h, kind,
                                               layer_cache[f"s{si}"], pos)
                    new_lc[f"s{si}"] = nc
                return h, new_lc

            x, ngc = jax.lax.scan(body, x, (gp, gc))
            new_cache[f"g{gi}"] = ngc
        logits = self._logits(params, x)
        return logits, new_cache

    def prefill(self, params: Params, batch: Dict[str, jax.Array], ctx: int
                ) -> Tuple[jax.Array, Params]:
        """Run the full context; return (last-token logits, filled cache)."""
        cfg = self.cfg
        tokens = batch["tokens"]
        B, S = tokens.shape
        x = self._embed_tokens(params, tokens)
        positions = jnp.arange(S, dtype=jnp.int32)
        enc_out = None
        if cfg.is_enc_dec:
            enc_out = self._encode(params, batch["enc_frames"], "full")
        new_cache: Params = {"pos": jnp.asarray(S, jnp.int32)}
        for gi, group in self._decoder_groups():
            gp = params[f"g{gi}"]

            def body(h, layer_params, _kinds=group.pattern):
                lp = self._cast_group(layer_params)
                lc = {}
                for si, kind in enumerate(_kinds):
                    if kind in ("attn", "local", "dec"):
                        # recompute k/v to fill the cache for this layer
                        hh = rms_norm(h, lp[f"s{si}"]["ln1"], cfg.norm_eps)
                        _, k, v = self._qkv(lp[f"s{si}"], hh)
                        from .common import apply_rope
                        k = apply_rope(k, positions, cfg.rope_theta,
                                       cfg.rotary_pct)
                        C = self.cache_len(kind, ctx)
                        # v without rope
                        sub = {"k": _ring_fill(k.astype(x.dtype), C, 1, 0),
                               "v": _ring_fill(v.astype(x.dtype), C, 1, 0),
                               "kpos": _ring_fill(positions, C, 0, -1)}
                        ekv = None
                        if kind == "dec":
                            Se = enc_out.shape[1]
                            eck = (enc_out @ lp[f"s{si}"]["c_wk"]).reshape(
                                B, Se, self.KV, self.hd)
                            ecv = (enc_out @ lp[f"s{si}"]["c_wv"]).reshape(
                                B, Se, self.KV, self.hd)
                            sub["ck"], sub["cv"] = eck, ecv
                            ekv = (eck, ecv)
                        h, _ = self._layer_fwd(lp[f"s{si}"], h, kind,
                                               positions, ekv)
                        lc[f"s{si}"] = sub
                    elif kind == "rwkv":
                        hh = rms_norm(h, lp[f"s{si}"]["ln1"], cfg.norm_eps)
                        shift0 = jnp.zeros((B, cfg.d_model), h.dtype)
                        wkv0 = jnp.zeros((B, self.rwkv_H, cfg.rwkv_head_dim,
                                          cfg.rwkv_head_dim), jnp.float32)
                        y, s1, wkv = rw.time_mix(lp[f"s{si}"], hh, shift0,
                                                 wkv0, self.rwkv_H,
                                                 cfg.rwkv_head_dim)
                        h = h + y.astype(h.dtype)
                        hh = rms_norm(h, lp[f"s{si}"]["ln2"], cfg.norm_eps)
                        y, s2 = rw.channel_mix(
                            {"mu_k": lp[f"s{si}"]["mu_k2"],
                             "mu_r": lp[f"s{si}"]["mu_r2"],
                             "w_in": lp[f"s{si}"]["w_in"],
                             "w_out": lp[f"s{si}"]["w_out"],
                             "w_rgate": lp[f"s{si}"]["w_rgate"]},
                            hh, jnp.zeros((B, cfg.d_model), h.dtype))
                        h = h + y
                        lc[f"s{si}"] = {"shift1": s1, "wkv": wkv,
                                        "shift2": s2.astype(h.dtype)}
                    elif kind == "rec":
                        hh = rms_norm(h, lp[f"s{si}"]["ln1"], cfg.norm_eps)
                        conv0 = jnp.zeros((B, cfg.conv1d_width - 1, self.W),
                                          h.dtype)
                        h0 = jnp.zeros((B, self.W), jnp.float32)
                        y, conv, hs = self._rglru_apply(lp[f"s{si}"], hh,
                                                        conv0, h0)
                        h = h + y.astype(h.dtype)
                        y, _ = self._ffn_sublayer(lp[f"s{si}"], h)
                        h = h + y
                        lc[f"s{si}"] = {"conv": conv.astype(h.dtype), "h": hs}
                return h, lc

            scan_body = self._checkpoint(body)
            x, gc = jax.lax.scan(scan_body, x, gp)
            new_cache[f"g{gi}"] = gc
        logits = self._logits(params, x[:, -1:, :])
        return logits, new_cache
