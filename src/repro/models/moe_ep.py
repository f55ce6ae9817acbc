"""Expert-parallel MoE via shard_map (the optimized path; DESIGN.md §2.2).

The control-flow-model transcription of routing: experts are *homed* on
model shards and tokens delegate computation to their experts' home shard —
no capacity buffer ever crosses the ICI. Per device everything is local
(router, top-k, scatter into the owned experts' capacity buffer, expert
FFN, gather-combine) except ONE ``psum`` over the model axis that merges
per-shard partial outputs (+ its transpose in backward).

When ``n_experts < tp`` each expert is split column-wise into
``split = tp / E`` *virtual experts* (TP inside the expert) — an exact
decomposition of the gated FFN, so every mesh size is served without
weight replication:

    silu(x Wg) * (x Wu) Wd  ==  Σ_h silu(x Wg_h) * (x Wu_h) Wd_h

Parameters are therefore STORED virtualized: ``[V, D, Fe/split]`` with the
virtual-expert dim sharded over "model" (and ZeRO over "data" on D).

Compared against the GSPMD scatter baseline (``ffn.moe_mlp``) in
EXPERIMENTS.md §Perf: it removes the TiB-scale involuntary-rematerialization
all-gathers/all-reduces the baseline suffers on both MoE archs.
"""
from __future__ import annotations

import functools
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh
from jax.sharding import PartitionSpec as P

from .config import ModelConfig
from .ffn import moe_capacity


def virtualization(cfg: ModelConfig, tp: int) -> Tuple[int, int]:
    """(V, split): virtual expert count and per-expert column split."""
    E = cfg.n_experts
    if E % tp == 0:
        return E, 1
    split = -(-tp // E)
    assert (E * split) % tp == 0, (E, tp)
    return E * split, split


def _local_moe(xt, router, w_gate, w_up, w_down, *, cfg: ModelConfig,
               V: int, split: int, tp: int, dp_axes: Tuple[str, ...]):
    """Per-device body (inside shard_map).

    xt: [T, D] (this data shard's tokens; replicated over model)
    router: [D, E]; w_*: [V_loc, D, Fe_v] / [V_loc, Fe_v, D] (owned virtuals)
    """
    T, D = xt.shape
    E, K = cfg.n_experts, cfg.top_k
    V_loc = V // tp
    m = jax.lax.axis_index("model")
    base = m * V_loc

    probs = jax.nn.softmax(
        (xt.astype(jnp.float32) @ router.astype(jnp.float32)), axis=-1)
    gate_vals, gate_idx = jax.lax.top_k(probs, K)               # [T, K]
    gate_vals = gate_vals / jnp.maximum(
        jnp.sum(gate_vals, axis=-1, keepdims=True), 1e-9)

    # expand to virtual destinations: expert e -> virtuals e*split+h
    vidx = (gate_idx[..., None] * split
            + jnp.arange(split)[None, None, :])                 # [T, K, split]
    vflat = vidx.reshape(-1)                                    # [T*K*split]
    wflat = jnp.repeat(gate_vals.reshape(-1), split)            # [T*K*split]

    # global intra-virtual positions (identical on every shard: deterministic)
    C = moe_capacity(T, E, K, cfg.capacity_factor)
    onehot = jax.nn.one_hot(vflat, V, dtype=jnp.int32)
    pos = (jnp.cumsum(onehot, axis=0) - 1)
    pos_in_v = jnp.take_along_axis(pos, vflat[:, None], axis=1)[:, 0]

    own = (vflat >= base) & (vflat < base + V_loc)
    keep = own & (pos_in_v < C)
    slot_v = jnp.where(keep, vflat - base, 0)
    slot_c = jnp.where(keep, pos_in_v, 0)

    src = jnp.repeat(xt, K * split, axis=0)                     # [T*K*split, D]
    src = jnp.where(keep[:, None], src, 0)
    buf = jnp.zeros((V_loc, C, D), xt.dtype).at[slot_v, slot_c].add(
        src, mode="drop")

    h = jax.nn.silu(jnp.einsum("vcd,vdf->vcf", buf, w_gate)) \
        * jnp.einsum("vcd,vdf->vcf", buf, w_up)
    out_buf = jnp.einsum("vcf,vfd->vcd", h, w_down)             # [V_loc, C, D]

    gathered = out_buf[slot_v, slot_c]                          # [T*K*split, D]
    gathered = jnp.where(keep[:, None], gathered, 0)
    y = jnp.sum((gathered * wflat[:, None].astype(gathered.dtype))
                .reshape(T, K * split, D), axis=1)
    y = jax.lax.psum(y, "model")                                # the one collective

    # Switch-style aux loss (identical across model shards; averaged over dp)
    density = jnp.mean(jax.nn.one_hot(gate_idx[:, 0], E, dtype=jnp.float32),
                       axis=0)
    density_proxy = jnp.mean(probs, axis=0)
    aux = jnp.sum(density * density_proxy) * E
    if dp_axes:
        aux = jax.lax.pmean(aux, dp_axes)
    return y, aux


def moe_mlp_ep(params: Dict, x: jax.Array, cfg: ModelConfig, mesh: Mesh,
               dp_axes: Tuple[str, ...]) -> Tuple[jax.Array, jax.Array]:
    """Expert-parallel MoE. x: [B, S, D] -> (y, aux).

    params["w_gate"]/["w_up"]: [V, D, Fe_v]; ["w_down"]: [V, Fe_v, D];
    ["router"]: [D, E]. Weights must already be gathered to their TP-only
    sharding (the per-layer ZeRO prefetch handles that upstream).
    """
    B, S, D = x.shape
    tp = mesh.shape.get("model", 1)
    V, split = virtualization(cfg, tp)
    dp = dp_axes if (B * S) % max(
        1, __import__("math").prod(mesh.shape[a] for a in dp_axes)) == 0 \
        and B > 1 else ()
    body = functools.partial(_local_moe, cfg=cfg, V=V, split=split, tp=tp,
                             dp_axes=dp)

    xt = x.reshape(B * S, D)
    tok_spec = P(dp or None, None)
    y, aux = jax.shard_map(
        body, mesh=mesh, check_vma=False,
        in_specs=(tok_spec,
                  P(None, None),
                  P("model", None, None),
                  P("model", None, None),
                  P("model", None, None)),
        out_specs=(tok_spec, P()),
    )(xt, params["router"], params["w_gate"], params["w_up"],
      params["w_down"])
    return y.reshape(B, S, D), aux
