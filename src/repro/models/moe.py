"""Mixture-of-Experts FFN with expert parallelism.

Experts are sharded over the "model" mesh axis (EP=TP axis). Because our
activations are TP-replicated over "model" between blocks, dispatch does NOT
need an all_to_all: every rank sees every token, gathers only the pairs owned
by its local experts into capacity-bounded buffers (argsort ranking — the
TPU-native replacement for random scatter), runs its experts, and the partial
outputs are psum-combined over "model". Communication per token is one
all-reduce of (T, d) — the same volume as GShard's double all_to_all at k=8,
with far simpler code and no load-dependent message sizes. See DESIGN.md §4.

Routing follows the config: softmax or sigmoid scores (deepseek-v3), top-k,
renormalized, optional routed scaling factor; shared experts bypass routing.
"""
from __future__ import annotations

import math
from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh
from jax.sharding import PartitionSpec as P

from repro.configs.base import ModelConfig
from repro.models import layers as L
from repro.models.param import ParamDesc

Tree = Any


def moe_descs(cfg: ModelConfig) -> Tree:
    m = cfg.moe
    dt = cfg.param_dtype
    E, d, f = m.num_experts, cfg.d_model, m.d_ff_expert
    t = {
        "router": ParamDesc((d, E), "float32", ("embed", None)),
        "gate": ParamDesc((E, d, f), dt, ("experts", "embed", None)),
        "up": ParamDesc((E, d, f), dt, ("experts", "embed", None)),
        "down": ParamDesc((E, f, d), dt, ("experts", None, "embed")),
    }
    if m.score_func == "sigmoid":
        t["bias"] = ParamDesc((E,), "float32", (None,), init="zeros")
    if m.num_shared_experts:
        f_sh = m.d_ff_shared * m.num_shared_experts
        t["shared"] = {
            "gate": L.linear_descs(d, f_sh, dt, in_axis="embed",
                                   out_axis="model"),
            "up": L.linear_descs(d, f_sh, dt, in_axis="embed",
                                 out_axis="model"),
            "down": L.linear_descs(f_sh, d, dt, in_axis="model",
                                   out_axis="embed"),
        }
    return t


def route(params, x, cfg: ModelConfig) -> Tuple[jax.Array, jax.Array]:
    """x: (T, d) -> (weights (T,k) f32, experts (T,k) i32)."""
    m = cfg.moe
    logits = x.astype(jnp.float32) @ params["router"]        # (T, E)
    if m.score_func == "sigmoid":
        scores = jax.nn.sigmoid(logits)
        sel = scores + params["bias"][None, :]               # bias only for selection
        w, idx = jax.lax.top_k(sel, m.top_k)
        w = jnp.take_along_axis(scores, idx, axis=-1)        # weight w/o bias
        w = w / jnp.maximum(jnp.sum(w, axis=-1, keepdims=True), 1e-9)
        w = w * m.routed_scaling_factor
    else:
        probs = jax.nn.softmax(logits, axis=-1)
        w, idx = jax.lax.top_k(probs, m.top_k)
        w = w / jnp.maximum(jnp.sum(w, axis=-1, keepdims=True), 1e-9)
    return w, idx


def _expert_gather_compute(x_flat, w_pair, e_pair, params_loc, E_loc, C,
                           my_first):
    """Masked local dispatch on one EP rank.

    x_flat: (T, d) all tokens (replicated); e_pair/w_pair: (T*k,) routing.
    Returns partial output (T, d) — nonzero only for pairs owned here.
    """
    T, d = x_flat.shape
    Pairs = e_pair.shape[0]
    k = Pairs // T
    le = e_pair - my_first
    valid = (le >= 0) & (le < E_loc)
    key = jnp.where(valid, le, E_loc).astype(jnp.int32)
    order = jnp.argsort(key, stable=True)                    # (Pairs,)
    sorted_le = key[order]
    start = jnp.searchsorted(sorted_le, jnp.arange(E_loc), side="left")
    rank_in_e = jnp.arange(Pairs) - start[jnp.clip(sorted_le, 0, E_loc - 1)]
    ok = (sorted_le < E_loc) & (rank_in_e < C)
    slot = jnp.where(ok, sorted_le * C + rank_in_e, E_loc * C)
    pair_tok = order // k                                    # token of pair
    # slot-space bookkeeping: (E_loc*C+1,) — NEVER pair-space (T*k, d)
    # tensors (a (T*k, d) combine buffer is the memory bug this replaces)
    buf_tok = jnp.full((E_loc * C + 1,), T, jnp.int32)
    buf_tok = buf_tok.at[slot].set(jnp.where(ok, pair_tok, T))
    w_slot = jnp.zeros((E_loc * C + 1,), jnp.float32)
    w_slot = w_slot.at[slot].set(jnp.where(ok, w_pair[order], 0.0))
    x_pad = jnp.concatenate([x_flat, jnp.zeros((1, d), x_flat.dtype)], 0)
    buf = x_pad[buf_tok[:-1]].reshape(E_loc, C, d)
    # expert FFN (silu-gated)
    h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", buf, params_loc["gate"])) * \
        jnp.einsum("ecd,edf->ecf", buf, params_loc["up"])
    out = jnp.einsum("ecf,efd->ecd", h, params_loc["down"])  # (E_loc,C,d)
    out_flat = out.reshape(E_loc * C, d)
    # combine: weight each SLOT row, scatter-add to its token
    rows = out_flat * w_slot[:-1, None].astype(out_flat.dtype)
    contrib = jnp.zeros((T + 1, d), out_flat.dtype)
    contrib = contrib.at[buf_tok[:-1]].add(rows)
    return contrib[:T]


def decode_ep_axes(cfg: ModelConfig, mesh: Mesh, tokens: int
                   ) -> Tuple[str, ...]:
    """EP axes for the SERVING path: widen EP over ("model","data") when
    the expert count divides and the token activations are small enough to
    replicate — then every device holds whole experts and the per-layer
    FSDP weight gathers disappear (EXPERIMENTS.md §Perf, deepseek decode)."""
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    axes = []
    prod = 1
    for ax in ("model", "data", "pod"):
        if ax in sizes and cfg.moe.num_experts % (prod * sizes[ax]) == 0:
            axes.append(ax)
            prod *= sizes[ax]
    # replicating x must stay cheap (decode: ~128 tokens)
    if tokens * cfg.d_model * 2 > 64 * 2**20:
        return ("model",)
    return tuple(axes) if axes else ("model",)


def moe_ffn(params, x, cfg: ModelConfig, mesh: Mesh,
            batch_axes: Tuple[str, ...],
            ep_axes: Tuple[str, ...] = ("model",)) -> jax.Array:
    """x: (B, S, d) -> (B, S, d). Experts sharded over ``ep_axes``.

    ep_axes == ("model",): training layout — activations replicated over
    "model", expert d/f dims FSDP-sharded over "data" (gathered per layer).
    Wider ep_axes (serving): x replicated over all ep axes, experts whole
    per device, combine = one psum over ep_axes."""
    m = cfg.moe
    B, S, d = x.shape
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    ep = math.prod([sizes[a] for a in ep_axes])
    E_loc = m.num_experts // ep
    rep_x = len(ep_axes) > 1                  # x fully replicated mode
    if rep_x:
        T_loc = B * S
        ba = None
    else:
        bsz = math.prod([sizes[a] for a in batch_axes]) if batch_axes else 1
        T_loc = (B // bsz) * S
        ba = batch_axes if batch_axes else None
    C = max(1, int(math.ceil(T_loc * m.top_k * m.capacity_factor
                             / m.num_experts)))
    bias = params.get("bias")
    if bias is None:
        bias = jnp.zeros((m.num_experts,), jnp.float32)

    def local(xb, router, b, gate, up, down):
        T = xb.shape[0] * xb.shape[1]
        xf = xb.reshape(T, d)
        p = {"router": router, "gate": gate, "up": up, "down": down,
             "bias": b}
        w, idx = route(p, xf, cfg)
        my_rank = jnp.zeros((), jnp.int32)
        for a in ep_axes:
            my_rank = my_rank * jax.lax.axis_size(a) + jax.lax.axis_index(a)
        my_first = my_rank * E_loc
        out = _expert_gather_compute(
            xf, w.reshape(-1), idx.reshape(-1).astype(jnp.int32),
            p, E_loc, C, my_first)
        out = jax.lax.psum(out, ep_axes)
        return out.reshape(xb.shape).astype(xb.dtype)

    espec = ep_axes[0] if len(ep_axes) == 1 else tuple(ep_axes)
    fn = jax.shard_map(
        local, mesh=mesh,
        in_specs=(P(ba, None, None), P(None, None), P(None),
                  P(espec, None, None), P(espec, None, None),
                  P(espec, None, None)),
        out_specs=P(ba, None, None), check_vma=False)
    y = fn(x, params["router"], bias, params["gate"], params["up"],
           params["down"])
    if m.num_shared_experts:
        y = y + L.ffn(params["shared"], x)
    return y


def load_balance_loss(params, x, cfg: ModelConfig) -> jax.Array:
    """Auxiliary load-balancing loss (Switch-style), computed on a token
    sample outside the shard_map (train-time regularizer)."""
    m = cfg.moe
    xf = x.reshape(-1, x.shape[-1]).astype(jnp.float32)
    probs = jax.nn.softmax(xf @ params["router"], axis=-1)   # (T, E)
    _, idx = jax.lax.top_k(probs, m.top_k)
    onehot = jax.nn.one_hot(idx[..., 0], m.num_experts)
    frac_tokens = jnp.mean(onehot, axis=0)
    frac_probs = jnp.mean(probs, axis=0)
    return m.num_experts * jnp.sum(frac_tokens * frac_probs)
