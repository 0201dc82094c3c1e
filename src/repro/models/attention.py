"""Attention: GQA/MQA/MHA with RoPE, qk-norm, biases; MLA (deepseek-v3).

Three execution paths:
  * train/prefill — chunked online-softmax causal attention (flash-style in
    pure JAX: q processed in blocks, kv scanned in chunks; O(S) memory).
  * decode       — distributed flash-decode: the KV cache's *sequence* dim is
    sharded over mesh axes (default "model"); each shard computes a partial
    softmax and the result is combined with pmax/psum — this is the TPU
    analogue of splitting one flow's history across collector shards.
  * cross        — full bidirectional attention (whisper cross-attn).

Projections are 2-D (d_model, H*D) so the "model" axis always divides them.
"""
from __future__ import annotations

import functools
from typing import Any, Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh
from jax.sharding import PartitionSpec as P

from repro.configs.base import ModelConfig
from repro.models import layers as L
from repro.models.param import ParamDesc

Tree = Any
NEG_INF = -1e30


# ------------------------------------------------------------- descs -------

def attn_descs(cfg: ModelConfig) -> Tree:
    D = cfg.resolved_head_dim
    dt = cfg.param_dtype
    t = {
        "q": L.linear_descs(cfg.d_model, cfg.num_heads * D, dt,
                            bias=cfg.qkv_bias, in_axis="embed",
                            out_axis="model"),
        "k": L.linear_descs(cfg.d_model, cfg.num_kv_heads * D, dt,
                            bias=cfg.qkv_bias, in_axis="embed",
                            out_axis="model"),
        "v": L.linear_descs(cfg.d_model, cfg.num_kv_heads * D, dt,
                            bias=cfg.qkv_bias, in_axis="embed",
                            out_axis="model"),
        "o": L.linear_descs(cfg.num_heads * D, cfg.d_model, dt,
                            in_axis="model", out_axis="embed"),
    }
    if cfg.qk_norm:
        t["q_norm"] = L.rms_norm_descs(D, dt)
        t["k_norm"] = L.rms_norm_descs(D, dt)
    return t


# ------------------------------------------- chunked causal attention ------

def _pick_chunk(size: int, target: int) -> int:
    """Largest divisor of ``size`` that is <= target (static shapes only)."""
    target = max(1, min(target, size))
    for c in range(target, 0, -1):
        if size % c == 0:
            return c
    return size


def _online_softmax_block(q, k, v, q_pos, k_pos, causal, scale, bias=None):
    """One (q block) x (kv chunk) update. q: (B,Q,KH,G,D), k/v: (B,C,KH,D)."""
    s = jnp.einsum("bqkgd,bckd->bkgqc", q, k,
                   preferred_element_type=jnp.float32) * scale
    if causal:
        mask = q_pos[:, None] >= k_pos[None, :]          # (Q, C)
        s = jnp.where(mask[None, None, None], s, NEG_INF)
    if bias is not None:
        s = s + bias
    m = jnp.max(s, axis=-1)                              # (B,KH,G,Q)
    p = jnp.exp(s - m[..., None])
    l = jnp.sum(p, axis=-1)
    o = jnp.einsum("bkgqc,bckd->bkgqd", p.astype(v.dtype), v,
                   preferred_element_type=jnp.float32)
    return m, l, o


def _flash_fwd_impl(q, k, v, causal, q_offset, q_chunk, kv_chunk, scale):
    """q: (B,Sq,KH,G,D); k: (B,Sk,KH,D); v: (B,Sk,KH,Dv).

    Returns (o (B,KH,G,Sq,Dv) f32, lse (B,KH,G,Sq) f32)."""
    B, Sq, KH, G, D = q.shape
    Sk = k.shape[1]
    Dv = v.shape[-1]
    nq, nk = Sq // q_chunk, Sk // kv_chunk
    k_s = k.reshape(B, nk, kv_chunk, KH, D).swapaxes(0, 1)
    v_s = v.reshape(B, nk, kv_chunk, KH, Dv).swapaxes(0, 1)

    def q_block(qb, qi):
        q_pos = q_offset + qi * q_chunk + jnp.arange(q_chunk)

        def kv_step(carry, xs):
            m, l, o = carry
            kc, vc, ki = xs
            k_pos = ki * kv_chunk + jnp.arange(kv_chunk)
            mb, lb, ob = _online_softmax_block(qb, kc, vc, q_pos, k_pos,
                                               causal, scale)
            m_new = jnp.maximum(m, mb)
            c1 = jnp.exp(m - m_new)
            c2 = jnp.exp(mb - m_new)
            l = l * c1 + lb * c2
            o = o * c1[..., None] + ob * c2[..., None]
            return (m_new, l, o), ()

        m0 = jnp.full((B, KH, G, q_chunk), NEG_INF, jnp.float32)
        l0 = jnp.zeros((B, KH, G, q_chunk), jnp.float32)
        o0 = jnp.zeros((B, KH, G, q_chunk, Dv), jnp.float32)
        (m, l, o), _ = jax.lax.scan(
            kv_step, (m0, l0, o0), (k_s, v_s, jnp.arange(nk)))
        o = o / jnp.maximum(l[..., None], 1e-30)
        lse = m + jnp.log(jnp.maximum(l, 1e-30))
        return o, lse                                     # per q block

    if nq == 1:
        o, lse = q_block(q, jnp.asarray(0))
    else:
        q_s = q.reshape(B, nq, q_chunk, KH, G, D).swapaxes(0, 1)
        o, lse = jax.lax.map(lambda xs: q_block(*xs),
                             (q_s, jnp.arange(nq)))
        o = jnp.moveaxis(o, 0, 3).reshape(B, KH, G, Sq, Dv)
        lse = jnp.moveaxis(lse, 0, 3).reshape(B, KH, G, Sq)
    return o, lse


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _flash_core(q, k, v, causal, q_offset, q_chunk, kv_chunk, scale):
    o, _ = _flash_fwd_impl(q, k, v, causal, q_offset, q_chunk, kv_chunk,
                           scale)
    return o.astype(q.dtype)


def _flash_core_fwd(q, k, v, causal, q_offset, q_chunk, kv_chunk, scale):
    o, lse = _flash_fwd_impl(q, k, v, causal, q_offset, q_chunk, kv_chunk,
                             scale)
    o = o.astype(q.dtype)
    return o, (q, k, v, o, lse)


def _flash_core_bwd(causal, q_offset, q_chunk, kv_chunk, scale, res, do):
    """Flash-attention backward: recompute p per (q, kv) chunk pair; no
    autodiff residuals (this is why train fits HBM — see DESIGN.md §9)."""
    q, k, v, o, lse = res
    B, Sq, KH, G, D = q.shape
    Sk = k.shape[1]
    Dv = v.shape[-1]
    nq, nk = Sq // q_chunk, Sk // kv_chunk
    do = do.astype(jnp.float32)
    Dsum = jnp.sum(do * o.astype(jnp.float32), axis=-1)   # (B,KH,G,Sq)
    q_s = q.reshape(B, nq, q_chunk, KH, G, D).swapaxes(0, 1)
    do_s = do.reshape(B, KH, G, nq, q_chunk, Dv).transpose(3, 0, 1, 2, 4, 5)
    ds_sum = Dsum.reshape(B, KH, G, nq, q_chunk).transpose(3, 0, 1, 2, 4)
    lse_s = lse.reshape(B, KH, G, nq, q_chunk).transpose(3, 0, 1, 2, 4)
    k_s = k.reshape(B, nk, kv_chunk, KH, D).swapaxes(0, 1)
    v_s = v.reshape(B, nk, kv_chunk, KH, Dv).swapaxes(0, 1)

    def kv_step(dq_acc, xs):
        kc, vc, ki = xs
        k_pos = ki * kv_chunk + jnp.arange(kv_chunk)

        def q_step(carry, xs2):
            dk_c, dv_c = carry
            qb, dob, dsb, lseb, qi = xs2
            q_pos = q_offset + qi * q_chunk + jnp.arange(q_chunk)
            s = jnp.einsum("bqkgd,bckd->bkgqc", qb, kc,
                           preferred_element_type=jnp.float32) * scale
            if causal:
                mask = q_pos[:, None] >= k_pos[None, :]
                s = jnp.where(mask[None, None, None], s, NEG_INF)
            p = jnp.exp(s - lseb[..., None])              # (B,KH,G,Q,C)
            dv_c = dv_c + jnp.einsum("bkgqc,bkgqe->bcke", p, dob,
                                     preferred_element_type=jnp.float32)
            dp = jnp.einsum("bkgqe,bcke->bkgqc", dob,
                            vc.astype(jnp.float32))
            ds = p * (dp - dsb[..., None]) * scale        # (B,KH,G,Q,C)
            dq_b = jnp.einsum("bkgqc,bckd->bqkgd", ds,
                              kc.astype(jnp.float32))
            dk_c = dk_c + jnp.einsum("bkgqc,bqkgd->bckd", ds,
                                     qb.astype(jnp.float32))
            return (dk_c, dv_c), dq_b

        dk0 = jnp.zeros((B, kv_chunk, KH, D), jnp.float32)
        dv0 = jnp.zeros((B, kv_chunk, KH, Dv), jnp.float32)
        (dk_c, dv_c), dq_bs = jax.lax.scan(
            q_step, (dk0, dv0),
            (q_s, do_s, ds_sum, lse_s, jnp.arange(nq)))
        # dq_bs: (nq, B, q_chunk, KH, G, D) -> flat (B, Sq, KH, G, D)
        dq_flat = dq_bs.swapaxes(0, 1).reshape(B, Sq, KH, G, D)
        return dq_acc + dq_flat, (dk_c, dv_c)

    dq0 = jnp.zeros((B, Sq, KH, G, D), jnp.float32)
    dq, (dk_s, dv_s) = jax.lax.scan(
        kv_step, dq0, (k_s, v_s, jnp.arange(nk)))
    dk = dk_s.swapaxes(0, 1).reshape(B, Sk, KH, D)
    dv = dv_s.swapaxes(0, 1).reshape(B, Sk, KH, Dv)
    return (dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype))


_flash_core.defvjp(_flash_core_fwd, _flash_core_bwd)


def _attn_tp_constraints(q5, k, v, mesh, batch_axes):
    """Shard attention activations over "model": the KV-head dim when it
    divides, else the query-group dim (MQA), else leave to GSPMD."""
    if mesh is None:
        return q5, k, v
    from jax.sharding import NamedSharding
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    m = sizes.get("model", 1)
    if m == 1:
        return q5, k, v
    ba = batch_axes or None
    B, Sq, KH, G, D = q5.shape
    cons = lambda x, spec: jax.lax.with_sharding_constraint(
        x, NamedSharding(mesh, spec))
    if KH % m == 0:
        q5 = cons(q5, P(ba, None, "model", None, None))
        k = cons(k, P(ba, None, "model", None))
        v = cons(v, P(ba, None, "model", None))
    elif G % m == 0:
        q5 = cons(q5, P(ba, None, None, "model", None))
    elif Sq % m == 0 and Sq >= m * 8:
        # heads not divisible by TP (40-head archs on a 16-way axis):
        # context-parallel queries — shard q's SEQ dim; K/V are gathered
        # once but q/scores/o stay sharded (the qwen3 prefill hillclimb,
        # EXPERIMENTS.md §Perf)
        q5 = cons(q5, P(ba, "model", None, None, None))
    return q5, k, v


def chunked_attention(q, k, v, *, causal: bool = True, q_offset: int = 0,
                      q_chunk: int = 256, kv_chunk: int = 1024,
                      scale: Optional[float] = None, mesh=None,
                      batch_axes=()) -> jax.Array:
    """Flash attention (pure JAX, custom VJP). q: (B,Sq,H,D);
    k: (B,Sk,KH,D); v: (B,Sk,KH,Dv) -> (B,Sq,H,Dv)."""
    B, Sq, H, D = q.shape
    Sk, KH = k.shape[1], k.shape[2]
    Dv = v.shape[-1]
    G = H // KH
    scale = scale if scale is not None else D ** -0.5
    q = q.reshape(B, Sq, KH, G, D)
    q, k, v = _attn_tp_constraints(q, k, v, mesh, batch_axes)
    q_chunk = _pick_chunk(Sq, q_chunk)
    kv_chunk = _pick_chunk(Sk, kv_chunk)
    o = _flash_core(q, k, v, causal, q_offset, q_chunk, kv_chunk, scale)
    return o.transpose(0, 3, 1, 2, 4).reshape(B, Sq, H, Dv)


def full_attention(q, k, v, *, scale: Optional[float] = None) -> jax.Array:
    """Small unmasked attention (cross-attn). q:(B,Sq,H,D), k/v:(B,Sk,KH,D)."""
    B, Sq, H, D = q.shape
    KH = k.shape[2]
    G = H // KH
    scale = scale if scale is not None else D ** -0.5
    qr = q.reshape(B, Sq, KH, G, D)
    s = jnp.einsum("bqkgd,bckd->bkgqc", qr, k,
                   preferred_element_type=jnp.float32) * scale
    p = jax.nn.softmax(s, axis=-1).astype(v.dtype)
    o = jnp.einsum("bkgqc,bckd->bqkgd", p, v)
    return o.reshape(B, Sq, H, D)


# -------------------------------------------------- distributed decode -----

def _linear_axis_index(axes: Sequence[str]) -> jax.Array:
    idx = jnp.zeros((), jnp.int32)
    for ax in axes:
        idx = idx * jax.lax.axis_size(ax) + jax.lax.axis_index(ax)
    return idx


def _update_row(buf, row, idx, valid):
    """buf: (S_loc, ...); row: (1, ...) write at idx if valid (per-batch).

    Invalid writes re-write the OLD row (a no-op) instead of selecting over
    the whole buffer — a full-buffer jnp.where makes a cache-sized copy per
    layer and defeats in-place donation."""
    idx_c = jnp.clip(idx, 0, buf.shape[0] - 1)
    old = jax.lax.dynamic_slice_in_dim(buf, idx_c, 1, axis=0)
    newrow = jnp.where(valid, row.astype(buf.dtype), old)
    return jax.lax.dynamic_update_slice_in_dim(buf, newrow, idx_c, axis=0)


def flash_decode(q, k_cache, v_cache, k_new, v_new, pos, *, mesh: Mesh,
                 seq_axes: Tuple[str, ...], batch_axes: Tuple[str, ...],
                 scale: Optional[float] = None):
    """One decode step against a sequence-sharded KV cache.

    q:       (B, H, D)         — current-token queries (all heads, replicated
                                 over the seq axes; tiny at decode).
    k_cache: (B, S, KH, D)     — S sharded over ``seq_axes``.
    k_new:   (B, KH, D)        — this step's K/V, written at ``pos``.
    pos:     (B,) int32        — per-sequence write/attend position.
    Returns (out (B,H,D), k_cache', v_cache').
    """
    B, H, D = q.shape
    KH = k_cache.shape[2]
    G = H // KH
    scale = scale if scale is not None else D ** -0.5

    def local(qb, kc, vc, kn, vn, p):
        Bl = qb.shape[0]                                   # LOCAL batch
        S_loc = kc.shape[1]
        shard = _linear_axis_index(seq_axes) if seq_axes else jnp.zeros(
            (), jnp.int32)
        offset = shard * S_loc
        # -- write this step's kv into the owning shard
        lidx = p - offset                                  # (B,)
        valid = (lidx >= 0) & (lidx < S_loc)
        kc = jax.vmap(_update_row)(kc, kn[:, None], lidx, valid)
        vc = jax.vmap(_update_row)(vc, vn[:, None], lidx, valid)
        # -- partial attention over the local slice
        qr = qb.reshape(Bl, KH, G, D)
        s = jnp.einsum("bkgd,bskd->bkgs", qr, kc,
                       preferred_element_type=jnp.float32) * scale
        kpos = offset + jnp.arange(S_loc)
        mask = kpos[None] <= p[:, None]                    # (B, S_loc)
        s = jnp.where(mask[:, None, None], s, NEG_INF)
        m = jnp.max(s, axis=-1)                            # (B,KH,G)
        e = jnp.exp(s - m[..., None])
        e = jnp.where(mask[:, None, None], e, 0.0)
        l = jnp.sum(e, axis=-1)
        o = jnp.einsum("bkgs,bskd->bkgd", e.astype(vc.dtype), vc,
                       preferred_element_type=jnp.float32)
        if seq_axes:
            M = jax.lax.pmax(m, seq_axes)
            c = jnp.where(m <= NEG_INF / 2, 0.0, jnp.exp(m - M))
            l = jax.lax.psum(l * c, seq_axes)
            o = jax.lax.psum(o * c[..., None], seq_axes)
        out = (o / jnp.maximum(l[..., None], 1e-30)).astype(qb.dtype)
        return out.reshape(Bl, H, D), kc, vc

    ba = batch_axes if batch_axes else None
    sa = seq_axes if seq_axes else None
    fn = jax.shard_map(
        local, mesh=mesh,
        in_specs=(P(ba, None, None), P(ba, sa, None, None),
                  P(ba, sa, None, None), P(ba, None, None),
                  P(ba, None, None), P(ba)),
        out_specs=(P(ba, None, None), P(ba, sa, None, None),
                   P(ba, sa, None, None)),
        check_vma=False)
    return fn(q, k_cache, v_cache, k_new, v_new, pos)


# --------------------------------------------------------- GQA block -------

def project_qkv(params, x, cfg: ModelConfig, positions, rope: bool = True):
    """x: (B,S,d) -> q (B,S,H,D), k/v (B,S,KH,D) with rope + qk-norm."""
    B, S, _ = x.shape
    D = cfg.resolved_head_dim
    q = L.linear(params["q"], x).reshape(B, S, cfg.num_heads, D)
    k = L.linear(params["k"], x).reshape(B, S, cfg.num_kv_heads, D)
    v = L.linear(params["v"], x).reshape(B, S, cfg.num_kv_heads, D)
    if cfg.qk_norm:
        q = L.rms_norm(params["q_norm"], q, cfg.norm_eps)
        k = L.rms_norm(params["k_norm"], k, cfg.norm_eps)
    if rope:
        cos, sin = L.rotary(positions, D, cfg.rope_theta)
        q = L.apply_rotary(q, cos, sin)
        k = L.apply_rotary(k, cos, sin)
    return q, k, v


def attn_train(params, x, cfg: ModelConfig, *, q_offset: int = 0,
               causal: bool = True, return_kv: bool = False,
               rope: bool = True, mesh=None, batch_axes=()):
    B, S, _ = x.shape
    positions = q_offset + jnp.arange(S)
    q, k, v = project_qkv(params, x, cfg, positions, rope=rope)
    o = chunked_attention(q, k, v, causal=causal, q_offset=q_offset,
                          q_chunk=min(cfg.attn_chunk // 2, 256) or S,
                          kv_chunk=cfg.attn_chunk, mesh=mesh,
                          batch_axes=batch_axes)
    y = L.linear(params["o"], o.reshape(B, S, -1))
    if return_kv:
        return y, (k, v)
    return y


def attn_decode(params, x, cfg: ModelConfig, k_cache, v_cache, pos, *,
                mesh: Mesh, seq_axes, batch_axes):
    """x: (B,1,d); pos: (B,) — returns (y (B,1,d), k_cache', v_cache')."""
    B = x.shape[0]
    D = cfg.resolved_head_dim
    q, k, v = project_qkv(params, x, cfg, pos[:, None].astype(jnp.float32))
    out, k_cache, v_cache = flash_decode(
        q[:, 0], k_cache, v_cache, k[:, 0], v[:, 0], pos, mesh=mesh,
        seq_axes=seq_axes, batch_axes=batch_axes)
    y = L.linear(params["o"], out.reshape(B, 1, -1))
    return y, k_cache, v_cache


# ---------------------------------------------------------------- MLA ------

def mla_descs(cfg: ModelConfig) -> Tree:
    m = cfg.mla
    dt = cfg.param_dtype
    H = cfg.num_heads
    qk_dim = m.qk_nope_head_dim + m.qk_rope_head_dim
    return {
        "q_down": L.linear_descs(cfg.d_model, m.q_lora_rank, dt,
                                 in_axis="embed"),
        "q_norm": L.rms_norm_descs(m.q_lora_rank, dt),
        "q_up": L.linear_descs(m.q_lora_rank, H * qk_dim, dt,
                               out_axis="model"),
        "kv_down": L.linear_descs(cfg.d_model,
                                  m.kv_lora_rank + m.qk_rope_head_dim, dt,
                                  in_axis="embed"),
        "kv_norm": L.rms_norm_descs(m.kv_lora_rank, dt),
        "k_up": L.linear_descs(m.kv_lora_rank, H * m.qk_nope_head_dim, dt,
                               out_axis="model"),
        "v_up": L.linear_descs(m.kv_lora_rank, H * m.v_head_dim, dt,
                               out_axis="model"),
        "o": L.linear_descs(H * m.v_head_dim, cfg.d_model, dt,
                            in_axis="model", out_axis="embed"),
    }


def _mla_qkv_latent(params, x, cfg: ModelConfig, positions):
    """Shared down-projections. Returns q (nope+rope'd), latent c_kv, k_rope."""
    m = cfg.mla
    B, S, _ = x.shape
    H = cfg.num_heads
    qk = m.qk_nope_head_dim + m.qk_rope_head_dim
    ql = L.rms_norm(params["q_norm"], L.linear(params["q_down"], x),
                    cfg.norm_eps)
    q = L.linear(params["q_up"], ql).reshape(B, S, H, qk)
    q_nope, q_rope = q[..., :m.qk_nope_head_dim], q[..., m.qk_nope_head_dim:]
    kv = L.linear(params["kv_down"], x)
    c_kv = L.rms_norm(params["kv_norm"], kv[..., :m.kv_lora_rank],
                      cfg.norm_eps)
    k_rope = kv[..., m.kv_lora_rank:]                     # (B,S,rope_dim)
    cos, sin = L.rotary(positions, m.qk_rope_head_dim, cfg.rope_theta)
    q_rope = L.apply_rotary(q_rope, cos, sin)
    k_rope = L.apply_rotary(k_rope[:, :, None, :], cos, sin)[:, :, 0]
    return q_nope, q_rope, c_kv, k_rope


def mla_train(params, x, cfg: ModelConfig, *, q_offset: int = 0,
              return_kv: bool = False, mesh=None, batch_axes=()):
    """Training/prefill MLA: expand latent to per-head K/V (standard path)."""
    m = cfg.mla
    B, S, _ = x.shape
    H = cfg.num_heads
    positions = q_offset + jnp.arange(S)
    q_nope, q_rope, c_kv, k_rope = _mla_qkv_latent(params, x, cfg, positions)
    k_nope = L.linear(params["k_up"], c_kv).reshape(B, S, H,
                                                    m.qk_nope_head_dim)
    v = L.linear(params["v_up"], c_kv).reshape(B, S, H, m.v_head_dim)
    q = jnp.concatenate([q_nope, q_rope], axis=-1)
    k = jnp.concatenate(
        [k_nope, jnp.broadcast_to(k_rope[:, :, None, :],
                                  (B, S, H, m.qk_rope_head_dim))], axis=-1)
    scale = (m.qk_nope_head_dim + m.qk_rope_head_dim) ** -0.5
    o = chunked_attention(q, k, v, causal=True, q_offset=q_offset,
                          q_chunk=min(cfg.attn_chunk // 2, 256),
                          kv_chunk=cfg.attn_chunk, scale=scale, mesh=mesh,
                          batch_axes=batch_axes)
    y = L.linear(params["o"], o.reshape(B, S, -1))
    if return_kv:
        return y, (c_kv, k_rope)
    return y


def mla_decode(params, x, cfg: ModelConfig, ckv_cache, krope_cache, pos, *,
               mesh: Mesh, seq_axes, batch_axes):
    """Absorbed-weight MLA decode over the *latent* cache (beyond-paper perf:
    the cache stores (kv_lora + rope) per token instead of H*(D_k+D_v)).

    ckv_cache: (B, S, R) latent; krope_cache: (B, S, Dr).
    """
    m = cfg.mla
    B = x.shape[0]
    H = cfg.num_heads
    R = m.kv_lora_rank
    q_nope, q_rope, c_new, kr_new = _mla_qkv_latent(
        params, x, cfg, pos[:, None].astype(jnp.float32))
    # absorb k_up into q: q_abs[b,h,r] = sum_d q_nope[b,h,d] * Wk[r, h, d]
    Wk = params["k_up"]["w"].reshape(R, H, m.qk_nope_head_dim)
    q_abs = jnp.einsum("bhd,rhd->bhr", q_nope[:, 0], Wk)   # (B,H,R)
    scale = (m.qk_nope_head_dim + m.qk_rope_head_dim) ** -0.5

    def local(qa, qr, ckv, krope, cn, krn, p):
        S_loc = ckv.shape[1]
        shard = _linear_axis_index(seq_axes) if seq_axes else jnp.zeros(
            (), jnp.int32)
        offset = shard * S_loc
        lidx = p - offset
        valid = (lidx >= 0) & (lidx < S_loc)
        ckv = jax.vmap(_update_row)(ckv, cn, lidx, valid)
        krope = jax.vmap(_update_row)(krope, krn, lidx, valid)
        s = (jnp.einsum("bhr,bsr->bhs", qa, ckv,
                        preferred_element_type=jnp.float32) +
             jnp.einsum("bhd,bsd->bhs", qr, krope,
                        preferred_element_type=jnp.float32)) * scale
        kpos = offset + jnp.arange(S_loc)
        mask = kpos[None] <= p[:, None]
        s = jnp.where(mask[:, None], s, NEG_INF)
        mx = jnp.max(s, axis=-1)
        e = jnp.where(mask[:, None], jnp.exp(s - mx[..., None]), 0.0)
        l = jnp.sum(e, axis=-1)
        o = jnp.einsum("bhs,bsr->bhr", e.astype(ckv.dtype), ckv,
                       preferred_element_type=jnp.float32)  # latent-space o
        if seq_axes:
            Mx = jax.lax.pmax(mx, seq_axes)
            c = jnp.where(mx <= NEG_INF / 2, 0.0, jnp.exp(mx - Mx))
            l = jax.lax.psum(l * c, seq_axes)
            o = jax.lax.psum(o * c[..., None], seq_axes)
        o = o / jnp.maximum(l[..., None], 1e-30)
        return o.astype(x.dtype), ckv, krope

    ba = batch_axes if batch_axes else None
    sa = seq_axes if seq_axes else None
    fn = jax.shard_map(
        local, mesh=mesh,
        in_specs=(P(ba, None, None), P(ba, None, None), P(ba, sa, None),
                  P(ba, sa, None), P(ba, None, None), P(ba, None, None),
                  P(ba)),
        out_specs=(P(ba, None, None), P(ba, sa, None), P(ba, sa, None)),
        check_vma=False)
    o_lat, ckv_cache, krope_cache = fn(
        q_abs, q_rope[:, 0], ckv_cache, krope_cache, c_new, kr_new, pos)
    # absorb v_up on the way out: o[b,h,p] = sum_r o_lat[b,h,r] Wv[r,h,p]
    Wv = params["v_up"]["w"].reshape(R, H, m.v_head_dim)
    o = jnp.einsum("bhr,rhp->bhp", o_lat, Wv)
    y = L.linear(params["o"], o.reshape(B, 1, -1))
    return y, ckv_cache, krope_cache
