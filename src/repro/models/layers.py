"""Manually tensor-parallel transformer layers (inside shard_map).

Conventions:
  * activations: bf16, reductions/norms in f32;
  * weights arrive *gathered* (TP-local logical shapes from sharding.py);
  * attention shards query heads over tp; KV projections are replicated
    (n_kv < tp for every assigned config), so K/V are computed redundantly
    — the flops are negligible and the replicated-weight gradients are
    psum'd over tp by the gather's custom vjp;
  * with ``ctx.seq_parallel`` the residual stream is sharded over tokens
    (sequence dim); blocks all-gather tokens on entry and reduce-scatter
    partial outputs on exit — same bytes as the psum they replace, but
    activation memory drops by 1/tp (Megatron-SP).
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

import repro.obs as _obs
from repro.kernels import ops
from repro.models.config import ModelConfig
from repro.models.sharding import (ShardCtx, psum_tp, all_gather_tp,
                                   reduce_scatter_tp, tp_index)

Array = jax.Array

ATTN_CHUNK = 512          # query-chunk length for memory-bounded attention

# Which path each attention call of a traced program took (counted at trace
# time, one count per call): the fused kernel or materialized score rows.
_ATTN_DISPATCH = {p: _obs.registry().counter("attention_dispatch", path=p)
                  for p in ("fused", "materialized")}


def rms_norm(x: Array, scale: Array, eps: float = 1e-5) -> Array:
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    out = xf * jax.lax.rsqrt(var + eps) * scale.astype(jnp.float32)
    return out.astype(x.dtype)


def rope_angles(positions: Array, head_dim: int, theta: float) -> tuple[Array, Array]:
    """positions: (...,) int32 -> cos/sin (..., head_dim/2) f32."""
    half = head_dim // 2
    freqs = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    ang = positions.astype(jnp.float32)[..., None] * freqs
    return jnp.cos(ang), jnp.sin(ang)


def apply_rope(x: Array, cos: Array, sin: Array) -> Array:
    """x: (..., S, n, head_dim); cos/sin: (S, head_dim/2) or broadcastable."""
    half = x.shape[-1] // 2
    x1, x2 = x[..., :half], x[..., half:]
    # cos/sin need a heads axis: (..., S, 1, half)
    c = cos[..., None, :]
    s = sin[..., None, :]
    xf1, xf2 = x1.astype(jnp.float32), x2.astype(jnp.float32)
    return jnp.concatenate([xf1 * c - xf2 * s, xf2 * c + xf1 * s],
                           axis=-1).astype(x.dtype)


def head_shards(cfg: ModelConfig, ctx: ShardCtx) -> int:
    """Distinct query-head shards: the largest power-of-two divisor of tp
    that divides n_heads (yi-34b 56H -> 8, whisper 12H -> 4, internvl
    14H -> 2, everything else -> tp)."""
    g = 1
    k = 2
    while k <= ctx.tp:
        if ctx.tp % k == 0 and cfg.n_heads % k == 0:
            g = k
        k *= 2
    return g


def head_repl(cfg: ModelConfig, ctx: ShardCtx) -> int:
    """Replication factor of the attention weights across tp."""
    return ctx.tp // head_shards(cfg, ctx)


def local_heads(cfg: ModelConfig, ctx: ShardCtx) -> int:
    return cfg.n_heads // head_shards(cfg, ctx)


def _kv_map_local(cfg: ModelConfig, ctx: ShardCtx) -> Array:
    """kv-head index for each local query head (GQA grouping)."""
    h_loc = local_heads(cfg, ctx)
    repl = head_repl(cfg, ctx)
    shard = tp_index(ctx) // repl
    heads = shard * h_loc + jnp.arange(h_loc)
    return heads // cfg.q_per_kv


def _softmax_attend(q: Array, k: Array, v: Array, mask: Array,
                    scale: float) -> Array:
    """q: (B,Sq,h,d) k/v: (B,Sk,h,d) mask: (Sq,Sk) bool -> (B,Sq,h,d)."""
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                        preferred_element_type=jnp.float32) * scale
    logits = jnp.where(mask[None, None], logits, -1e30)
    probs = jax.nn.softmax(logits, axis=-1).astype(v.dtype)
    return jnp.einsum("bhqk,bkhd->bqhd", probs, v)


def _fused(S: int, causal: bool, window: int) -> bool:
    """Whether self-attention over S tokens at positions 0..S-1 goes through
    the fused kernel (``ops.causal_attention``): on the TPU, causal with no
    window, over more than one query chunk, S a multiple of 128.  Else the
    scores are materialized, in query chunks over ``ATTN_CHUNK``."""
    fused = (ops.on_tpu() and causal and not window and S > ATTN_CHUNK
             and S % 128 == 0)
    _ATTN_DISPATCH["fused" if fused else "materialized"].inc()
    return fused


def attention(xg: Array, w: dict, cfg: ModelConfig, ctx: ShardCtx, *,
              positions: Array, causal: bool = True, window: int = 0,
              kv_out: bool = False):
    """Training/prefill attention over gathered tokens.

    xg: (B, S, D); returns partial output (B, S, D) — caller psums/scatters.
    w: {"wq": (D, Hl*hd), "wk": (D, KV*hd), "wv": ..., "wo": (Hl*hd, D),
        optional "qn","kn": (hd,)}
    """
    B, S, D = xg.shape
    hd = cfg.head_dim
    h_loc = local_heads(cfg, ctx)
    kv = cfg.n_kv

    q = (xg @ w["wq"]).reshape(B, S, h_loc, hd)
    k = (xg @ w["wk"]).reshape(B, S, kv, hd)
    v = (xg @ w["wv"]).reshape(B, S, kv, hd)

    if cfg.qk_norm:
        q = rms_norm(q, w["qn"], cfg.norm_eps)
        k = rms_norm(k, w["kn"], cfg.norm_eps)

    cos, sin = rope_angles(positions, hd, cfg.rope_theta)
    q = apply_rope(q, cos, sin)
    k = apply_rope(k, cos, sin)

    kv_idx = _kv_map_local(cfg, ctx)                  # (h_loc,)
    k_h = jnp.take(k, kv_idx, axis=2)                 # (B,S,h_loc,hd)
    v_h = jnp.take(v, kv_idx, axis=2)
    scale = 1.0 / np.sqrt(hd)

    if _fused(S, causal, window):
        out = ops.causal_attention(q, k_h, v_h, scale=scale)
    elif S <= ATTN_CHUNK:
        qpos = positions
        kpos = positions
        mask = jnp.ones((S, S), bool)
        if causal:
            mask = qpos[:, None] >= kpos[None, :]
        if window:
            mask &= (qpos[:, None] - kpos[None, :]) < window
        out = _softmax_attend(q, k_h, v_h, mask, scale)
    else:
        # query-chunked attention (memory-bounded); scan over chunks
        C = ATTN_CHUNK
        pad = (-S) % C
        qp = jnp.pad(q, ((0, 0), (0, pad), (0, 0), (0, 0)))
        pp = jnp.pad(positions, (0, pad), constant_values=-1)
        nchunk = qp.shape[1] // C
        qc = qp.reshape(B, nchunk, C, h_loc, hd).swapaxes(0, 1)
        pc = pp.reshape(nchunk, C)

        def body(carry, inp):
            qi, pi = inp
            mask = jnp.ones((C, S), bool)
            if causal:
                mask = pi[:, None] >= positions[None, :]
            if window:
                mask &= (pi[:, None] - positions[None, :]) < window
            return carry, _softmax_attend(qi, k_h, v_h, mask, scale)

        _, oc = jax.lax.scan(body, None, (qc, pc))
        out = oc.swapaxes(0, 1).reshape(B, nchunk * C, h_loc, hd)[:, :S]

    out = out.reshape(B, S, h_loc * hd) @ w["wo"]     # partial over tp
    if kv_out:
        return out, (k, v)
    return out


def mla_attention(xg: Array, w: dict, cfg: ModelConfig, *,
                  positions: Array) -> Array:
    """Multi-head latent attention (DeepSeek-V2/V3), the expanded form, over
    gathered tokens; every head on this chip (tensor parallelism is not
    supported).

    q = x Wq, per head [nope (head_dim) | rope (qk_rope_dim)];
    [c_kv | k_pe] = x Wkv_a; c_kv = RMSNorm(c_kv); [k_nope | v] = c_kv Wkv_b
    per head; rotary positions on q's rope part and on the one k_pe every
    head shares; softmax scale 1/sqrt(head_dim + qk_rope_dim).  Over
    ``ATTN_CHUNK`` tokens the queries go in chunks whose body is
    rematerialised, so no (B, H, S, S) tensor is kept for the backward.

    xg: (B, S, D); w: {"wq": (D, H*(hd+r)), "wkv_a": (D, R+r), "kv_norm":
    (R,), "wkv_b": (R, H*(hd+vd)), "wo": (H*vd, D)} -> (B, S, D)."""
    B, S, _ = xg.shape
    H, hd, r = cfg.n_heads, cfg.head_dim, cfg.qk_rope_dim
    vd, R = cfg.v_head_dim, cfg.kv_lora_rank
    with jax.named_scope("mla"):
        q = (xg @ w["wq"]).reshape(B, S, H, hd + r)
        kv_a = xg @ w["wkv_a"]
        c_kv = rms_norm(kv_a[..., :R], w["kv_norm"], cfg.norm_eps)
        kv = (c_kv @ w["wkv_b"]).reshape(B, S, H, hd + vd)
        cos, sin = rope_angles(positions, r, cfg.rope_theta)
        q = jnp.concatenate([q[..., :hd], apply_rope(q[..., hd:], cos, sin)],
                            -1)
        k_pe = apply_rope(kv_a[..., None, R:], cos, sin)
        k = jnp.concatenate([kv[..., :hd],
                             jnp.broadcast_to(k_pe, (B, S, H, r))], -1)
        v = kv[..., hd:]
        scale = 1.0 / np.sqrt(hd + r)
        if _fused(S, True, 0):
            out = ops.causal_attention(q, k, v, scale=scale)
        elif S <= ATTN_CHUNK:
            mask = positions[:, None] >= positions[None, :]
            out = _softmax_attend(q, k, v, mask, scale)
        else:
            C = ATTN_CHUNK
            pad = (-S) % C
            qp = jnp.pad(q, ((0, 0), (0, pad), (0, 0), (0, 0)))
            pp = jnp.pad(positions, (0, pad), constant_values=-1)
            n = qp.shape[1] // C
            qc = qp.reshape(B, n, C, H, hd + r).swapaxes(0, 1)

            @jax.checkpoint
            def body(carry, inp):
                qi, pi = inp
                mask = pi[:, None] >= positions[None, :]
                return carry, _softmax_attend(qi, k, v, mask, scale)

            _, oc = jax.lax.scan(body, None, (qc, pp.reshape(n, C)))
            out = oc.swapaxes(0, 1).reshape(B, n * C, H, vd)[:, :S]
        return out.reshape(B, S, H * vd) @ w["wo"]


def mlp(xg: Array, w: dict, cfg: ModelConfig) -> Array:
    """Gathered-token MLP; returns partial output (psum over tp by caller).

    swiglu: w = {wg (D,Fl), wu (D,Fl), wd (Fl,D)}
    squared_relu / gelu: w = {wi (D,Fl), wd (Fl,D)}
    """
    if cfg.act == "swiglu":
        h = jax.nn.silu((xg @ w["wg"]).astype(jnp.float32))
        h = (h * (xg @ w["wu"]).astype(jnp.float32)).astype(xg.dtype)
    elif cfg.act == "squared_relu":
        h = jax.nn.relu((xg @ w["wi"]).astype(jnp.float32))
        h = (h * h).astype(xg.dtype)
    else:
        h = jax.nn.gelu((xg @ w["wi"]).astype(jnp.float32)).astype(xg.dtype)
    return h @ w["wd"]


# ---------------------------------------------------------------------------
# Sequence-parallel entry/exit
# ---------------------------------------------------------------------------

def sp_enter(x: Array, ctx: ShardCtx) -> Array:
    """(B, S/tp, D) -> (B, S, D)."""
    return all_gather_tp(x, ctx, axis=1) if ctx.seq_parallel else x


def sp_exit(partial_out: Array, ctx: ShardCtx) -> Array:
    """partial (B, S, D) -> reduced (B, S/tp, D) [SP] or psum (B,S,D)."""
    if ctx.seq_parallel:
        return reduce_scatter_tp(partial_out, ctx, axis=1)
    return psum_tp(partial_out, ctx)


def token_slice(x: Array, ctx: ShardCtx) -> Array:
    """(B, S, D) -> this rank's (B, S/tp, D) token slice."""
    if ctx.tp == 1:
        return x
    s_loc = x.shape[1] // ctx.tp
    return jax.lax.dynamic_slice_in_dim(x, tp_index(ctx) * s_loc, s_loc, 1)


def attn_exit(att: Array, cfg: ModelConfig, ctx: ShardCtx) -> Array:
    """Exit for attention partials.  When heads are partially replicated
    (repl > 1), every replica contributes an identical copy of its shard's
    partial, so the psum / reduce-scatter over-counts by exactly repl —
    divide it back out."""
    repl = head_repl(cfg, ctx)
    out = sp_exit(att, ctx)
    if repl > 1:
        out = out / repl
    return out


# ---------------------------------------------------------------------------
# Vocab-parallel embedding / logits / cross-entropy
# ---------------------------------------------------------------------------

def vp_embed(tokens: Array, emb: Array, ctx: ShardCtx) -> Array:
    """tokens (B,S) int32; emb (V/tp, D) local vocab slice -> (B,S,D)."""
    v_loc = emb.shape[0]
    off = tp_index(ctx) * v_loc
    local = tokens - off
    ok = (local >= 0) & (local < v_loc)
    safe = jnp.clip(local, 0, v_loc - 1)
    out = jnp.take(emb, safe, axis=0)
    out = jnp.where(ok[..., None], out, 0)
    return psum_tp(out, ctx)


def vp_ce_loss(x: Array, emb_out: Array, targets: Array, ctx: ShardCtx,
               mask: Optional[Array] = None) -> Array:
    """Vocab-parallel cross entropy without materializing full logits.

    x: (T, D) final hidden; emb_out: (V/tp, D); targets: (T,) int32.
    Returns mean NLL over masked tokens (replicated over tp).
    """
    logits = (x.astype(jnp.float32) @ emb_out.astype(jnp.float32).T)  # (T, V/tp)
    m_loc = jnp.max(logits, axis=-1)
    m_loc = jax.lax.stop_gradient(m_loc)
    m = jax.lax.pmax(m_loc, ctx.tp_axis) if ctx.tp > 1 else m_loc
    z = jnp.sum(jnp.exp(logits - m[:, None]), axis=-1)
    z = psum_tp(z, ctx)
    v_loc = emb_out.shape[0]
    off = tp_index(ctx) * v_loc
    local = targets - off
    ok = (local >= 0) & (local < v_loc)
    safe = jnp.clip(local, 0, v_loc - 1)
    tgt_logit = jnp.take_along_axis(logits, safe[:, None], axis=1)[:, 0]
    tgt_logit = psum_tp(jnp.where(ok, tgt_logit, 0.0), ctx)
    nll = jnp.log(z) + m - tgt_logit
    if mask is not None:
        mf = mask.astype(jnp.float32)
        return jnp.sum(nll * mf) / jnp.maximum(jnp.sum(mf), 1.0)
    return jnp.mean(nll)
