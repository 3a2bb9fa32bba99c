"""Serving path: prefill + single-token decode with sharded KV caches.

Decode cache sharding (DESIGN §3 "SP"): the ``model`` axis is factored into
``g1`` kv-head groups x ``g2`` sequence shards (g1 = largest power-of-two
divisor of tp that divides n_kv).  Rank r = (i, j) holds

    cache[k|v]: (B_loc, n_kv/g1, S_max/g2, head_dim)

i.e. kv-head group i, sequence chunk j.  A decode step:

  1. gathers its head-group's query projection over the g2-subgroup
     (weights stay in the training TP layout — no serving-specific copy),
  2. attends its query group against its local seq chunk,
  3. merges partial softmax stats with psum/pmax over the g2-subgroup
     (flash-decoding combine, via ``axis_index_groups``),
  4. projects out through its own wo shard and psums over the full tp axis.

Window attention (recurrentgemma local blocks) uses a replicated ring-buffer
cache instead (W << S so replication is cheap) with head-sharded queries.

SSM / RG-LRU decode carry O(1) recurrent state; no KV growth.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.models.config import ModelConfig
from repro.models import layers as LY
from repro.models import ssm as SSM
from repro.models import rglru as RG
from repro.models import moe as MOE
from repro.models.sharding import (ShardCtx, gather_param, make_gathers,
                                   psum_tp, tp_index)
from repro.models.transformer import (all_metas, n_scan_steps, _gather_tree,
                                      _leaf_key, _sub)

Array = jax.Array


def groups_of(cfg: ModelConfig, ctx: ShardCtx) -> tuple[int, int]:
    g1 = cfg.kv_groups(ctx.tp)
    return g1, ctx.tp // g1


def seq_groups(cfg: ModelConfig, ctx: ShardCtx) -> list[list[int]]:
    g1, g2 = groups_of(cfg, ctx)
    return [[i * g2 + j for j in range(g2)] for i in range(g1)]


# ---------------------------------------------------------------------------
# Cache shapes (ShapeDtypeStruct builders for the dry-run + init for tests)
# ---------------------------------------------------------------------------

def cache_struct(cfg: ModelConfig, ctx: ShardCtx, batch_local: int,
                 s_max: int, dtype=jnp.bfloat16, kv_quant: bool = False) -> dict:
    """Local (per-device) cache pytree shapes.

    kv_quant: store k/v as int8 with per-(layer,batch,head) scales — a
    beyond-paper application of the quantization idea to the decode memory
    term (halves KV-cache HBM traffic vs bf16; see EXPERIMENTS.md "Perf").
    """
    L = n_scan_steps(cfg)
    B = batch_local
    if cfg.family == "ssm":
        inner = cfg.ssm_expand * cfg.d_model // ctx.tp
        h_loc = inner // cfg.ssm_headdim
        return {
            "ssm": (L, B, h_loc, cfg.ssm_headdim, cfg.ssm_state),
            "conv_x": (L, B, cfg.conv_width - 1, inner),
            "conv_bc": (L, B, cfg.conv_width - 1, 2 * cfg.ssm_state),
        }
    if cfg.family == "hybrid":
        c_loc = (cfg.lru_width or cfg.d_model) // ctx.tp
        W = cfg.window
        d = {
            "lru1": (L, B, c_loc), "conv1": (L, B, cfg.conv_width - 1, c_loc),
            "lru2": (L, B, c_loc), "conv2": (L, B, cfg.conv_width - 1, c_loc),
            # replicated ring-buffer window cache for the local-attn block
            "wk": (L, B, W, cfg.n_kv, cfg.head_dim),
            "wv": (L, B, W, cfg.n_kv, cfg.head_dim),
        }
        for t in range(cfg.n_layers % 3):          # unscanned tail rec layers
            d[f"tail{t}_lru"] = (B, c_loc)
            d[f"tail{t}_conv"] = (B, cfg.conv_width - 1, c_loc)
        return d
    if cfg.kv_lora_rank:
        # latent attention caches the normed latent and the shared rope key
        R, r = cfg.kv_lora_rank, cfg.qk_rope_dim
        d = {"ckv": (L, B, s_max, R), "kpe": (L, B, s_max, r)}
        for i in range(cfg.first_dense_layers):
            d[f"dense{i}_ckv"] = (B, s_max, R)
            d[f"dense{i}_kpe"] = (B, s_max, r)
        return d
    g1, g2 = groups_of(cfg, ctx)
    kv_loc = cfg.n_kv // g1
    s_loc = -(-s_max // g2)
    shapes = {
        "k": (L, B, kv_loc, s_loc, cfg.head_dim),
        "v": (L, B, kv_loc, s_loc, cfg.head_dim),
    }
    if kv_quant:
        # per-POSITION scales: old entries are immutable (a running per-head
        # scale would silently inflate previously written entries)
        shapes["k_scale"] = (L, B, kv_loc, s_loc)
        shapes["v_scale"] = (L, B, kv_loc, s_loc)
    if cfg.family == "encdec":
        shapes["xk"] = (cfg.n_layers, B, cfg.enc_seq, cfg.n_kv, cfg.head_dim)
        shapes["xv"] = (cfg.n_layers, B, cfg.enc_seq, cfg.n_kv, cfg.head_dim)
    return shapes


def cache_dtype(name: str, kv_quant: bool):
    if kv_quant and name in ("k", "v"):
        return jnp.int8
    if name.endswith("_scale"):
        return jnp.float32
    return jnp.bfloat16


def cache_zeros(cfg: ModelConfig, ctx: ShardCtx, batch_local: int,
                s_max: int, dtype=jnp.bfloat16, kv_quant: bool = False) -> dict:
    return {k: jnp.zeros(s, cache_dtype(k, kv_quant))
            for k, s in cache_struct(cfg, ctx, batch_local, s_max,
                                     kv_quant=kv_quant).items()}


# ---------------------------------------------------------------------------
# Decode attention (full-context, 2D-sharded cache)
# ---------------------------------------------------------------------------

def decode_attention(x: Array, wts: dict, ck: Array, cv: Array, pos: Array,
                     cfg: ModelConfig, ctx: ShardCtx,
                     kscale: Optional[Array] = None,
                     vscale: Optional[Array] = None):
    """x: (B, D) one token per sequence.  ck/cv: (B, kv_loc, S_loc, hd).

    With kscale/vscale given, ck/cv are int8 and are dequantized on the fly
    (absmax/127 per (batch, kv head, position); scales fold into the logits
    and probabilities post-einsum so the cache is read in int8).
    Returns (out (B,D) partial, new ck, new cv[, new kscale, new vscale]).
    """
    B, D = x.shape
    hd = cfg.head_dim
    g1, g2 = groups_of(cfg, ctx)
    kv_loc = cfg.n_kv // g1
    hg = cfg.n_heads // g1                       # query heads in my group
    h_loc = LY.local_heads(cfg, ctx)
    repl = LY.head_repl(cfg, ctx)
    shards = LY.head_shards(cfg, ctx)
    assert shards % g1 == 0, (shards, g1)
    s_loc = ck.shape[2]

    r = tp_index(ctx)
    i = r // g2 if g2 > 0 else r
    j = jnp.mod(r, g2) if g2 > 1 else jnp.zeros((), jnp.int32)
    sg = seq_groups(cfg, ctx)

    # -- group query projection: gather wq over the seq-subgroup --
    if h_loc == hg:
        wq_g = wts["wq"]                          # shard already covers group
    else:
        wq_g = jax.lax.all_gather(wts["wq"], ctx.tp_axis, axis=1, tiled=True,
                                  axis_index_groups=sg)
        if repl > 1:
            # dedupe replicated shard runs: keep every repl-th block
            wq_g = wq_g.reshape(D, g2, h_loc * hd)[:, ::repl].reshape(D, hg * hd)
    q = (x @ wq_g).reshape(B, hg, hd)

    # -- new k/v for my kv group (wk/wv replicated; slice group i) --
    k_all = (x @ wts["wk"]).reshape(B, cfg.n_kv, hd)
    v_all = (x @ wts["wv"]).reshape(B, cfg.n_kv, hd)
    if g1 > 1:
        k_new = jax.lax.dynamic_slice_in_dim(k_all, i * kv_loc, kv_loc, 1)
        v_new = jax.lax.dynamic_slice_in_dim(v_all, i * kv_loc, kv_loc, 1)
    else:
        k_new, v_new = k_all, v_all

    if cfg.qk_norm:
        q = LY.rms_norm(q, wts["qn"], cfg.norm_eps)
        k_new = LY.rms_norm(k_new, wts["kn"], cfg.norm_eps)
    cos, sin = LY.rope_angles(pos[None], hd, cfg.rope_theta)   # (1, hd/2)
    q = LY.apply_rope(q[:, None], cos, sin)[:, 0]
    k_new = LY.apply_rope(k_new[:, None], cos, sin)[:, 0]

    # -- write into my seq chunk if I own position pos --
    owner = (pos // s_loc)
    local_pos = jnp.mod(pos, s_loc)
    quant = kscale is not None
    if quant:
        # fresh per-position scale for the new entry (old entries immutable)
        ks_new = jnp.max(jnp.abs(k_new.astype(jnp.float32)), axis=-1)  # (B,kv)
        vs_new = jnp.max(jnp.abs(v_new.astype(jnp.float32)), axis=-1)
        kq = jnp.round(k_new.astype(jnp.float32)
                       / jnp.maximum(ks_new, 1e-9)[..., None] * 127.0)
        vq = jnp.round(v_new.astype(jnp.float32)
                       / jnp.maximum(vs_new, 1e-9)[..., None] * 127.0)
        k_w = jnp.clip(kq, -127, 127).astype(jnp.int8)
        v_w = jnp.clip(vq, -127, 127).astype(jnp.int8)
        upd_ks = jax.lax.dynamic_update_slice(kscale, ks_new[:, :, None],
                                              (0, 0, local_pos))
        upd_vs = jax.lax.dynamic_update_slice(vscale, vs_new[:, :, None],
                                              (0, 0, local_pos))
    else:
        k_w = k_new.astype(ck.dtype)
        v_w = v_new.astype(cv.dtype)
    upd_k = jax.lax.dynamic_update_slice(ck, k_w[:, :, None],
                                         (0, 0, local_pos, 0))
    upd_v = jax.lax.dynamic_update_slice(cv, v_w[:, :, None],
                                         (0, 0, local_pos, 0))
    mine = (owner == j) if g2 > 1 else jnp.array(True)
    ck = jnp.where(mine, upd_k, ck)
    cv = jnp.where(mine, upd_v, cv)
    if quant:
        kscale = jnp.where(mine, upd_ks, kscale)
        vscale = jnp.where(mine, upd_vs, vscale)

    # -- partial attention over my chunk --
    # GQA-batched: group-local head t shares kv head t // q_per_kv; instead
    # of materializing an expanded (B, hg, S, hd) copy of the cache (q_per_kv
    # x duplication, the decode memory hog), reshape q to (B, kv_loc, qpk,
    # hd) and batch the contraction per kv head — the cache is read once, in
    # its stored dtype (int8 dequant fuses into the dot on TPU).
    qpk = hg // max(kv_loc, 1)
    q4 = q.reshape(B, kv_loc, qpk, hd).astype(jnp.bfloat16)
    kf = ck.astype(jnp.bfloat16)
    vf = cv.astype(jnp.bfloat16)
    logits = jnp.einsum("bkqd,bksd->bkqs", q4, kf,
                        preferred_element_type=jnp.float32) / np.sqrt(hd)
    if quant:
        logits = logits * (kscale / 127.0)[:, :, None, :]
    gpos = (j * s_loc if g2 > 1 else 0) + jnp.arange(s_loc)
    valid = gpos <= pos
    logits = jnp.where(valid[None, None, None], logits, -1e30)

    m_loc = jnp.max(logits, axis=-1)             # (B, kv_loc, qpk)
    if g2 > 1:
        m = jax.lax.pmax(m_loc, ctx.tp_axis, axis_index_groups=sg)
    else:
        m = m_loc
    p = jnp.exp(logits - m[..., None])
    l_loc = jnp.sum(p, axis=-1)
    if quant:
        p = p * (vscale / 127.0)[:, :, None, :]   # fold v scales into probs
    o_loc = jnp.einsum("bkqs,bksd->bkqd", p.astype(jnp.bfloat16), vf,
                       preferred_element_type=jnp.float32)
    if g2 > 1:
        l = jax.lax.psum(l_loc, ctx.tp_axis, axis_index_groups=sg)
        o = jax.lax.psum(o_loc, ctx.tp_axis, axis_index_groups=sg)
    else:
        l, o = l_loc, o_loc
    out_g = (o / jnp.maximum(l, 1e-30)[..., None]).astype(x.dtype)
    out_g = out_g.reshape(B, hg, hd)             # (B, hg, hd)

    # -- my wo shard covers my h_loc heads: locate my shard within group --
    if h_loc < hg:
        off = (r // repl) * h_loc - i * hg
        out_mine = jax.lax.dynamic_slice_in_dim(out_g, off, h_loc, 1)
    else:
        out_mine = out_g
    out = out_mine.reshape(B, h_loc * hd) @ wts["wo"]  # partial over tp
    if quant:
        return out, ck, cv, kscale, vscale
    return out, ck, cv


def mla_decode(x: Array, wts: dict, ckv: Array, kpe: Array, pos: Array,
               cfg: ModelConfig):
    """Latent attention of one token per sequence against the latent cache
    (the expanded form, as layers.mla_attention; tp=1).  x: (B, D); ckv:
    (B, S, R); kpe: (B, S, r).  Returns (out (B, D), ckv, kpe)."""
    B, _ = x.shape
    H, hd, r = cfg.n_heads, cfg.head_dim, cfg.qk_rope_dim
    vd, R = cfg.v_head_dim, cfg.kv_lora_rank
    q = (x @ wts["wq"]).reshape(B, 1, H, hd + r)
    kv_a = x @ wts["wkv_a"]
    c = LY.rms_norm(kv_a[:, :R], wts["kv_norm"], cfg.norm_eps)
    cos, sin = LY.rope_angles(pos[None], r, cfg.rope_theta)
    q = jnp.concatenate([q[..., :hd], LY.apply_rope(q[..., hd:], cos, sin)],
                        -1)[:, 0]
    k_pe = LY.apply_rope(kv_a[:, None, None, R:], cos, sin)[:, 0, 0]
    ckv = jax.lax.dynamic_update_slice(ckv, c[:, None].astype(ckv.dtype),
                                       (0, pos, 0))
    kpe = jax.lax.dynamic_update_slice(kpe, k_pe[:, None].astype(kpe.dtype),
                                       (0, pos, 0))
    S = ckv.shape[1]
    kv = (ckv @ wts["wkv_b"]).reshape(B, S, H, hd + vd)
    k = jnp.concatenate([kv[..., :hd], jnp.broadcast_to(
        kpe[:, :, None], (B, S, H, r))], -1)
    logits = jnp.einsum("bhd,bshd->bhs", q, k,
                        preferred_element_type=jnp.float32) / np.sqrt(hd + r)
    logits = jnp.where((jnp.arange(S) <= pos)[None, None], logits, -1e30)
    p = jax.nn.softmax(logits, axis=-1).astype(kv.dtype)
    o = jnp.einsum("bhs,bshd->bhd", p, kv[..., hd:])
    return o.reshape(B, H * vd) @ wts["wo"], ckv, kpe


def _mla_decode_layer(x: Array, wts: dict, ckv: Array, kpe: Array,
                      pos: Array, cfg: ModelConfig):
    a = LY.rms_norm(x, wts["ln1"], cfg.norm_eps)
    att, ckv, kpe = mla_decode(a, wts, ckv, kpe, pos, cfg)
    x = x + att
    m = LY.rms_norm(x, wts["ln2"], cfg.norm_eps)
    if "router" in wts:
        out, _, _ = MOE.share_moe(m, wts, cfg, None, m.shape[0])
    else:
        out = LY.mlp(m, wts, cfg)
    return x + out, ckv, kpe


def window_decode_attention(x: Array, wts: dict, ck: Array, cv: Array,
                            pos: Array, cfg: ModelConfig, ctx: ShardCtx):
    """Ring-buffer window cache, replicated across tp; heads sharded.

    ck/cv: (B, W, n_kv, hd).  Returns (out partial, ck, cv).
    """
    B, D = x.shape
    hd = cfg.head_dim
    W = ck.shape[1]
    h_loc = LY.local_heads(cfg, ctx)

    q = (x @ wts["wq"]).reshape(B, h_loc, hd)
    k_new = (x @ wts["wk"]).reshape(B, cfg.n_kv, hd)
    v_new = (x @ wts["wv"]).reshape(B, cfg.n_kv, hd)
    cos, sin = LY.rope_angles(pos[None], hd, cfg.rope_theta)
    q = LY.apply_rope(q[:, None], cos, sin)[:, 0]
    k_new = LY.apply_rope(k_new[:, None], cos, sin)[:, 0]

    slot = jnp.mod(pos, W)
    ck = jax.lax.dynamic_update_slice(ck, k_new[:, None].astype(ck.dtype),
                                      (0, slot, 0, 0))
    cv = jax.lax.dynamic_update_slice(cv, v_new[:, None].astype(cv.dtype),
                                      (0, slot, 0, 0))
    kv_map = LY._kv_map_local(cfg, ctx)
    k_h = jnp.take(ck, kv_map, axis=2)           # (B, W, h_loc, hd)
    v_h = jnp.take(cv, kv_map, axis=2)
    logits = jnp.einsum("bhd,bwhd->bhw", q.astype(jnp.float32),
                        k_h.astype(jnp.float32)) / np.sqrt(hd)
    # ring-buffer validity: slot w holds position p_w = pos - ((slot - w) mod W)
    wids = jnp.arange(W)
    p_w = pos - jnp.mod(slot - wids, W)
    valid = (p_w >= 0) & (p_w <= pos) & (pos - p_w < cfg.window)
    logits = jnp.where(valid[None, None], logits, -1e30)
    probs = jax.nn.softmax(logits, axis=-1)
    o = jnp.einsum("bhw,bwhd->bhd", probs, v_h.astype(jnp.float32))
    out = o.astype(x.dtype).reshape(B, h_loc * hd) @ wts["wo"]
    return out, ck, cv


# ---------------------------------------------------------------------------
# serve_step builders
# ---------------------------------------------------------------------------

def _moe_decode(x: Array, wts: dict, cfg: ModelConfig, ctx: ShardCtx) -> Array:
    """MoE for (B, D) decode tokens: pad tokens to a tp multiple, slice."""
    B, D = x.shape
    if ctx.tp == 1:
        out, _ = MOE.moe_mlp(x, wts, cfg, ctx)
        return out
    Bp = -(-B // ctx.tp) * ctx.tp
    xp = jnp.pad(x, ((0, Bp - B), (0, 0)))
    t_loc = Bp // ctx.tp
    sl = jax.lax.dynamic_slice_in_dim(xp, tp_index(ctx) * t_loc, t_loc, 0)
    out, _ = MOE.moe_mlp(sl, wts, cfg, ctx)
    full = jax.lax.all_gather(out, ctx.tp_axis, axis=0, tiled=True)
    return full[:B]


def make_encdec_serve_step(cfg: ModelConfig, ctx: ShardCtx):
    """Whisper-style decoder step: self-attn decode + cross-attn against the
    precomputed encoder K/V cache (xk/xv, built once per audio segment by
    prefill).  cache: {"k","v" (L,B,kv_loc,S_loc,hd), "xk","xv"
    (L,B,Se,KV,hd)}."""
    from repro.models import encdec as ED
    metas = ED.encdec_metas(cfg, ctx)
    gathers = make_gathers(ctx)
    L = cfg.n_layers

    def zero_y():
        return jnp.ones((), jnp.float32)

    def serve_step(params, cache, tokens, pos, key):
        from repro.dist.fsdp import TELE_WIDTH
        B = tokens.shape[0]
        tz = jnp.zeros((TELE_WIDTH,), jnp.float32)
        kt = jax.random.fold_in(key, 0)
        emb = gather_param(params["top"]["embed"], metas["top"]["embed"], ctx,
                           zero_y(), _leaf_key(kt, "embed"), tz, gathers)
        x = LY.vp_embed(tokens[:, 0], emb, ctx)

        def body(carry, xs):
            xc = carry
            lp, lc, idx = xs
            kl = jax.random.fold_in(key, idx + 1)
            ly = {k: zero_y() for k in metas["dec"]}
            lt = {k: tz for k in metas["dec"]}
            wts = _gather_tree(lp, metas["dec"], ctx, ly, kl, lt, gathers)
            a = LY.rms_norm(xc, wts["ln1"], cfg.norm_eps)
            att, ck, cv = decode_attention(a, wts, lc["k"], lc["v"], pos,
                                           cfg, ctx)
            xc = xc + psum_tp(att, ctx) / LY.head_repl(cfg, ctx)
            c = LY.rms_norm(xc, wts["ln2"], cfg.norm_eps)
            xa = ED.cross_attention(c[:, None], lc["xk"], lc["xv"],
                                    wts, cfg, ctx)[:, 0]
            xc = xc + psum_tp(xa, ctx) / LY.head_repl(cfg, ctx)
            m = LY.rms_norm(xc, wts["ln3"], cfg.norm_eps)
            xc = xc + psum_tp(LY.mlp(m[:, None], wts, cfg)[:, 0], ctx)
            return xc, {"k": ck, "v": cv, "xk": lc["xk"], "xv": lc["xv"]}

        x, new_cache = jax.lax.scan(
            body, x, (params["dec"], cache, jnp.arange(L, dtype=jnp.int32)))

        fn = gather_param(params["top"]["final_norm"],
                          metas["top"]["final_norm"], ctx, zero_y(),
                          _leaf_key(kt, "fn"), tz, gathers)
        x = LY.rms_norm(x, fn, cfg.norm_eps)
        head = gather_param(params["top"]["lm_head"], metas["top"]["lm_head"],
                            ctx, zero_y(), _leaf_key(kt, "head"), tz, gathers)
        logits = x.astype(jnp.float32) @ head.astype(jnp.float32).T
        loc_max = jnp.max(logits, axis=-1)
        loc_arg = jnp.argmax(logits, axis=-1) + tp_index(ctx) * head.shape[0]
        if ctx.tp > 1:
            gmax = jax.lax.pmax(loc_max, ctx.tp_axis)
            cand = jnp.where(loc_max >= gmax, loc_arg, 0)
            nxt = jax.lax.pmax(cand, ctx.tp_axis)
        else:
            nxt = loc_arg
        return nxt.astype(jnp.int32), new_cache

    return serve_step


def make_serve_step(cfg: ModelConfig, ctx: ShardCtx, kv_quant: bool = False):
    """Returns serve_step(params, cache, tokens (B,1), pos ()) ->
    (next_token (B,), new_cache).  Runs inside shard_map."""
    if cfg.family == "encdec":
        return make_encdec_serve_step(cfg, ctx)
    metas = all_metas(cfg, ctx)
    gathers = make_gathers(ctx)
    L = n_scan_steps(cfg)

    def zero_y():
        return jnp.ones((), jnp.float32)

    def serve_step(params, cache, tokens, pos, key):
        from repro.dist.fsdp import TELE_WIDTH
        B = tokens.shape[0]
        tz = jnp.zeros((TELE_WIDTH,), jnp.float32)
        kt = jax.random.fold_in(key, 0)
        emb = gather_param(params["top"]["embed"], metas["top"]["embed"], ctx,
                           zero_y(), _leaf_key(kt, "embed"), tz, gathers)
        x = LY.vp_embed(tokens[:, 0], emb, ctx) * cfg.emb_scale   # (B, D)
        lead = {}
        for i in range(cfg.first_dense_layers):
            p = f"dense{i}_"
            kl = jax.random.fold_in(key, 20_000 + i)
            sw = {k[len(p):]: gather_param(
                params["top"][k], metas["top"][k], ctx, zero_y(),
                _leaf_key(kl, k), tz, gathers)
                for k in metas["top"] if k.startswith(p)}
            x, lead[f"{p}ckv"], lead[f"{p}kpe"] = _mla_decode_layer(
                x, sw, cache[f"{p}ckv"], cache[f"{p}kpe"], pos, cfg)

        def body(carry, xs):
            xc = carry
            lp, lc, idx = xs
            kl = jax.random.fold_in(key, idx + 1)
            ly = {k: zero_y() for k in metas["layers"]}
            lt = {k: tz for k in metas["layers"]}
            wts = _gather_tree(lp, metas["layers"], ctx, ly, kl, lt, gathers)
            nc = dict(lc)
            if cfg.family == "ssm":
                a = LY.rms_norm(xc, wts["ln1"], cfg.norm_eps)
                st = {"ssm": lc["ssm"], "conv_x": lc["conv_x"],
                      "conv_bc": lc["conv_bc"]}
                out, ns = SSM.mamba2_block(a[:, None], wts, cfg, ctx, state=st)
                xc = xc + psum_tp(out[:, 0], ctx)
                nc = {"ssm": ns["ssm"], "conv_x": ns["conv_x"],
                      "conv_bc": ns["conv_bc"]}
            elif cfg.family == "hybrid":
                xc, nc = _hybrid_decode_unit(xc, wts, lc, pos, cfg, ctx)
            elif cfg.kv_lora_rank:
                xc, nc["ckv"], nc["kpe"] = _mla_decode_layer(
                    xc, wts, lc["ckv"], lc["kpe"], pos, cfg)
            else:
                a = LY.rms_norm(xc, wts["ln1"], cfg.norm_eps)
                if kv_quant:
                    att, ck, cv, ks, vs = decode_attention(
                        a, wts, lc["k"], lc["v"], pos, cfg, ctx,
                        kscale=lc["k_scale"], vscale=lc["v_scale"])
                    nc["k_scale"], nc["v_scale"] = ks, vs
                else:
                    att, ck, cv = decode_attention(a, wts, lc["k"], lc["v"],
                                                   pos, cfg, ctx)
                xc = xc + psum_tp(att, ctx) / LY.head_repl(cfg, ctx)
                nc["k"], nc["v"] = ck, cv
                m = LY.rms_norm(xc, wts["ln2"], cfg.norm_eps)
                if cfg.family == "moe":
                    xc = xc + _moe_decode(m, wts, cfg, ctx)
                else:
                    xc = xc + psum_tp(LY.mlp(m[:, None], wts, cfg)[:, 0], ctx)
            return xc, nc

        # scan over layers, cache as stacked xs/ys
        def sbody(carry, xs):
            lp = {k: xs[0][k] for k in xs[0]}
            lc = {k: xs[1][k] for k in xs[1]}
            out, nc = body(carry, (lp, lc, xs[2]))
            return out, nc

        cache_scan = {k: v for k, v in cache.items()
                      if not k.startswith(("tail", "dense"))}
        x, new_cache = jax.lax.scan(
            sbody, x, (params["layers"], cache_scan,
                       jnp.arange(L, dtype=jnp.int32)))
        new_cache.update(lead)

        # hybrid unscanned tail recurrent layers
        if cfg.family == "hybrid" and cfg.n_layers % 3:
            for t in range(cfg.n_layers % 3):
                p = f"tail{t}_"
                names = [k for k in metas["top"] if k.startswith(p)]
                kl = jax.random.fold_in(key, 10_000 + t)
                sw = {k[len(p):]: gather_param(
                    params["top"][k], metas["top"][k], ctx, zero_y(),
                    _leaf_key(kl, k), tz, gathers) for k in names}
                a = LY.rms_norm(x, sw["ln1"], cfg.norm_eps)
                st = {"lru": cache[f"{p}lru"], "conv": cache[f"{p}conv"]}
                out, ns = RG.recurrent_block(a[:, None], sw, cfg, ctx, state=st)
                x = x + psum_tp(out[:, 0], ctx)
                new_cache[f"{p}lru"] = ns["lru"]
                new_cache[f"{p}conv"] = ns["conv"]
                m = LY.rms_norm(x, sw["ln2"], cfg.norm_eps)
                x = x + psum_tp(LY.mlp(m[:, None], sw, cfg)[:, 0], ctx)

        fn = gather_param(params["top"]["final_norm"],
                          metas["top"]["final_norm"], ctx, zero_y(),
                          _leaf_key(kt, "fn"), tz, gathers)
        x = LY.rms_norm(x, fn, cfg.norm_eps)
        if cfg.tie_embeddings:
            head = emb
        else:
            head = gather_param(params["top"]["lm_head"], metas["top"]["lm_head"],
                                ctx, zero_y(), _leaf_key(kt, "head"), tz, gathers)
        logits = x.astype(jnp.float32) @ head.astype(jnp.float32).T  # (B, V/tp)
        # vocab-parallel greedy sampling
        loc_max = jnp.max(logits, axis=-1)
        loc_arg = jnp.argmax(logits, axis=-1) + tp_index(ctx) * head.shape[0]
        if ctx.tp > 1:
            gmax = jax.lax.pmax(loc_max, ctx.tp_axis)
            cand = jnp.where(loc_max >= gmax, loc_arg, 0)
            nxt = jax.lax.pmax(cand, ctx.tp_axis)
        else:
            nxt = loc_arg
        return nxt.astype(jnp.int32), new_cache

    return serve_step


def _hybrid_decode_unit(x: Array, wts: dict, lc: dict, pos: Array,
                        cfg: ModelConfig, ctx: ShardCtx):
    nc = dict(lc)
    for n, p in ((1, "r1_"), (2, "r2_")):
        sw = _sub(wts, p)
        a = LY.rms_norm(x, sw["ln1"], cfg.norm_eps)
        st = {"lru": lc[f"lru{n}"], "conv": lc[f"conv{n}"]}
        out, ns = RG.recurrent_block(a[:, None], sw, cfg, ctx, state=st)
        x = x + psum_tp(out[:, 0], ctx)
        nc[f"lru{n}"], nc[f"conv{n}"] = ns["lru"], ns["conv"]
        m = LY.rms_norm(x, sw["ln2"], cfg.norm_eps)
        x = x + psum_tp(LY.mlp(m[:, None], sw, cfg)[:, 0], ctx)
    sw = _sub(wts, "at_")
    a = LY.rms_norm(x, sw["ln1"], cfg.norm_eps)
    att, ck, cv = window_decode_attention(a, sw, lc["wk"], lc["wv"], pos,
                                          cfg, ctx)
    x = x + psum_tp(att, ctx) / LY.head_repl(cfg, ctx)
    nc["wk"], nc["wv"] = ck, cv
    m = LY.rms_norm(x, sw["ln2"], cfg.norm_eps)
    x = x + psum_tp(LY.mlp(m[:, None], sw, cfg)[:, 0], ctx)
    return x, nc


# ---------------------------------------------------------------------------
# Prefill (forward pass writing the cache; used by prefill_32k dry-runs)
# ---------------------------------------------------------------------------

def make_prefill(cfg: ModelConfig, ctx: ShardCtx):
    """prefill(params, tokens (B,S), key) -> (last_hidden (B,D), cache).

    Uses the training forward (head-sharded attention) and re-shards the
    computed K/V into the decode layout (kv-group x seq-chunk local slices).
    Latent attention has no prefill here: its cache fills token by token.
    """
    if cfg.kv_lora_rank:
        raise NotImplementedError(f"{cfg.arch}: no prefill for latent "
                                  f"attention")
    metas = all_metas(cfg, ctx)
    gathers = make_gathers(ctx)
    L = n_scan_steps(cfg)
    g1, g2 = groups_of(cfg, ctx)

    def zero_y():
        return jnp.ones((), jnp.float32)

    def prefill(params, tokens, key, img=None):
        from repro.dist.fsdp import TELE_WIDTH
        B, S = tokens.shape
        tz = jnp.zeros((TELE_WIDTH,), jnp.float32)
        kt = jax.random.fold_in(key, 0)
        emb = gather_param(params["top"]["embed"], metas["top"]["embed"], ctx,
                           zero_y(), _leaf_key(kt, "embed"), tz, gathers)
        x = LY.vp_embed(tokens, emb, ctx) * cfg.emb_scale
        if img is not None:                      # vlm: patch embeds prefix
            x = jnp.concatenate([img.astype(x.dtype), x], axis=1)
            S = x.shape[1]
        positions = jnp.arange(S, dtype=jnp.int32)
        r = tp_index(ctx)
        i = r // g2 if g2 > 0 else r
        j = jnp.mod(r, g2) if g2 > 1 else jnp.zeros((), jnp.int32)
        kv_loc = max(cfg.n_kv // g1, 1)
        s_loc = -(-S // g2)

        def body(carry, xs):
            xc = carry
            lp, idx = xs
            kl = jax.random.fold_in(key, idx + 1)
            ly = {k: zero_y() for k in metas["layers"]}
            lt = {k: tz for k in metas["layers"]}
            wts = _gather_tree(lp, metas["layers"], ctx, ly, kl, lt, gathers)
            if cfg.family == "ssm":
                a = LY.rms_norm(xc, wts["ln1"], cfg.norm_eps)
                out, ns = SSM.mamba2_block(a, wts, cfg, ctx)
                xc = xc + psum_tp(out, ctx)
                conv_in_x = a @ wts["wx"]
                conv_in_bc = a @ wts["wbc"]
                Wc = cfg.conv_width - 1
                return xc, {"ssm": ns["ssm"].astype(jnp.bfloat16),
                            "conv_x": conv_in_x[:, -Wc:].astype(jnp.bfloat16),
                            "conv_bc": conv_in_bc[:, -Wc:].astype(jnp.bfloat16)}
            if cfg.family == "hybrid":
                piece = {}
                Wc = cfg.conv_width - 1
                for nsub, p in ((1, "r1_"), (2, "r2_")):
                    sw = _sub(wts, p)
                    a = LY.rms_norm(xc, sw["ln1"], cfg.norm_eps)
                    xbr_raw = a @ sw["wx"]
                    out, ns = RG.recurrent_block(a, sw, cfg, ctx)
                    xc = xc + psum_tp(out, ctx)
                    piece[f"lru{nsub}"] = ns["lru"].astype(jnp.bfloat16)
                    piece[f"conv{nsub}"] = xbr_raw[:, -Wc:].astype(jnp.bfloat16)
                    m = LY.rms_norm(xc, sw["ln2"], cfg.norm_eps)
                    xc = xc + psum_tp(LY.mlp(m, sw, cfg), ctx)
                sw = _sub(wts, "at_")
                a = LY.rms_norm(xc, sw["ln1"], cfg.norm_eps)
                att, (k, v) = LY.attention(a, sw, cfg, ctx, positions=positions,
                                           causal=True, window=cfg.window,
                                           kv_out=True)
                xc = xc + LY.attn_exit(att, cfg, ctx)
                m = LY.rms_norm(xc, sw["ln2"], cfg.norm_eps)
                xc = xc + psum_tp(LY.mlp(m, sw, cfg), ctx)
                # window ring buffer: last W positions (slot = pos mod W)
                Wn = cfg.window
                kw_ = k[:, -Wn:] if k.shape[1] >= Wn else jnp.pad(
                    k, ((0, 0), (Wn - k.shape[1], 0), (0, 0), (0, 0)))
                vw_ = v[:, -Wn:] if v.shape[1] >= Wn else jnp.pad(
                    v, ((0, 0), (Wn - v.shape[1], 0), (0, 0), (0, 0)))
                # roll so that position p lands in slot p mod W
                shift = jnp.mod(S, Wn)
                kw_ = jnp.roll(kw_, shift, axis=1)
                vw_ = jnp.roll(vw_, shift, axis=1)
                piece["wk"] = kw_.astype(jnp.bfloat16)
                piece["wv"] = vw_.astype(jnp.bfloat16)
                return xc, piece
            a = LY.rms_norm(xc, wts["ln1"], cfg.norm_eps)
            att, (k, v) = LY.attention(a, wts, cfg, ctx, positions=positions,
                                       causal=True, kv_out=True)
            xc = xc + psum_tp(att, ctx)
            m = LY.rms_norm(xc, wts["ln2"], cfg.norm_eps)
            if cfg.family == "moe":
                from repro.models.transformer import _moe_apply
                out, _ = _moe_apply(m, wts, cfg, ctx)
                xc = xc + out
            else:
                xc = xc + psum_tp(LY.mlp(m, wts, cfg), ctx)
            # re-shard k/v (B,S,KV,hd) -> decode layout (B,kv_loc,s_loc,hd)
            kk = jnp.swapaxes(k, 1, 2)                       # (B,KV,S,hd)
            vv = jnp.swapaxes(v, 1, 2)
            if g1 > 1:
                kk = jax.lax.dynamic_slice_in_dim(kk, i * kv_loc, kv_loc, 1)
                vv = jax.lax.dynamic_slice_in_dim(vv, i * kv_loc, kv_loc, 1)
            if g2 > 1:
                pad = g2 * s_loc - S
                kk = jnp.pad(kk, ((0, 0), (0, 0), (0, pad), (0, 0)))
                vv = jnp.pad(vv, ((0, 0), (0, 0), (0, pad), (0, 0)))
                kk = jax.lax.dynamic_slice_in_dim(kk, j * s_loc, s_loc, 2)
                vv = jax.lax.dynamic_slice_in_dim(vv, j * s_loc, s_loc, 2)
            return xc, {"k": kk.astype(jnp.bfloat16),
                        "v": vv.astype(jnp.bfloat16)}

        x, cache = jax.lax.scan(body, x,
                                (params["layers"], jnp.arange(L, dtype=jnp.int32)))
        fn = gather_param(params["top"]["final_norm"],
                          metas["top"]["final_norm"], ctx, zero_y(),
                          _leaf_key(kt, "fn"), tz, gathers)
        last = LY.rms_norm(x[:, -1], fn, cfg.norm_eps)
        return last, cache

    return prefill


def make_encdec_prefill(cfg: ModelConfig, ctx: ShardCtx):
    """Whisper prefill: run the encoder over the (stub) frames, build the
    per-decoder-layer cross K/V cache, and prefill the decoder self-attn
    cache over the prompt tokens."""
    from repro.models import encdec as ED
    metas = ED.encdec_metas(cfg, ctx)
    gathers = make_gathers(ctx)
    g1, g2 = groups_of(cfg, ctx)

    def zero_y():
        return jnp.ones((), jnp.float32)

    def prefill(params, frames, tokens, key):
        from repro.dist.fsdp import TELE_WIDTH
        B, S = tokens.shape
        Se = frames.shape[1]
        tz = jnp.zeros((TELE_WIDTH,), jnp.float32)
        kt = jax.random.fold_in(key, 0)
        x = frames.astype(jnp.bfloat16)
        pos_e = jnp.arange(Se, dtype=jnp.int32)

        def ebody(carry, xs):
            xc = carry
            lp, idx = xs
            kl = jax.random.fold_in(key, idx + 1)
            ly = {k: zero_y() for k in metas["enc"]}
            lt = {k: tz for k in metas["enc"]}
            wts = _gather_tree(lp, metas["enc"], ctx, ly, kl, lt, gathers)
            a = LY.rms_norm(xc, wts["ln1"], cfg.norm_eps)
            att = LY.attention(a, wts, cfg, ctx, positions=pos_e, causal=False)
            xc = xc + LY.attn_exit(att, cfg, ctx)
            m = LY.rms_norm(xc, wts["ln2"], cfg.norm_eps)
            xc = xc + psum_tp(LY.mlp(m, wts, cfg), ctx)
            return xc, None

        x, _ = jax.lax.scan(ebody, x, (params["enc"],
                                       jnp.arange(cfg.enc_layers, dtype=jnp.int32)))
        en = gather_param(params["top"]["enc_norm"], metas["top"]["enc_norm"],
                          ctx, zero_y(), _leaf_key(kt, "en"), tz, gathers)
        memory = LY.rms_norm(x, en, cfg.norm_eps)

        emb = gather_param(params["top"]["embed"], metas["top"]["embed"], ctx,
                           zero_y(), _leaf_key(kt, "embed"), tz, gathers)
        h = LY.vp_embed(tokens, emb, ctx)
        pos_d = jnp.arange(S, dtype=jnp.int32)
        r = tp_index(ctx)
        i = r // g2 if g2 > 0 else r
        j = jnp.mod(r, g2) if g2 > 1 else jnp.zeros((), jnp.int32)
        kv_loc = max(cfg.n_kv // g1, 1)
        s_loc = -(-S // g2)

        def dbody(carry, xs):
            hc = carry
            lp, idx = xs
            kl = jax.random.fold_in(key, 1000 + idx)
            ly = {k: zero_y() for k in metas["dec"]}
            lt = {k: tz for k in metas["dec"]}
            wts = _gather_tree(lp, metas["dec"], ctx, ly, kl, lt, gathers)
            a = LY.rms_norm(hc, wts["ln1"], cfg.norm_eps)
            att, (k, v) = LY.attention(a, wts, cfg, ctx, positions=pos_d,
                                       causal=True, kv_out=True)
            hc = hc + LY.attn_exit(att, cfg, ctx)
            c = LY.rms_norm(hc, wts["ln2"], cfg.norm_eps)
            mk = (memory @ wts["x_wk"]).reshape(B, Se, cfg.n_kv, cfg.head_dim)
            mv = (memory @ wts["x_wv"]).reshape(B, Se, cfg.n_kv, cfg.head_dim)
            xa = ED.cross_attention(c, mk, mv, wts, cfg, ctx)
            hc = hc + LY.attn_exit(xa, cfg, ctx)
            m = LY.rms_norm(hc, wts["ln3"], cfg.norm_eps)
            hc = hc + psum_tp(LY.mlp(m, wts, cfg), ctx)
            # decode-layout self KV
            kk = jnp.swapaxes(k, 1, 2)
            vv = jnp.swapaxes(v, 1, 2)
            if g1 > 1:
                kk = jax.lax.dynamic_slice_in_dim(kk, i * kv_loc, kv_loc, 1)
                vv = jax.lax.dynamic_slice_in_dim(vv, i * kv_loc, kv_loc, 1)
            if g2 > 1:
                pad = g2 * s_loc - S
                kk = jnp.pad(kk, ((0, 0), (0, 0), (0, pad), (0, 0)))
                vv = jnp.pad(vv, ((0, 0), (0, 0), (0, pad), (0, 0)))
                kk = jax.lax.dynamic_slice_in_dim(kk, j * s_loc, s_loc, 2)
                vv = jax.lax.dynamic_slice_in_dim(vv, j * s_loc, s_loc, 2)
            return hc, {"k": kk.astype(jnp.bfloat16),
                        "v": vv.astype(jnp.bfloat16),
                        "xk": mk.astype(jnp.bfloat16),
                        "xv": mv.astype(jnp.bfloat16)}

        h, cache = jax.lax.scan(dbody, h,
                                (params["dec"], jnp.arange(cfg.n_layers,
                                                           dtype=jnp.int32)))
        fn = gather_param(params["top"]["final_norm"],
                          metas["top"]["final_norm"], ctx, zero_y(),
                          _leaf_key(kt, "fn"), tz, gathers)
        return LY.rms_norm(h[:, -1], fn, cfg.norm_eps), cache

    return prefill
