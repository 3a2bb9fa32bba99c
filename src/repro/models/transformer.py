"""Model assembly: parameter metas, init, and the training forward pass.

Families covered here: dense, moe, ssm, hybrid (RG-LRU), vlm.
Encoder-decoder (whisper) lives in models/encdec.py on the same substrate.

Everything below executes *inside* ``jax.shard_map``; parameters arrive in
ZeRO-3 storage layout (see models/sharding.py) and each layer re-gathers its
weights through the custom-vjp FSDP gather whose backward runs the paper's
quantized reduce-scatter.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.models.config import ModelConfig
from repro.models import layers as LY
from repro.models import moe as MOE
from repro.models import ssm as SSM
from repro.models import rglru as RG
from repro.models.sharding import (LeafMeta, ShardCtx, gather_param,
                                   gather_param_async, gather_param_wait,
                                   make_gathers, make_split_gathers,
                                   init_leaf, tp_index, psum_tp,
                                   all_gather_tp)

Array = jax.Array


# ---------------------------------------------------------------------------
# Leaf metas per family
# ---------------------------------------------------------------------------

def _attn_metas(cfg: ModelConfig, ctx: ShardCtx, prefix: str = "",
                kv: Optional[int] = None) -> dict[str, LeafMeta]:
    from repro.models.layers import head_repl, local_heads
    D, hd = cfg.d_model, cfg.head_dim
    h_loc = local_heads(cfg, ctx)
    repl = head_repl(cfg, ctx)
    kv = cfg.n_kv if kv is None else kv
    m = {
        f"{prefix}wq": LeafMeta((D, h_loc * hd), tp_dim=1, tp_repl=repl),
        f"{prefix}wk": LeafMeta((D, kv * hd), tp_dim=None),
        f"{prefix}wv": LeafMeta((D, kv * hd), tp_dim=None),
        f"{prefix}wo": LeafMeta((h_loc * hd, D), tp_dim=0, tp_repl=repl),
    }
    if cfg.qk_norm:
        m[f"{prefix}qn"] = LeafMeta((hd,), tp_dim=None, init="ones")
        m[f"{prefix}kn"] = LeafMeta((hd,), tp_dim=None, init="ones")
    return m


def _mlp_metas(cfg: ModelConfig, ctx: ShardCtx, prefix: str = "") -> dict[str, LeafMeta]:
    D, F = cfg.d_model, cfg.d_ff
    f_loc = F // ctx.tp
    if cfg.act == "swiglu":
        return {
            f"{prefix}wg": LeafMeta((D, f_loc), tp_dim=1),
            f"{prefix}wu": LeafMeta((D, f_loc), tp_dim=1),
            f"{prefix}wd": LeafMeta((f_loc, D), tp_dim=0),
        }
    return {
        f"{prefix}wi": LeafMeta((D, f_loc), tp_dim=1),
        f"{prefix}wd": LeafMeta((f_loc, D), tp_dim=0),
    }


def _moe_metas(cfg: ModelConfig, ctx: ShardCtx) -> dict[str, LeafMeta]:
    D, F, E = cfg.d_model, cfg.d_ff, cfg.n_experts
    e_loc = E // ctx.tp if E >= ctx.tp else E
    m = {
        "router": LeafMeta((D, E), tp_dim=None),
        "w1": LeafMeta((e_loc, D, F), tp_dim=0),
        "w2": LeafMeta((e_loc, F, D), tp_dim=0),
    }
    if cfg.act == "swiglu":
        m["w3"] = LeafMeta((e_loc, D, F), tp_dim=0)
    return m


def _mla_metas(cfg: ModelConfig, prefix: str = "") -> dict[str, LeafMeta]:
    """Latent attention (layers.mla_attention); every head on this chip."""
    D, H, hd, r = cfg.d_model, cfg.n_heads, cfg.head_dim, cfg.qk_rope_dim
    R, vd = cfg.kv_lora_rank, cfg.v_head_dim
    return {
        f"{prefix}wq": LeafMeta((D, H * (hd + r))),
        f"{prefix}wkv_a": LeafMeta((D, R + r)),
        f"{prefix}kv_norm": LeafMeta((R,), init="ones"),
        f"{prefix}wkv_b": LeafMeta((R, H * (hd + vd))),
        f"{prefix}wo": LeafMeta((H * vd, D)),
    }


def _share_metas(cfg: ModelConfig) -> dict[str, LeafMeta]:
    """The router over every expert, the held experts, the shared experts
    (moe.share_moe)."""
    D, F, E = cfg.d_model, cfg.expert_width, cfg.held
    m = {
        "router": LeafMeta((D, cfg.n_experts)),
        "w1": LeafMeta((E, D, F)),
        "w3": LeafMeta((E, D, F)),
        "w2": LeafMeta((E, F, D)),
    }
    if cfg.n_shared_experts:
        Fs = cfg.n_shared_experts * F
        m.update({"shared_wg": LeafMeta((D, Fs)),
                  "shared_wu": LeafMeta((D, Fs)),
                  "shared_wd": LeafMeta((Fs, D))})
    return m


def _ssm_metas(cfg: ModelConfig, ctx: ShardCtx) -> dict[str, LeafMeta]:
    D = cfg.d_model
    inner = cfg.ssm_expand * D
    i_loc = inner // ctx.tp
    P = cfg.ssm_headdim
    h_loc = i_loc // P
    N = cfg.ssm_state
    W = cfg.conv_width
    return {
        "wz": LeafMeta((D, i_loc), tp_dim=1),
        "wx": LeafMeta((D, i_loc), tp_dim=1),
        "wbc": LeafMeta((D, 2 * N), tp_dim=None),
        "wdt": LeafMeta((D, h_loc), tp_dim=1),
        "conv_x": LeafMeta((W, i_loc), tp_dim=1, init="normal", init_scale=0.5),
        "conv_bc": LeafMeta((W, 2 * N), tp_dim=None, init="normal", init_scale=0.5),
        "A_log": LeafMeta((h_loc,), tp_dim=0, init="a_log"),
        "D": LeafMeta((h_loc,), tp_dim=0, init="ones"),
        "dt_bias": LeafMeta((h_loc,), tp_dim=0, init="dt_bias"),
        "norm": LeafMeta((i_loc,), tp_dim=0, init="ones"),
        "wo": LeafMeta((i_loc, D), tp_dim=0),
    }


def _rec_metas(cfg: ModelConfig, ctx: ShardCtx, prefix: str) -> dict[str, LeafMeta]:
    D = cfg.d_model
    C = (cfg.lru_width or cfg.d_model) // ctx.tp
    W = cfg.conv_width
    return {
        f"{prefix}wy": LeafMeta((D, C), tp_dim=1),
        f"{prefix}wx": LeafMeta((D, C), tp_dim=1),
        f"{prefix}conv": LeafMeta((W, C), tp_dim=1, init="normal", init_scale=0.5),
        f"{prefix}w_r": LeafMeta((C,), tp_dim=0, init="normal", init_scale=8.0),
        f"{prefix}b_r": LeafMeta((C,), tp_dim=0, init="zeros"),
        f"{prefix}w_i": LeafMeta((C,), tp_dim=0, init="normal", init_scale=8.0),
        f"{prefix}b_i": LeafMeta((C,), tp_dim=0, init="zeros"),
        f"{prefix}lam": LeafMeta((C,), tp_dim=0, init="a_log"),
        f"{prefix}wo": LeafMeta((C, D), tp_dim=0),
    }


def block_metas(cfg: ModelConfig, ctx: ShardCtx) -> dict[str, LeafMeta]:
    """Metas of one scanned layer (or super-unit for hybrid)."""
    D = cfg.d_model
    ln = lambda: LeafMeta((D,), tp_dim=None, init="ones")
    if cfg.family in ("dense", "vlm"):
        return {"ln1": ln(), "ln2": ln(),
                **_attn_metas(cfg, ctx), **_mlp_metas(cfg, ctx)}
    if cfg.family == "moe" and cfg.kv_lora_rank:
        return {"ln1": ln(), "ln2": ln(),
                **_mla_metas(cfg), **_share_metas(cfg)}
    if cfg.family == "moe":
        return {"ln1": ln(), "ln2": ln(),
                **_attn_metas(cfg, ctx), **_moe_metas(cfg, ctx)}
    if cfg.family == "ssm":
        return {"ln1": ln(), **_ssm_metas(cfg, ctx)}
    if cfg.family == "hybrid":
        # super-unit = (rec, rec, local-attn), each with its own MLP
        m: dict[str, LeafMeta] = {}
        for p in ("r1_", "r2_"):
            m[f"{p}ln1"] = ln()
            m[f"{p}ln2"] = ln()
            m.update(_rec_metas(cfg, ctx, p))
            m.update({f"{p}{k}": v for k, v in _mlp_metas(cfg, ctx).items()})
        m["at_ln1"] = ln()
        m["at_ln2"] = ln()
        m.update(_attn_metas(cfg, ctx, "at_"))
        m.update({f"at_{k}": v for k, v in _mlp_metas(cfg, ctx).items()})
        return m
    raise ValueError(cfg.family)


def top_metas(cfg: ModelConfig, ctx: ShardCtx) -> dict[str, LeafMeta]:
    V, D = cfg.vocab, cfg.d_model
    v_loc = -(-V // ctx.tp)       # ceil; vocab padded to tp multiple
    m = {
        "embed": LeafMeta((v_loc, D), tp_dim=0, scanned=False, init="embed"),
        "final_norm": LeafMeta((D,), tp_dim=None, scanned=False, init="ones"),
    }
    if not cfg.tie_embeddings:
        m["lm_head"] = LeafMeta((v_loc, D), tp_dim=0, scanned=False, init="embed")
    if cfg.family == "hybrid":
        # unscanned tail recurrent layers (n_layers % 3)
        tail = cfg.n_layers % 3
        for t in range(tail):
            p = f"tail{t}_"
            m[f"{p}ln1"] = LeafMeta((D,), tp_dim=None, scanned=False, init="ones")
            m[f"{p}ln2"] = LeafMeta((D,), tp_dim=None, scanned=False, init="ones")
            for k, v in _rec_metas(cfg, ctx, p).items():
                m[k] = dataclasses.replace(v, scanned=False)
            for k, v in _mlp_metas(cfg, ctx, p).items():
                m[k] = dataclasses.replace(v, scanned=False)
    for i in range(cfg.first_dense_layers):
        # leading dense layers (latent attention + SwiGLU), unscanned
        p = f"dense{i}_"
        lead = {f"{p}ln1": LeafMeta((D,), init="ones"),
                f"{p}ln2": LeafMeta((D,), init="ones"),
                **_mla_metas(cfg, p), **_mlp_metas(cfg, ctx, p)}
        m.update({k: dataclasses.replace(v, scanned=False)
                  for k, v in lead.items()})
    return m


def n_scan_steps(cfg: ModelConfig) -> int:
    if cfg.family == "hybrid":
        return cfg.n_layers // 3
    return cfg.n_layers - cfg.first_dense_layers


def all_metas(cfg: ModelConfig, ctx: ShardCtx) -> dict[str, dict[str, LeafMeta]]:
    return {"layers": block_metas(cfg, ctx), "top": top_metas(cfg, ctx)}


# ---------------------------------------------------------------------------
# Init (host-side global arrays) + shape-only variant for the dry-run
# ---------------------------------------------------------------------------

def init_params(cfg: ModelConfig, ctx: ShardCtx, key: Array) -> dict:
    metas = all_metas(cfg, ctx)
    L = n_scan_steps(cfg)
    out: dict[str, dict[str, Array]] = {"layers": {}, "top": {}}
    ks = jax.random.split(key, len(metas["layers"]) + len(metas["top"]))
    i = 0
    for name, meta in sorted(metas["layers"].items()):
        out["layers"][name] = init_leaf(ks[i], meta, ctx, L)
        i += 1
    for name, meta in sorted(metas["top"].items()):
        out["top"][name] = init_leaf(ks[i], meta, ctx, L)
        i += 1
    return out


def param_shapes(cfg: ModelConfig, ctx: ShardCtx) -> dict:
    """ShapeDtypeStructs of the storage pytree (dry-run; no allocation)."""
    from repro.models.sharding import storage_shape
    metas = all_metas(cfg, ctx)
    L = n_scan_steps(cfg)
    out: dict[str, dict[str, jax.ShapeDtypeStruct]] = {"layers": {}, "top": {}}
    for name, meta in metas["layers"].items():
        out["layers"][name] = jax.ShapeDtypeStruct(
            storage_shape(meta, ctx, L), jnp.float32)
    for name, meta in metas["top"].items():
        out["top"][name] = jax.ShapeDtypeStruct(
            storage_shape(meta, ctx, L), jnp.float32)
    return out


def y_init(cfg: ModelConfig, ctx: ShardCtx, value: float = 1.0) -> dict:
    """Initial distance-bound state, one per-bucket vector per leaf (per
    layer): shape (L, nb) for scanned leaves, (nb,) for top-level ones,
    with nb = sharding.leaf_nb — the QState y the FSDP gradient sync
    consumes and the trainer updates bucket by bucket from telemetry.

    With ``ctx.qcfg.rotate`` each leaf is seeded from the paper's §6
    rotated-space bound instead of the raw-space guess — see
    :func:`repro.models.sharding.leaf_y0`.  With ``ctx.anchor_grads`` each
    leaf carries ``{"y": ..., "anchor": ...}`` — the anchor (the previous
    step's decoded gradient mean) starts at zero, which is bit-identical to
    the unanchored path on step 0.  Its layout follows
    :func:`repro.models.sharding.anchor_shape`: ZeRO-3 storage
    ``(tp, dp, shard)`` beside the weights when ``ctx.anchor_sharded``
    (rebuilt by the forward gather), legacy replicated ``(m,)`` otherwise.
    """
    from repro.models.sharding import anchor_shape, leaf_nb, leaf_y0
    metas = all_metas(cfg, ctx)
    L = n_scan_steps(cfg)

    def leaf(meta, scanned):
        nb = leaf_nb(meta, ctx)
        shape = (L, nb) if scanned else (nb,)
        y = jnp.full(shape, leaf_y0(meta, ctx, value), jnp.float32)
        if not ctx.anchor_grads:
            return y
        a_shape = anchor_shape(meta, ctx, L if scanned else 0)
        return {"y": y, "anchor": jnp.zeros(a_shape, jnp.float32)}

    return {
        "layers": {k: leaf(m, True) for k, m in metas["layers"].items()},
        "top": {k: leaf(m, False) for k, m in metas["top"].items()},
    }


def tele_zeros(cfg: ModelConfig, ctx: ShardCtx) -> dict:
    """Zero tele inputs, one per leaf, sized to carry the scalar telemetry
    plus the per-bucket maps (and the next anchor when ctx.anchor_grads) —
    see dist/fsdp.py's tele layout."""
    from repro.models.sharding import leaf_tele_width
    metas = all_metas(cfg, ctx)
    L = n_scan_steps(cfg)
    return {
        "layers": {k: jnp.zeros((L, leaf_tele_width(m, ctx)), jnp.float32)
                   for k, m in metas["layers"].items()},
        "top": {k: jnp.zeros((leaf_tele_width(m, ctx),), jnp.float32)
                for k, m in metas["top"].items()},
    }


# ---------------------------------------------------------------------------
# Blocks (operating on gathered weights)
# ---------------------------------------------------------------------------

def _moe_apply(x_norm: Array, wts: dict, cfg: ModelConfig, ctx: ShardCtx):
    """Token-sliced MoE; returns (full out matching x_norm layout, aux)."""
    B, S, D = x_norm.shape
    if ctx.seq_parallel or ctx.tp == 1:
        flat = x_norm.reshape(B * S, D)
        out, aux = MOE.moe_mlp(flat, wts, cfg, ctx)
        return out.reshape(B, S, D), aux
    # non-SP: slice tokens over tp, compute, gather back
    T = B * S
    t_loc = T // ctx.tp
    flat = x_norm.reshape(T, D)
    sl = jax.lax.dynamic_slice_in_dim(flat, tp_index(ctx) * t_loc, t_loc, 0)
    out, aux = MOE.moe_mlp(sl, wts, cfg, ctx)
    full = all_gather_tp(out, ctx, axis=0)
    return full.reshape(B, S, D), aux


def dense_block(x: Array, wts: dict, cfg: ModelConfig, ctx: ShardCtx,
                positions: Array, window: int = 0) -> tuple[Array, Array]:
    a_in = LY.rms_norm(x, wts["ln1"], cfg.norm_eps)
    xg = LY.sp_enter(a_in, ctx)
    att = LY.attention(xg, wts, cfg, ctx, positions=positions,
                       causal=True, window=window)
    x = x + LY.attn_exit(att, cfg, ctx)
    m_in = LY.rms_norm(x, wts["ln2"], cfg.norm_eps)
    aux = jnp.zeros((), jnp.float32)
    if cfg.family == "moe":
        out, aux = _moe_apply(m_in, wts, cfg, ctx)
        x = x + out
    else:
        mg = LY.sp_enter(m_in, ctx)
        x = x + LY.sp_exit(LY.mlp(mg, wts, cfg), ctx)
    return x, aux


def mla_block(x: Array, wts: dict, cfg: ModelConfig, positions: Array,
              bias: Optional[Array] = None):
    """Latent attention, then a SwiGLU (a leading dense layer) or the
    chip's share of the MoE layer.  Returns (x, aux, loads or None)."""
    B, S, D = x.shape
    a_in = LY.rms_norm(x, wts["ln1"], cfg.norm_eps)
    x = x + LY.mla_attention(a_in, wts, cfg, positions=positions)
    m_in = LY.rms_norm(x, wts["ln2"], cfg.norm_eps)
    if "router" not in wts:
        return x + LY.mlp(m_in, wts, cfg), jnp.zeros((), jnp.float32), None
    out, aux, loads = MOE.share_moe(m_in.reshape(B * S, D), wts, cfg, bias, B)
    return x + out.reshape(B, S, D), aux, loads


def ssm_block(x: Array, wts: dict, cfg: ModelConfig, ctx: ShardCtx) -> Array:
    a_in = LY.rms_norm(x, wts["ln1"], cfg.norm_eps)
    xg = LY.sp_enter(a_in, ctx)
    out, _ = SSM.mamba2_block(xg, wts, cfg, ctx)
    return x + LY.sp_exit(out, ctx)


def _sub(wts: dict, prefix: str) -> dict:
    n = len(prefix)
    return {k[n:]: v for k, v in wts.items() if k.startswith(prefix)}


def hybrid_unit(x: Array, wts: dict, cfg: ModelConfig, ctx: ShardCtx,
                positions: Array) -> Array:
    for p in ("r1_", "r2_"):
        sw = _sub(wts, p)
        a_in = LY.rms_norm(x, sw["ln1"], cfg.norm_eps)
        xg = LY.sp_enter(a_in, ctx)
        out, _ = RG.recurrent_block(xg, sw, cfg, ctx)
        x = x + LY.sp_exit(out, ctx)
        m_in = LY.rms_norm(x, sw["ln2"], cfg.norm_eps)
        mg = LY.sp_enter(m_in, ctx)
        x = x + LY.sp_exit(LY.mlp(mg, sw, cfg), ctx)
    sw = _sub(wts, "at_")
    x, _ = dense_block(x, sw, dataclasses.replace(cfg, family="dense"), ctx,
                       positions, window=cfg.window)
    return x


# ---------------------------------------------------------------------------
# Training forward + loss (inside shard_map)
# ---------------------------------------------------------------------------

def _leaf_key(key: Array, name: str) -> Array:
    # deterministic across processes (never Python hash(): it is salted)
    import zlib
    return jax.random.fold_in(key, zlib.crc32(name.encode()) & 0x7FFFFFFF)


def _gather_tree(params: dict, metas: dict, ctx: ShardCtx, y: dict, key: Array,
                 tele: dict, gathers, dtype=jnp.bfloat16) -> dict:
    out = {}
    for name in params:
        out[name] = gather_param(params[name], metas[name], ctx, y[name],
                                 _leaf_key(key, name), tele[name], gathers,
                                 dtype)
    return out


def _prefetch_layer_scan(x0: Array, params_l: dict, metas_l: dict,
                         ctx: ShardCtx, y_l, tele_l, L: int, split,
                         key_fn, apply_fn, remat: bool):
    """Double-buffered layer scan (``ctx.prefetch``): layer i+1's FSDP
    gather is *issued* while layer i computes.

    The carry holds the in-flight handle dict for the layer about to run;
    the body first issues layer i+1 (``lax.cond``-gated off on the last
    iteration), then consumes the carried handles through the pinned
    :func:`repro.models.sharding.gather_param_wait` and runs
    ``apply_fn(x, wts) -> (x', aux)``.  ``key_fn(i)`` must reproduce the
    serial body's per-layer key fold exactly — the split gather shares
    every internal with the monolithic one, so with matching keys the scan
    is bit-identical to the serial formulation (values and grads).
    """
    def issue(i):
        sl = lambda a: jax.lax.dynamic_index_in_dim(a, i, 0, keepdims=False)
        lp = jax.tree.map(sl, params_l)
        ly = jax.tree.map(sl, y_l)
        lt = jax.tree.map(sl, tele_l)
        kl = key_fn(i)
        return {name: gather_param_async(lp[name], metas_l[name], ctx,
                                         ly[name], _leaf_key(kl, name),
                                         lt[name], split)
                for name in lp}

    def body(carry, idx):
        xcur, auxsum, bufs = carry
        nxt = jax.lax.cond(idx < L - 1,
                           lambda i: issue(i + 1),
                           lambda i: jax.tree.map(jnp.zeros_like, bufs),
                           idx)
        wts = {name: gather_param_wait(bufs[name], metas_l[name], ctx, split)
               for name in bufs}
        xnew, aux = apply_fn(xcur, wts)
        return (xnew, auxsum + aux, nxt), None

    body_fn = jax.checkpoint(body) if remat else body
    (xf, aux, _), _ = jax.lax.scan(
        body_fn, (x0, jnp.zeros((), jnp.float32), issue(0)),
        jnp.arange(L, dtype=jnp.int32))
    return xf, aux


def make_loss_fn(cfg: ModelConfig, ctx: ShardCtx) -> Callable:
    """Returns loss_fn(params, tele, batch, key, y) -> (loss, metrics).

    batch: {"tokens": (B, S) int32, "targets": (B, S) int32,
            "mask": (B, S) f32/bool; vlm additionally "img": (B, Timg, D)}
    loss is tp-global / dp-local (DESIGN: the FSDP gather's bwd performs the
    DP mean).
    """
    metas = all_metas(cfg, ctx)
    gathers = make_gathers(ctx)
    split = make_split_gathers(ctx) if ctx.prefetch else None
    L = n_scan_steps(cfg)

    if cfg.kv_lora_rank and (ctx.tp > 1 or ctx.prefetch):
        raise ValueError(f"{cfg.arch}: latent attention and the expert "
                         f"share run with tp=1 and the serial layer scan")

    def loss_fn(params, tele, batch, key, y, bias=None):
        """``bias``: (layers, n_experts) selection bias of a sigmoid_bias
        router (zeros when not given)."""
        tokens = batch["tokens"]
        B, S = tokens.shape
        kt = jax.random.fold_in(key, 0)

        emb = gather_param(params["top"]["embed"], metas["top"]["embed"], ctx,
                           y["top"]["embed"], _leaf_key(kt, "embed"),
                           tele["top"]["embed"], gathers)
        x = LY.vp_embed(tokens, emb, ctx) * cfg.emb_scale
        if cfg.family == "vlm":
            x = jnp.concatenate([batch["img"].astype(x.dtype), x], axis=1)
        S_full = x.shape[1]
        positions = jnp.arange(S_full, dtype=jnp.int32)

        if ctx.seq_parallel and ctx.tp > 1:
            s_loc = S_full // ctx.tp
            x = jax.lax.dynamic_slice_in_dim(x, tp_index(ctx) * s_loc, s_loc, 1)

        if cfg.router == "sigmoid_bias" and bias is None:
            bias = jnp.zeros((L, cfg.n_experts), jnp.float32)

        for i in range(cfg.first_dense_layers):
            p = f"dense{i}_"
            kl = jax.random.fold_in(key, 20_000 + i)
            sw = {k[len(p):]: gather_param(
                params["top"][k], metas["top"][k], ctx, y["top"][k],
                _leaf_key(kl, k), tele["top"][k], gathers)
                for k in metas["top"] if k.startswith(p)}
            x, _, _ = mla_block(x, sw, cfg, positions)

        def apply_block(xcur, wts, *bias_l):
            if cfg.kv_lora_rank:
                return mla_block(xcur, wts, cfg, positions, *bias_l)
            if cfg.family == "ssm":
                return ssm_block(xcur, wts, cfg, ctx), jnp.zeros((), jnp.float32)
            if cfg.family == "hybrid":
                return (hybrid_unit(xcur, wts, cfg, ctx, positions),
                        jnp.zeros((), jnp.float32))
            return dense_block(xcur, wts, cfg, ctx, positions)

        if ctx.prefetch:
            x, aux = _prefetch_layer_scan(
                x, params["layers"], metas["layers"], ctx, y["layers"],
                tele["layers"], L, split,
                lambda i: jax.random.fold_in(key, i + 1), apply_block,
                ctx.remat)
        else:
            def body(carry, xs):
                xcur, auxsum = carry
                lp, ly, lt, idx = xs[:4]
                kl = jax.random.fold_in(key, idx + 1)
                wts = _gather_tree(lp, metas["layers"], ctx, ly, kl, lt,
                                   gathers)
                xnew, aux, *loads = apply_block(xcur, wts, *xs[4:])
                return (xnew, auxsum + aux), (loads[0] if loads else None)

            body_fn = jax.checkpoint(body) if ctx.remat else body
            xs = (params["layers"],
                  y["layers"],
                  tele["layers"],
                  jnp.arange(L, dtype=jnp.int32))
            if bias is not None:
                xs = xs + (bias,)
            (x, aux), loads = jax.lax.scan(
                body_fn, (x, jnp.zeros((), jnp.float32)), xs)

        # hybrid tail layers (unscanned)
        if cfg.family == "hybrid" and cfg.n_layers % 3:
            for t in range(cfg.n_layers % 3):
                p = f"tail{t}_"
                names = [k for k in metas["top"] if k.startswith(p)]
                kl = jax.random.fold_in(key, 10_000 + t)
                sw = {k[len(p):]: gather_param(
                    params["top"][k], metas["top"][k], ctx, y["top"][k],
                    _leaf_key(kl, k), tele["top"][k], gathers)
                    for k in names}
                a_in = LY.rms_norm(x, sw["ln1"], cfg.norm_eps)
                xg = LY.sp_enter(a_in, ctx)
                out, _ = RG.recurrent_block(xg, sw, cfg, ctx)
                x = x + LY.sp_exit(out, ctx)
                m_in = LY.rms_norm(x, sw["ln2"], cfg.norm_eps)
                mg = LY.sp_enter(m_in, ctx)
                x = x + LY.sp_exit(LY.mlp(mg, sw, cfg), ctx)

        fn = gather_param(params["top"]["final_norm"], metas["top"]["final_norm"],
                          ctx, y["top"]["final_norm"], _leaf_key(kt, "fn"),
                          tele["top"]["final_norm"], gathers)
        x = LY.rms_norm(x, fn, cfg.norm_eps)

        if cfg.tie_embeddings:
            head = emb
        else:
            head = gather_param(params["top"]["lm_head"], metas["top"]["lm_head"],
                                ctx, y["top"]["lm_head"], _leaf_key(kt, "head"),
                                tele["top"]["lm_head"], gathers)

        targets = batch["targets"]
        mask = batch.get("mask")
        if cfg.family == "vlm":
            timg = batch["img"].shape[1]
            pad_t = jnp.zeros((B, timg), targets.dtype)
            targets = jnp.concatenate([pad_t, targets], axis=1)
            pad_m = jnp.zeros((B, timg), jnp.float32)
            m0 = jnp.ones((B, S), jnp.float32) if mask is None else mask.astype(jnp.float32)
            mask = jnp.concatenate([pad_m, m0], axis=1)

        if ctx.seq_parallel and ctx.tp > 1:
            # vocab-parallel CE needs every rank to see every token (the
            # vocab axis is sharded over tp too) — gather tokens back,
            # Megatron-style, before the head.
            x = LY.sp_enter(x, ctx)
        nll_sum, cnt = _ce_sum(x.reshape(-1, cfg.d_model), head,
                               targets.reshape(-1), ctx,
                               None if mask is None else mask.reshape(-1))
        loss = nll_sum / jnp.maximum(cnt, 1.0)

        loss = loss + cfg.aux_coef * aux
        metrics = {"loss": loss, "aux": aux}
        if cfg.kv_lora_rank:
            metrics["loads"] = loads      # (layers, n_experts) rows routed
        # shard_map autodiff computes d(sum over devices of the returned
        # scalar)/dw (transpose(psum) = psum); the loss here is replicated
        # over tp, so scale by 1/tp so per-device grads are exact.
        return loss / ctx.tp, metrics

    return loss_fn


def _ce_sum(x: Array, head: Array, targets: Array, ctx: ShardCtx,
            mask: Optional[Array]):
    """Vocab-parallel CE; returns (sum nll, token count) over given tokens."""
    logits = x.astype(jnp.float32) @ head.astype(jnp.float32).T
    m_loc = jnp.max(logits, axis=-1)
    # stop_gradient: the max-shift cancels in CE's gradient; pmax itself has
    # no differentiation rule.
    m_loc = jax.lax.stop_gradient(m_loc)
    m = jax.lax.pmax(m_loc, ctx.tp_axis) if ctx.tp > 1 else m_loc
    zed = psum_tp(jnp.sum(jnp.exp(logits - m[:, None]), axis=-1), ctx)
    v_loc = head.shape[0]
    off = tp_index(ctx) * v_loc
    local = targets - off
    ok = (local >= 0) & (local < v_loc)
    safe = jnp.clip(local, 0, v_loc - 1)
    tgt = jnp.take_along_axis(logits, safe[:, None], axis=1)[:, 0]
    tgt = psum_tp(jnp.where(ok, tgt, 0.0), ctx)
    nll = jnp.log(zed) + m - tgt
    if mask is not None:
        mf = mask.astype(jnp.float32)
        return jnp.sum(nll * mf), jnp.sum(mf)
    return jnp.sum(nll), jnp.float32(nll.shape[0])
