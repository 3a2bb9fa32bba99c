"""Expert-parallel MoE MLP (top-k router, capacity-bounded, all_to_all).

Experts are sharded over the ``model`` axis (E_loc = E/tp per rank).  Each tp
rank routes a disjoint token slice (the sequence-parallel slice), dispatches
via tiled ``all_to_all``, computes its local experts, and returns tokens with
a second all_to_all.  The router weight is tp-replicated (its gradient is
psum'd by the gather vjp).

Dispatch layout:
  disp  (E = tp*E_loc, C, D)  --a2a(split 0, concat 1)-->  (E_loc, tp*C, D)
  out   (E_loc, tp*C, D)      --a2a(split 1, concat 0)-->  (E, C, D)

``share_moe`` is the dropless alternative (``capacity_factor = 0``): one
chip's share of an expert-parallel layer.  It routes over every expert
(``route``: sigmoid scores with a selection bias), computes only the
experts it holds, over rows sorted by expert into a static T*top_k buffer
with one grouped matmul per weight, and adds the shared experts every
token takes.
"""
from __future__ import annotations

import math
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.models.config import ModelConfig
from repro.models.sharding import ShardCtx

Array = jax.Array


def capacity(tokens: int, cfg: ModelConfig) -> int:
    c = int(math.ceil(tokens * cfg.top_k / cfg.n_experts * cfg.capacity_factor))
    return max(8, ((c + 7) // 8) * 8)


def moe_mlp(x: Array, w: dict, cfg: ModelConfig, ctx: ShardCtx
            ) -> tuple[Array, Array]:
    """x: (T, D) this rank's token slice.  Returns (out (T,D), aux_loss ()).

    w: {"router": (D, E), "w1": (E_loc, D, F), "w3": (E_loc, D, F) [swiglu],
        "w2": (E_loc, F, D)}
    """
    T, D = x.shape
    E, K = cfg.n_experts, cfg.top_k
    e_loc = E // ctx.tp
    C = capacity(T, cfg)

    logits = x.astype(jnp.float32) @ w["router"].astype(jnp.float32)   # (T,E)
    probs = jax.nn.softmax(logits, axis=-1)
    gate, idx = jax.lax.top_k(probs, K)                                # (T,K)
    gate = gate / jnp.maximum(jnp.sum(gate, -1, keepdims=True), 1e-9)

    # load-balance auxiliary loss (Switch):  E * sum_e f_e * p_e
    me = jnp.mean(probs, axis=0)                                       # (E,)
    ce = jnp.zeros((E,), jnp.float32).at[idx.reshape(-1)].add(1.0) / (T * K)
    aux = E * jnp.sum(me * ce)

    # positions within each expert's capacity buffer
    e_flat = idx.reshape(-1)                                           # (T*K,)
    onehot = jax.nn.one_hot(e_flat, E, dtype=jnp.int32)
    pos = jnp.sum((jnp.cumsum(onehot, axis=0) - 1) * onehot, axis=-1)  # (T*K,)
    keep = (pos < C).astype(x.dtype)
    dest = e_flat * C + jnp.minimum(pos, C - 1)

    x_rep = jnp.repeat(x, K, axis=0)                                   # (T*K, D)
    disp = jnp.zeros((E * C, D), x.dtype).at[dest].add(
        x_rep * keep[:, None]).reshape(E, C, D)

    if ctx.tp > 1:
        recv = jax.lax.all_to_all(disp, ctx.tp_axis, split_axis=0,
                                  concat_axis=1, tiled=True)           # (E_loc, tp*C, D)
    else:
        recv = disp

    # expert FFN
    if cfg.act == "swiglu":
        h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", recv, w["w1"],
                                   preferred_element_type=jnp.float32))
        h = (h * jnp.einsum("ecd,edf->ecf", recv, w["w3"],
                            preferred_element_type=jnp.float32)).astype(x.dtype)
    else:
        h = jax.nn.gelu(jnp.einsum("ecd,edf->ecf", recv, w["w1"],
                                   preferred_element_type=jnp.float32)
                        ).astype(x.dtype)
    eo = jnp.einsum("ecf,efd->ecd", h, w["w2"])                        # (E_loc, tp*C, D)

    if ctx.tp > 1:
        back = jax.lax.all_to_all(eo, ctx.tp_axis, split_axis=1,
                                  concat_axis=0, tiled=True)           # (E, C, D)
    else:
        back = eo

    flat = back.reshape(E * C, D)
    tok = jnp.take(flat, dest, axis=0) * (keep * gate.reshape(-1).astype(x.dtype))[:, None]
    out = tok.reshape(T, K, D).sum(axis=1)
    return out, aux


# ---------------------------------------------------------------------------
# The chip's share of an expert-parallel layer, dropless
# ---------------------------------------------------------------------------

def route(x: Array, router: Array, cfg: ModelConfig,
          bias: Optional[Array] = None, n_seq: int = 1):
    """The ``sigmoid_bias`` router over all ``cfg.n_experts`` experts, in
    float32 (DeepSeek-V3, arXiv:2412.19437 §2.1.2): s = sigmoid(x W); the
    top-k of s + bias choose, the bias (not trained by the gradient) only
    chooses; the weights are the chosen s over their sum, times
    ``routed_scale``; the loss is the sequence-wise sum_i f_i P_i averaged
    over the ``n_seq`` sequences of x.

    Returns (idx (T, K) chosen experts, gate (T, K) their weights, aux the
    load-balance loss, loads (E,) int32 rows routed to each expert)."""
    T = x.shape[0]
    E, K = cfg.n_experts, cfg.top_k
    # HIGHEST: the TPU's default float32 product is one bfloat16 pass
    scores = jax.nn.sigmoid(jnp.matmul(
        x.astype(jnp.float32), router.astype(jnp.float32),
        precision=jax.lax.Precision.HIGHEST))                          # (T,E)
    pick = scores if bias is None else \
        scores + jax.lax.stop_gradient(bias)[None]
    _, idx = jax.lax.top_k(pick, K)
    # one-hot sums, not scatters: a TPU scatter-add of T*K updates into E
    # bins runs update by update
    hot = jax.nn.one_hot(idx, E, dtype=jnp.float32)                # (T,K,E)
    gate = jnp.einsum("tke,te->tk", hot, scores)
    if cfg.norm_topk:
        gate = gate / (jnp.sum(gate, -1, keepdims=True) + 1e-20)
    gate = gate * cfg.routed_scale
    loads = jnp.sum(hot, (0, 1)).astype(jnp.int32)
    S = T // n_seq
    hit = jnp.sum(hot.reshape(n_seq, S * K, E), axis=1)
    f = hit * E / (K * S)
    p = jnp.mean((scores / jnp.sum(scores, -1, keepdims=True))
                 .reshape(n_seq, S, E), axis=1)
    aux = jnp.mean(jnp.sum(f * p, axis=-1))
    return idx, gate, aux, loads


@partial(jax.custom_vjp, nondiff_argnums=(3,))
def _dispatch(x, order, inv, k):
    """(T, D) tokens -> (M, D) rows of the sorted buffer: row r holds token
    order[r] // k (the padding rows, past T * k, repeat the last token)."""
    return jnp.take(x, jnp.minimum(order // k, x.shape[0] - 1), axis=0)


def _dispatch_fwd(x, order, inv, k):
    return _dispatch(x, order, inv, k), (inv, x.shape[0])


def _dispatch_bwd(k, res, g):
    # a permutation's transpose is a gather by its inverse, no scatter-add
    inv, T = res
    ga = jnp.take(g, inv[:T * k], axis=0).reshape(T, k, -1)
    return jnp.sum(ga.astype(jnp.float32), axis=1).astype(g.dtype), None, None


_dispatch.defvjp(_dispatch_fwd, _dispatch_bwd)


@jax.custom_vjp
def _permute(x, perm, inv):
    """x[perm] for a permutation ``perm`` whose inverse is ``inv``."""
    return jnp.take(x, perm, axis=0)


def _permute_fwd(x, perm, inv):
    return _permute(x, perm, inv), (perm, inv)


def _permute_bwd(res, g):
    perm, inv = res
    return jnp.take(g, inv, axis=0), None, None


_permute.defvjp(_permute_fwd, _permute_bwd)


def _held_experts(rows: Array, w: dict, sizes: Array) -> Array:
    """SwiGLU of each held expert over its group of the sorted rows: one
    grouped matmul per weight; rows of the last (absent) group give 0."""
    from repro.kernels import ops as K
    h1 = K.grouped_matmul(rows, w["w1"], sizes)
    h3 = K.grouped_matmul(rows, w["w3"], sizes)
    h = (jax.nn.silu(h1) * h3).astype(rows.dtype)
    return K.grouped_matmul(h, w["w2"], sizes, rows.dtype)


def share_moe(x: Array, w: dict, cfg: ModelConfig, bias: Optional[Array],
              n_seq: int) -> tuple[Array, Array, Array]:
    """The chip's share of an expert-parallel MoE layer, dropless.

    x: (T, D) tokens of ``n_seq`` whole sequences.  The router scores all
    ``cfg.n_experts`` experts; the chip computes the assignments to its
    held experts ``expert_offset .. expert_offset + held - 1`` only (the
    other chips' experts add nothing here), sorted by expert into a static
    buffer of T * top_k rows (padded to the row tile), so no token is ever
    dropped; then the shared experts, which every token takes.

    w: {"router": (D, E), "w1"/"w3": (held, D, F), "w2": (held, F, D),
        optional "shared_wg"/"shared_wu" (D, Fs), "shared_wd" (Fs, D)}.
    Returns (out (T, D), aux, loads (E,) int32)."""
    from repro.kernels.grouped_matmul import TILE_M
    T, D = x.shape
    K, held, off = cfg.top_k, cfg.held, cfg.expert_offset
    with jax.named_scope("moe.route"):
        idx, gate, aux, loads = route(x, w["router"], cfg, bias, n_seq)
        A = T * K
        M = -(-A // TILE_M) * TILE_M
        local = idx.reshape(-1) - off
        mine = (local >= 0) & (local < held)
        key = jnp.pad(jnp.where(mine, local, held), (0, M - A),
                      constant_values=held)
        order = jnp.argsort(key, stable=True).astype(jnp.int32)
        inv = jnp.argsort(order).astype(jnp.int32)
        mine_loads = jax.lax.dynamic_slice_in_dim(loads, off, held)
        sizes = jnp.concatenate([mine_loads, (M - jnp.sum(mine_loads))[None]])
        # each sorted row's weight: 0 for other chips' experts and padding
        w_row = _permute(jnp.pad(jnp.where(mine, gate.reshape(-1), 0.0),
                                 (0, M - A)), order, inv)
    with jax.named_scope("moe.experts"):
        rows = _dispatch(x, order, inv, K)
        y = _held_experts(rows, w, sizes)                          # (M, D)
        y = (y.astype(jnp.float32) * w_row[:, None]).astype(x.dtype)
        ya = _permute(y, inv, order)[:A].reshape(T, K, D)
        out = jnp.sum(ya.astype(jnp.float32), axis=1).astype(x.dtype)
    if cfg.n_shared_experts:
        from repro.models.layers import mlp
        with jax.named_scope("moe.shared"):
            out = out + mlp(x, {k[7:]: v for k, v in w.items()
                                if k.startswith("shared_")}, cfg)
    return out, aux, loads
