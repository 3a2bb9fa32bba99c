"""Plain reference of the granite-moe training step the benchmark runs.

Straightforward ``jax.numpy`` in float32 with ``precision="highest"``: no
kernels, no sharding, no weight gathering, no dispatch buffers.  It follows
the program's ``moe`` family as the configuration file states it, which
departs from the published GraniteMoe in three places, noted there: no
embedding, attention, residual or logit multipliers; untied embeddings; and
experts with a capacity of ``capacity_factor * T * K / E`` tokens per
``T``-token block, the tokens past it dropped in token order.

The step: causal GQA attention with rotary positions, a top-k router over
all experts with a Switch load-balance loss, SwiGLU experts, cross entropy
over the vocabulary; the loss of a data-parallel step is the mean over the
ranks' blocks of ``nll + aux_coef * aux``; AdamW with global-norm clipping
and a warm-up-cosine learning rate.

``dtype`` below float32 rounds every matmul operand to that type
(accumulating in float32; an 8-bit type under a per-tensor scale, in the
backward pass too): the control that a precision step down must fail the
comparison.
"""
from __future__ import annotations

import math
import zlib
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32


def leaf_shapes(cfg: dict) -> dict:
    D = cfg["hidden_size"]
    H = cfg["num_attention_heads"]
    KV = cfg["num_key_value_heads"]
    hd = cfg["head_dim"]
    F = cfg["intermediate_size"]
    E = cfg["num_local_experts"]
    V = cfg["vocab_size"]
    L = cfg["num_hidden_layers"]
    return {
        "layers": {"ln1": (L, D), "ln2": (L, D),
                   "wq": (L, D, H * hd), "wk": (L, D, KV * hd),
                   "wv": (L, D, KV * hd), "wo": (L, H * hd, D),
                   "router": (L, D, E), "w1": (L, E, D, F),
                   "w3": (L, E, D, F), "w2": (L, E, F, D)},
        "top": {"embed": (V, D), "final_norm": (D,), "lm_head": (V, D)},
    }


def init_params(cfg: dict, key) -> dict:
    """Seeded float32 weights: norms at one, embeddings N(0, 0.02^2),
    projections N(0, 1/fan_in) with fan_in the contracted width."""
    out = {}
    for grp, leaves in leaf_shapes(cfg).items():
        out[grp] = {}
        for name, shape in leaves.items():
            k = jax.random.fold_in(key, zlib.crc32(name.encode()) & 0x7FFFFFFF)
            if name in ("ln1", "ln2", "final_norm"):
                out[grp][name] = jnp.ones(shape, F32)
            elif name in ("embed", "lm_head"):
                out[grp][name] = 0.02 * jax.random.normal(k, shape, F32)
            else:
                out[grp][name] = (jax.random.normal(k, shape, F32)
                                  / math.sqrt(shape[-2]))
    return out


def _quant(x, dtype):
    """Round x to an 8-bit type under a symmetric per-tensor scale, and
    back to float32."""
    if jnp.issubdtype(dtype, jnp.integer):
        top = float(jnp.iinfo(dtype).max)
        s = jnp.max(jnp.abs(x)) / top + 1e-30
        return jnp.clip(jnp.round(x / s), -top, top) * s
    top = float(jnp.finfo(dtype).max)
    s = jnp.max(jnp.abs(x)) / top + 1e-30
    return (x / s).astype(dtype).astype(F32) * s


def _mm8(eq, a, b, dtype):
    """einsum with every operand, forward and backward, rounded to an 8-bit
    type under a per-tensor scale and products accumulated in float32: what
    an int8 (or fp8) matmul does, scaled as 8-bit training scales it."""
    @jax.custom_vjp
    def f(a, b):
        return jnp.einsum(eq, _quant(a, dtype), _quant(b, dtype),
                          precision="highest")

    def fwd(a, b):
        return f(a, b), (a, b)

    def bwd(res, g):
        a, b = res
        _, vjp = jax.vjp(lambda x, y: jnp.einsum(eq, x, y,
                                                 precision="highest"),
                         _quant(a, dtype), _quant(b, dtype))
        return vjp(_quant(g, dtype))

    f.defvjp(fwd, bwd)
    return f(a, b)


def _mm(eq, a, b, dtype):
    if dtype == F32:
        return jnp.einsum(eq, a, b, precision="highest")
    if jnp.dtype(dtype).itemsize == 1:
        return _mm8(eq, a, b, dtype)
    return jnp.einsum(eq, a.astype(dtype), b.astype(dtype),
                      preferred_element_type=F32)


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def _rope(x, theta):
    S, _, hd = x.shape
    half = hd // 2
    freqs = 1.0 / (theta ** (jnp.arange(half, dtype=F32) / half))
    ang = jnp.arange(S, dtype=F32)[:, None] * freqs
    c, s = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], -1)


def capacity(tokens: int, cfg: dict) -> int:
    c = math.ceil(tokens * cfg["num_experts_per_tok"]
                  / cfg["num_local_experts"] * cfg["capacity_factor"])
    return max(8, ((c + 7) // 8) * 8)


def _attention(x, w, cfg, dtype):
    """x: (S, D) one sequence -> (S, D)."""
    H, KV, hd = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                 cfg["head_dim"])
    S = x.shape[0]
    q = _mm("sd,de->se", x, w["wq"], dtype).reshape(S, H, hd)
    k = _mm("sd,de->se", x, w["wk"], dtype).reshape(S, KV, hd)
    v = _mm("sd,de->se", x, w["wv"], dtype).reshape(S, KV, hd)
    q, k = _rope(q, cfg["rope_theta"]), _rope(k, cfg["rope_theta"])
    grp = jnp.arange(H) // (H // KV)
    k, v = k[:, grp], v[:, grp]
    s = _mm("qhd,khd->hqk", q, k, dtype) / math.sqrt(hd)
    causal = jnp.arange(S)[:, None] >= jnp.arange(S)[None, :]
    p = jax.nn.softmax(jnp.where(causal[None], s, -1e30), axis=-1)
    o = _mm("hqk,khd->qhd", p, v, dtype).reshape(S, H * hd)
    return _mm("se,ed->sd", o, w["wo"], dtype)


def _moe(x, w, cfg, dtype):
    """x: (T, D) the block's tokens -> ((T, D), aux)."""
    T = x.shape[0]
    E, K = cfg["num_local_experts"], cfg["num_experts_per_tok"]
    probs = jax.nn.softmax(_mm("td,de->te", x, w["router"], dtype), -1)
    gate, idx = jax.lax.top_k(probs, K)
    gate = gate / jnp.maximum(jnp.sum(gate, -1, keepdims=True), 1e-9)
    count = jnp.zeros((E,), F32).at[idx.reshape(-1)].add(1.0) / (T * K)
    aux = E * jnp.sum(jnp.mean(probs, 0) * count)
    onehot = jax.nn.one_hot(idx.reshape(-1), E, dtype=jnp.int32)
    pos = jnp.sum((jnp.cumsum(onehot, 0) - 1) * onehot, -1).reshape(T, K)
    keep = (pos < capacity(T, cfg)).astype(F32)
    comb = jnp.einsum("tke,tk->te", jax.nn.one_hot(idx, E, dtype=F32),
                      keep * gate)

    @jax.checkpoint
    def expert(acc, e):
        we = {n: w[n][e] for n in ("w1", "w3", "w2")}
        h = (jax.nn.silu(_mm("td,df->tf", x, we["w1"], dtype))
             * _mm("td,df->tf", x, we["w3"], dtype))
        y = _mm("tf,fd->td", h, we["w2"], dtype)
        return acc + jnp.take(comb, e, axis=1)[:, None] * y, None

    out, _ = jax.lax.scan(expert, jnp.zeros_like(x), jnp.arange(E))
    return out, aux


def block_loss(params, tokens, targets, cfg, dtype=F32):
    """Mean next-token NLL plus aux_coef x the summed load-balance loss of
    one rank's block of sequences (B, S)."""
    B, S = tokens.shape
    eps = cfg["rms_norm_eps"]
    x = params["top"]["embed"][tokens]

    @jax.checkpoint
    def layer(carry, w):
        x, aux = carry
        a = jax.lax.map(jax.checkpoint(
            lambda xs: _attention(_rms(xs, w["ln1"], eps), w, cfg, dtype)),
            x)
        x = x + a
        m, aux_l = _moe(_rms(x, w["ln2"], eps).reshape(B * S, -1), w, cfg,
                        dtype)
        return (x + m.reshape(B, S, -1), aux + aux_l), None

    (x, aux), _ = jax.lax.scan(layer, (x, jnp.zeros((), F32)),
                               params["layers"])
    x = _rms(x, params["top"]["final_norm"], eps).reshape(B * S, -1)
    t = targets.reshape(-1)
    chunk = math.gcd(B * S, 1024)

    @jax.checkpoint
    def ce(_, xs):
        xc, tc = xs
        logits = _mm("td,vd->tv", xc, params["top"]["lm_head"], dtype)
        lse = jax.nn.logsumexp(logits, -1)
        return None, jnp.sum(lse - jnp.take_along_axis(
            logits, tc[:, None], 1)[:, 0])

    _, nll = jax.lax.scan(ce, None, (x.reshape(-1, chunk, x.shape[-1]),
                                     t.reshape(-1, chunk)))
    return jnp.sum(nll) / (B * S) + cfg["aux_loss_coef"] * aux


def lr_at(opt: dict, step: int) -> float:
    s = float(step)
    warm = min(s / max(opt["warmup"], 1), 1.0)
    prog = min(max((s - opt["warmup"])
                   / max(opt["decay_steps"] - opt["warmup"], 1), 0.0), 1.0)
    cos = 0.5 * (1 + math.cos(math.pi * prog))
    scale = opt["min_lr_ratio"] + (1 - opt["min_lr_ratio"]) * cos
    return opt["lr"] * warm * scale


def _norms(tree) -> dict:
    """Per-leaf (per-layer for stacked leaves) L2 norms, on the device."""
    return {"layers": {n: jnp.sqrt(jnp.sum(jnp.square(
        a.reshape(a.shape[0], -1)), axis=1))
        for n, a in tree["layers"].items()},
        "top": {n: jnp.sqrt(jnp.sum(jnp.square(a)))[None]
                for n, a in tree["top"].items()}}


def flat_norms(norms) -> dict:
    """{"layers/<name>/<l>": norm, "top/<name>": norm}."""
    out = {}
    for name, a in norms["layers"].items():
        for i, v in enumerate(np.asarray(a)):
            out[f"layers/{name}/{i}"] = float(v)
    for name, a in norms["top"].items():
        out[f"top/{name}"] = float(np.asarray(a)[0])
    return out


def train(cfg: dict, opt: dict, key, batches, steps: int = 3,
          dtype=F32) -> dict:
    """Run ``steps`` AdamW steps from :func:`init_params` (key).

    ``batches(step)`` yields the step's rank blocks as (tokens, targets)
    pairs.  Returns {"loss": [per step], "grad": leaf norms of the first
    step's clipped gradient, "head_grad": that gradient's LM head (on the
    host), "delta": leaf norms of the parameters' change after ``steps``}.  Buffers are donated from step to step, so the
    peak is the weights, two moments, one gradient and one block's
    activations."""
    hcfg = _Hashable(cfg)
    init = jax.jit(init_params, static_argnums=0)
    vg = jax.jit(jax.value_and_grad(
        lambda p, tok, tgt: block_loss(p, tok, tgt, cfg, dtype)))
    add = jax.jit(lambda a, b: jax.tree.map(jnp.add, a, b),
                  donate_argnums=0)

    @partial(jax.jit, donate_argnums=(0, 1, 2, 3))
    def update(p, m, v, g, n_blocks, lr, t):
        g = jax.tree.map(lambda x: x / n_blocks, g)
        gnorm = jnp.sqrt(sum(jnp.sum(jnp.square(x))
                             for x in jax.tree.leaves(g)))
        clip = jnp.minimum(1.0, opt["grad_clip"] / jnp.maximum(gnorm, 1e-12))
        g = jax.tree.map(lambda x: x * clip, g)
        b1, b2 = opt["b1"], opt["b2"]
        c1, c2 = 1.0 / (1.0 - b1 ** t), 1.0 / (1.0 - b2 ** t)
        m = jax.tree.map(lambda m, g: b1 * m + (1 - b1) * g, m, g)
        v = jax.tree.map(lambda v, g: b2 * v + (1 - b2) * g * g, v, g)
        p = jax.tree.map(
            lambda p, m, v: p - lr * ((m * c1) / (jnp.sqrt(v * c2)
                                                  + opt["eps"])
                                      + opt["weight_decay"] * p), p, m, v)
        return p, m, v, _norms(g), g["top"]["lm_head"]

    delta = jax.jit(lambda p, key: _norms(jax.tree.map(
        jnp.subtract, p, init_params(hcfg, key))))

    p = init(hcfg, key)
    m = jax.tree.map(jnp.zeros_like, p)
    v = jax.tree.map(jnp.zeros_like, p)
    losses, grad, head_grad = [], None, None
    with jax.default_matmul_precision("highest"):
        for step in range(steps):
            g, loss_sum, n = None, 0.0, 0
            for tok, tgt in batches(step):
                loss, gb = vg(p, tok, tgt)
                g = gb if g is None else add(g, gb)
                del gb
                loss_sum += float(loss)
                n += 1
            p, m, v, gn, gh = update(p, m, v, g, float(n),
                                     lr_at(opt, step), float(step + 1))
            del g
            losses.append(loss_sum / n)
            if step == 0:
                grad = flat_norms(gn)
                head_grad = np.asarray(gh)
            del gh
        del m, v
        dn = flat_norms(delta(p, key))
    return {"loss": losses, "grad": grad, "head_grad": head_grad,
            "delta": dn}


class _Hashable(dict):
    """A dict usable as a static jit argument (compared by its items)."""

    def __hash__(self):
        return hash(tuple(sorted((k, str(v)) for k, v in self.items())))
