"""Plain reference of the Moonlight-16B-A3B training step the benchmark
runs, at the chip's share of an expert-parallel deployment.

Straightforward ``jax.numpy`` in float32 with ``precision="highest"``: no
kernels, no sharding, no weight gathering, no dispatch buffers.  It follows
the published deepseek_v3 block as the configuration file states it, with
the departures listed there (the repo's half-split rotary layout, untied
embeddings as published, the vocabulary slice):

- the first ``first_k_dense_replace`` layers: multi-head latent attention
  and a SwiGLU of ``intermediate_size``;
- the others: latent attention, then the router over all
  ``published_n_routed_experts`` experts (sigmoid scores; the top
  ``num_experts_per_tok`` of score + selection bias choose; the weights are
  the chosen scores over their sum, times ``routed_scaling_factor``), the
  held experts ``expert_offset .. + n_routed_experts - 1`` evaluated densely
  under those weights (the other chips' experts add nothing), and the
  ``n_shared_experts`` shared experts as one SwiGLU every token takes;
- latent attention: q = x Wq split per head into no-rope and rope parts;
  [c_kv | k_pe] = x Wkv_a; c_kv normed; [k_nope | v] = c_kv Wkv_b; rotary
  positions on q's rope part and the one k_pe all heads share; softmax
  scale 1/sqrt(qk_nope + qk_rope);
- loss: mean next-token NLL plus ``aux_loss_alpha`` x the sequence-wise
  balance loss summed over the MoE layers; AdamW as
  ``bench.reference.granite``; after each step the selection bias moves by
  ``bias_update_speed`` x sign(mean load - load) (DeepSeek-V3 §2.1.2).

``dtype`` below float32 rounds every matmul operand to that type, as in
``bench.reference.granite``: the control that must fail the comparison.
"""
from __future__ import annotations

import math
import zlib
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from bench.reference.granite import (F32, _Hashable, _mm, _norms, _rms,
                                     _rope, flat_norms, lr_at)

CHUNK = 512        # query chunk of the reference's attention


def dims(cfg: dict) -> dict:
    return {"D": cfg["hidden_size"], "H": cfg["num_attention_heads"],
            "hd": cfg["qk_nope_head_dim"], "r": cfg["qk_rope_head_dim"],
            "vd": cfg["v_head_dim"], "R": cfg["kv_lora_rank"],
            "F": cfg["moe_intermediate_size"],
            "Fd": cfg["intermediate_size"], "E": cfg["n_routed_experts"],
            "Er": cfg["published_n_routed_experts"],
            "K": cfg["num_experts_per_tok"], "V": cfg["vocab_size"],
            "Ld": cfg["first_k_dense_replace"],
            "Lm": cfg["num_hidden_layers"] - cfg["first_k_dense_replace"],
            "Fs": cfg["n_shared_experts"] * cfg["moe_intermediate_size"]}


def _mla_shapes(d: dict, prefix: str = "", lead: tuple = ()) -> dict:
    D, H, hd, r, vd, R = (d["D"], d["H"], d["hd"], d["r"], d["vd"], d["R"])
    return {f"{prefix}ln1": lead + (D,), f"{prefix}ln2": lead + (D,),
            f"{prefix}wq": lead + (D, H * (hd + r)),
            f"{prefix}wkv_a": lead + (D, R + r),
            f"{prefix}kv_norm": lead + (R,),
            f"{prefix}wkv_b": lead + (R, H * (hd + vd)),
            f"{prefix}wo": lead + (H * vd, D)}


def leaf_shapes(cfg: dict) -> dict:
    d = dims(cfg)
    D, F, E, L = d["D"], d["F"], d["E"], d["Lm"]
    layers = {**_mla_shapes(d, lead=(L,)),
              "router": (L, D, d["Er"]), "w1": (L, E, D, F),
              "w3": (L, E, D, F), "w2": (L, E, F, D),
              "shared_wg": (L, D, d["Fs"]), "shared_wu": (L, D, d["Fs"]),
              "shared_wd": (L, d["Fs"], D)}
    top = {"embed": (d["V"], D), "final_norm": (D,), "lm_head": (d["V"], D)}
    for i in range(d["Ld"]):
        p = f"dense{i}_"
        top.update(_mla_shapes(d, p))
        top.update({f"{p}wg": (D, d["Fd"]), f"{p}wu": (D, d["Fd"]),
                    f"{p}wd": (d["Fd"], D)})
    return {"layers": layers, "top": top}


def init_params(cfg: dict, key) -> dict:
    """Seeded float32 weights: norms at one, embeddings N(0, 0.02^2),
    projections N(0, 1/fan_in) with fan_in the contracted width."""
    out = {}
    for grp, leaves in leaf_shapes(cfg).items():
        out[grp] = {}
        for name, shape in leaves.items():
            k = jax.random.fold_in(key, zlib.crc32(name.encode()) & 0x7FFFFFFF)
            if name.endswith(("ln1", "ln2", "norm")):
                out[grp][name] = jnp.ones(shape, F32)
            elif name in ("embed", "lm_head"):
                out[grp][name] = 0.02 * jax.random.normal(k, shape, F32)
            else:
                out[grp][name] = (jax.random.normal(k, shape, F32)
                                  / math.sqrt(shape[-2]))
    return out


def _mla(x, w, cfg, dtype):
    """x: (S, D) one sequence -> (S, D); queries in chunks of CHUNK, each
    rematerialised, so the (H, S, S) scores never live whole."""
    d = dims(cfg)
    H, hd, r, vd, R = d["H"], d["hd"], d["r"], d["vd"], d["R"]
    S = x.shape[0]
    theta = cfg["rope_theta"]
    q = _mm("sd,de->se", x, w["wq"], dtype).reshape(S, H, hd + r)
    kv_a = _mm("sd,de->se", x, w["wkv_a"], dtype)
    c = _rms(kv_a[:, :R], w["kv_norm"], cfg["rms_norm_eps"])
    kv = _mm("sr,re->se", c, w["wkv_b"], dtype).reshape(S, H, hd + vd)
    q = jnp.concatenate([q[..., :hd], _rope(q[..., hd:], theta)], -1)
    k_pe = _rope(kv_a[:, None, R:], theta)
    k = jnp.concatenate([kv[..., :hd], jnp.broadcast_to(k_pe, (S, H, r))],
                        -1)
    v = kv[..., hd:]
    C = min(CHUNK, S)
    pos = jnp.arange(S)

    @jax.checkpoint
    def chunk(_, i):
        qc = jax.lax.dynamic_slice_in_dim(q, i * C, C, 0)
        s = _mm("qhd,khd->hqk", qc, k, dtype) / math.sqrt(hd + r)
        causal = (i * C + jnp.arange(C))[:, None] >= pos[None, :]
        p = jax.nn.softmax(jnp.where(causal[None], s, -1e30), axis=-1)
        return None, _mm("hqk,khd->qhd", p, v, dtype)

    _, o = jax.lax.scan(chunk, None, jnp.arange(S // C))
    return _mm("se,ed->sd", o.reshape(S, H * vd), w["wo"], dtype)


def _swiglu(x, wg, wu, wd, dtype):
    h = (jax.nn.silu(_mm("td,df->tf", x, wg, dtype))
         * _mm("td,df->tf", x, wu, dtype))
    return _mm("tf,fd->td", h, wd, dtype)


def _moe(x, w, bias, cfg, n_seq, dtype):
    """x: (T, D) the block's tokens, n_seq whole sequences -> ((T, D), aux,
    loads (Er,))."""
    d = dims(cfg)
    T = x.shape[0]
    Er, K, E, off = d["Er"], d["K"], d["E"], cfg["expert_offset"]
    s = jax.nn.sigmoid(_mm("td,de->te", x, w["router"], dtype))
    _, idx = jax.lax.top_k(s + bias[None], K)
    gate = jnp.take_along_axis(s, idx, 1)
    gate = gate / (jnp.sum(gate, -1, keepdims=True) + 1e-20)
    gate = gate * cfg["routed_scaling_factor"]
    hot = jax.nn.one_hot(idx, Er, dtype=F32)                    # (T, K, Er)
    loads = jnp.sum(hot, (0, 1))
    S = T // n_seq
    f = jnp.sum(hot.reshape(n_seq, S * K, Er), 1) * Er / (K * S)
    p = jnp.mean((s / jnp.sum(s, -1, keepdims=True)).reshape(n_seq, S, Er),
                 1)
    aux = jnp.mean(jnp.sum(f * p, -1))
    comb = jnp.einsum("tke,tk->te", hot, gate)[:, off:off + E]

    @jax.checkpoint
    def expert(acc, e):
        y = _swiglu(x, w["w1"][e], w["w3"][e], w["w2"][e], dtype)
        return acc + jnp.take(comb, e, axis=1)[:, None] * y, None

    out, _ = jax.lax.scan(expert, jnp.zeros_like(x), jnp.arange(E))
    out = out + _swiglu(x, w["shared_wg"], w["shared_wu"], w["shared_wd"],
                        dtype)
    return out, aux, loads


def block_loss(params, bias, tokens, targets, cfg, dtype=F32):
    """(mean next-token NLL + alpha x the summed balance loss, loads (Lm,
    Er)) of one rank's block of sequences (B, S)."""
    B, S = tokens.shape
    eps = cfg["rms_norm_eps"]
    x = params["top"]["embed"][tokens]

    def attend(x, w):
        return x + jax.lax.map(jax.checkpoint(
            lambda xs: _mla(_rms(xs, w["ln1"], eps), w, cfg, dtype)), x)

    for i in range(cfg["first_k_dense_replace"]):
        p = f"dense{i}_"
        w = {k[len(p):]: v for k, v in params["top"].items()
             if k.startswith(p)}
        x = attend(x, w)
        x = x + _swiglu(_rms(x, w["ln2"], eps).reshape(B * S, -1), w["wg"],
                        w["wu"], w["wd"], dtype).reshape(B, S, -1)

    @jax.checkpoint
    def layer(carry, xs):
        x, aux = carry
        w, b = xs
        x = attend(x, w)
        m, aux_l, loads = _moe(_rms(x, w["ln2"], eps).reshape(B * S, -1), w,
                               b, cfg, B, dtype)
        return (x + m.reshape(B, S, -1), aux + aux_l), loads

    (x, aux), loads = jax.lax.scan(layer, (x, jnp.zeros((), F32)),
                                   (params["layers"], bias))
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
    return jnp.sum(nll) / (B * S) + cfg["aux_loss_alpha"] * aux, loads


def update_bias(bias, loads, rate: float):
    return bias + rate * jnp.sign(jnp.mean(loads, -1, keepdims=True) - loads)


def train(cfg: dict, opt: dict, key, batches, steps: int = 3,
          dtype=F32) -> dict:
    """Run ``steps`` AdamW steps from :func:`init_params` (key) and a zero
    selection bias, as ``bench.reference.granite.train``; the bias moves
    after each step by the step's loads over all its rank blocks.  Besides
    granite's readings it returns the bias after the last step ("bias",
    (Lm, Er)) and each step's loads ("loads", (steps, Lm, Er))."""
    hcfg = _Hashable(cfg)
    d = dims(cfg)
    init = jax.jit(init_params, static_argnums=0)
    vg = jax.jit(jax.value_and_grad(
        lambda p, b, tok, tgt: block_loss(p, b, tok, tgt, cfg, dtype),
        has_aux=True))
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
    bias = jnp.zeros((d["Lm"], d["Er"]), F32)
    losses, grad, head_grad, step_loads = [], None, None, []
    with jax.default_matmul_precision("highest"):
        for step in range(steps):
            g, loss_sum, n, loads = None, 0.0, 0, 0.0
            for tok, tgt in batches(step):
                (loss, lb), gb = vg(p, bias, tok, tgt)
                g = gb if g is None else add(g, gb)
                del gb
                loss_sum += float(loss)
                loads = loads + lb
                n += 1
            p, m, v, gn, gh = update(p, m, v, g, float(n),
                                     lr_at(opt, step), float(step + 1))
            del g
            bias = update_bias(bias, loads, cfg["bias_update_speed"])
            step_loads.append(np.asarray(loads))
            losses.append(loss_sum / n)
            if step == 0:
                grad = flat_norms(gn)
                head_grad = np.asarray(gh)
            del gh
        del m, v
        dn = flat_norms(delta(p, key))
    return {"loss": losses, "grad": grad, "head_grad": head_grad,
            "delta": dn, "bias": np.asarray(bias),
            "loads": np.stack(step_loads)}
