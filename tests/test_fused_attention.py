"""The fused causal attention (``ops.causal_attention``: the installed Pallas
splash kernel, forward, dq and dkv, in interpret mode on the CPU) against the
materialized softmax of ``layers._softmax_attend`` under the same causal
mask: the output and the gradients of q, k and v, in float32 and in
bfloat16, directly and through the two attention bodies of ``layers``."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro.obs as obs
from repro.configs import registry
from repro.kernels import ops
from repro.models import layers as LY
from repro.models.sharding import ShardCtx

F32, BF16 = jnp.float32, jnp.bfloat16


def _rel(x, ref):
    x, ref = x.astype(F32), ref.astype(F32)
    return float(jnp.linalg.norm(x - ref) / jnp.linalg.norm(ref))


def _out_and_grads(f, args, g, dtype):
    """f's output and the gradients of its arguments under cotangent g, all
    in ``dtype``."""
    out, vjp = jax.vjp(f, *[a.astype(dtype) for a in args])
    return [out, *vjp(g.astype(dtype))]


# B, S, query heads, KV heads, q/k width, v width
CASES = {"mla-192-128": (1, 1024, 2, 2, 192, 128),
         "gqa-16-on-8": (1, 1024, 16, 8, 64, 64),
         "s2048-b2": (2, 2048, 2, 1, 64, 64),
         "mla-s2048": (1, 2048, 2, 2, 192, 128)}


@pytest.mark.parametrize("case", list(CASES))
def test_fused_matches_materialized(case):
    """float32: the fused kernel reads as the materialized path to float32
    rounding.  bfloat16: its gap to the float32 materialized path is at most
    twice the materialized path's own bfloat16 gap, for the output and each
    gradient (the KV heads' gradients summed over the query heads that
    share them, through the same ``jnp.take`` head map as ``attention``)."""
    B, S, H, KV, dk, dv = CASES[case]
    ks = jax.random.split(jax.random.PRNGKey(S + H + dk), 4)
    q = jax.random.normal(ks[0], (B, S, H, dk), F32)
    k = jax.random.normal(ks[1], (B, S, KV, dk), F32)
    v = jax.random.normal(ks[2], (B, S, KV, dv), F32)
    g = jax.random.normal(ks[3], (B, S, H, dv), F32)
    heads = jnp.arange(H) // (H // KV)
    scale = 1.0 / np.sqrt(dk)
    mask = jnp.tril(jnp.ones((S, S), bool))

    def fused(q, k, v):
        return ops.causal_attention(q, jnp.take(k, heads, 2),
                                    jnp.take(v, heads, 2), scale=scale)

    def materialized(q, k, v):
        return LY._softmax_attend(q, jnp.take(k, heads, 2),
                                  jnp.take(v, heads, 2), mask, scale)

    want = _out_and_grads(materialized, (q, k, v), g, F32)
    got32 = _out_and_grads(fused, (q, k, v), g, F32)
    cur16 = _out_and_grads(materialized, (q, k, v), g, BF16)
    got16 = _out_and_grads(fused, (q, k, v), g, BF16)
    for name, w, a, c, b in zip(("out", "dq", "dk", "dv"), want, got32,
                                cur16, got16):
        assert a.shape == w.shape and b.dtype == BF16, name
        assert _rel(a, w) < 1e-5, (name, _rel(a, w))
        assert _rel(b, w) <= 2 * _rel(c, w), (name, _rel(b, w), _rel(c, w))


def _dispatch():
    return {p: obs.registry().value("attention_dispatch", path=p)
            for p in ("fused", "materialized")}


def _reset_dispatch():
    for p in ("fused", "materialized"):
        obs.registry().counter("attention_dispatch", path=p).reset()


def _weights(shapes, seed):
    key = jax.random.PRNGKey(seed)
    return {n: jax.random.normal(jax.random.fold_in(key, i), s, F32)
            / np.sqrt(s[0]) for i, (n, s) in enumerate(shapes.items())}


def _gqa_layer():
    cfg = dataclasses.replace(registry.smoke_config("granite-moe-1b-a400m"),
                              n_heads=16, n_kv=8)
    D, hd = cfg.d_model, cfg.head_dim
    w = _weights({"wq": (D, 16 * hd), "wk": (D, 8 * hd), "wv": (D, 8 * hd),
                  "wo": (16 * hd, D)}, 3)
    ctx = ShardCtx(tp=1, dp=1)
    return cfg, w, lambda x, w, **kw: LY.attention(x, w, cfg, ctx, **kw)


def _mla_layer():
    cfg = dataclasses.replace(registry.smoke_config("moonlight-16b-a3b"),
                              n_heads=2)
    D, H, hd = cfg.d_model, cfg.n_heads, cfg.head_dim
    r, vd, R = cfg.qk_rope_dim, cfg.v_head_dim, cfg.kv_lora_rank
    w = _weights({"wq": (D, H * (hd + r)), "wkv_a": (D, R + r),
                  "wkv_b": (R, H * (hd + vd)), "wo": (H * vd, D)}, 4)
    w["kv_norm"] = jnp.ones((R,), F32)
    return cfg, w, lambda x, w, **kw: LY.mla_attention(x, w, cfg, **kw)


@pytest.mark.parametrize("layer", ["attention", "mla_attention"])
def test_layer_takes_the_fused_path(layer, monkeypatch):
    """Where the kernel engages (``ops.on_tpu`` patched to hold; the kernel
    itself stays in interpret mode), ``attention`` (16 query heads on 8 KV)
    and ``mla_attention`` give the materialized path's output and weight
    gradients, and count one fused call each."""
    cfg, w, fn = (_gqa_layer if layer == "attention" else _mla_layer)()
    S = 1024
    x = jax.random.normal(jax.random.PRNGKey(5), (1, S, cfg.d_model), F32)

    def loss(x, w):
        out = fn(x, w, positions=jnp.arange(S))
        return jnp.sum(out * jnp.cos(jnp.arange(out.size).reshape(out.shape)))

    _reset_dispatch()
    with jax.default_matmul_precision("highest"):
        want = jax.grad(loss, (0, 1))(x, w)
        assert _dispatch() == {"fused": 0, "materialized": 1}
        monkeypatch.setattr(ops, "on_tpu", lambda: True)
        got = jax.grad(loss, (0, 1))(x, w)
    assert _dispatch() == {"fused": 1, "materialized": 1}
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        assert _rel(a, b) < 1e-5


@pytest.mark.parametrize("S,window", [(1024, 16), (512, 0), (1000, 0)])
def test_materialized_where_the_kernel_does_not_engage(S, window,
                                                       monkeypatch):
    """On the TPU too, a sliding window, one query chunk or a length that is
    not a multiple of 128 keeps the materialized path."""
    cfg, w, _ = _gqa_layer()
    monkeypatch.setattr(ops, "on_tpu", lambda: True)
    x = jax.ShapeDtypeStruct((1, S, cfg.d_model), F32)
    _reset_dispatch()
    jax.eval_shape(lambda x: LY.attention(x, w, cfg, ShardCtx(tp=1, dp=1),
                                          positions=jnp.arange(S),
                                          window=window), x)
    assert _dispatch() == {"fused": 0, "materialized": 1}
