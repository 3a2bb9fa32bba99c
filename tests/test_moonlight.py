"""Moonlight-16B-A3B's block at small widths on the CPU: the grouped
matmul kernel, the expert share, dropless routing, latent attention and
the selection bias, each against a plain float32 computation
(``bench/reference/moonlight.py`` where it has one)."""
import dataclasses
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _ROOT not in sys.path:
    sys.path.insert(0, _ROOT)

from bench.reference import moonlight as REF  # noqa: E402
from repro.configs import registry  # noqa: E402
from repro.kernels import ops as K  # noqa: E402
from repro.models import layers as LY  # noqa: E402
from repro.models import moe as MOE  # noqa: E402

F32 = jnp.float32


def _cfg(**kw):
    return dataclasses.replace(registry.smoke_config("moonlight-16b-a3b"),
                               **kw)


def _ref_cfg(cfg, held=None, offset=0):
    """The reference's configuration keys for a program config."""
    return {"hidden_size": cfg.d_model, "num_attention_heads": cfg.n_heads,
            "qk_nope_head_dim": cfg.head_dim,
            "qk_rope_head_dim": cfg.qk_rope_dim,
            "v_head_dim": cfg.v_head_dim, "kv_lora_rank": cfg.kv_lora_rank,
            "moe_intermediate_size": cfg.moe_d_ff,
            "intermediate_size": cfg.d_ff,
            "n_routed_experts": held or cfg.n_experts,
            "published_n_routed_experts": cfg.n_experts,
            "expert_offset": offset, "num_experts_per_tok": cfg.top_k,
            "vocab_size": cfg.vocab, "num_hidden_layers": cfg.n_layers,
            "first_k_dense_replace": cfg.first_dense_layers,
            "n_shared_experts": cfg.n_shared_experts,
            "rms_norm_eps": cfg.norm_eps, "rope_theta": cfg.rope_theta,
            "routed_scaling_factor": cfg.routed_scale}


def _moe_weights(cfg, key):
    D, F, E = cfg.d_model, cfg.moe_d_ff, cfg.n_experts
    Fs = cfg.n_shared_experts * F
    ks = jax.random.split(key, 7)
    n = lambda k, s: jax.random.normal(k, s, F32) / np.sqrt(s[-2])  # noqa
    return {"router": n(ks[0], (D, E)), "w1": n(ks[1], (E, D, F)),
            "w3": n(ks[2], (E, D, F)), "w2": n(ks[3], (E, F, D)),
            "shared_wg": n(ks[4], (D, Fs)), "shared_wu": n(ks[5], (D, Fs)),
            "shared_wd": n(ks[6], (Fs, D))}


def _share(w, lo, hi):
    return dict(w, **{k: w[k][lo:hi] for k in ("w1", "w3", "w2")})


# ---------------------------------------------------------------- kernel

@pytest.mark.parametrize("sizes", [(100, 0, 60, 96, 256), (0, 0, 512, 0, 0),
                                   (3, 1, 0, 0, 508)])
def test_grouped_matmul_matches_einsum_per_group(sizes):
    """Forward and VJP against one einsum per group, empty groups
    included; the rows of the last group (no weights) come out zero."""
    m, k, n, G = 512, 128, 256, len(sizes) - 1
    key = jax.random.PRNGKey(3)
    k1, k2, k3 = jax.random.split(key, 3)
    lhs = jax.random.normal(k1, (m, k), F32)
    rhs = jax.random.normal(k2, (G, k, n), F32) / np.sqrt(k)
    g = jax.random.normal(k3, (m, n), F32)
    gs = jnp.asarray(sizes, jnp.int32)
    seg = np.repeat(np.arange(G + 1), sizes)

    def ref(lhs, rhs):
        w = jnp.concatenate([rhs, jnp.zeros((1, k, n), F32)])[seg]
        return jnp.einsum("mk,mkn->mn", lhs, w, precision="highest")

    out, vjp = jax.vjp(lambda a, b: K.grouped_matmul(a, b, gs), lhs, rhs)
    want, vjp_ref = jax.vjp(ref, lhs, rhs)
    np.testing.assert_allclose(out, want, rtol=1e-5, atol=1e-4)
    assert not np.any(np.asarray(out)[sum(sizes[:-1]):])
    for a, b in zip(vjp(g), vjp_ref(g)):
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-3)


# ----------------------------------------------------------------- share

def test_the_shares_sum_to_the_uncut_layer():
    """8 experts in 4 shares of 2: the routed parts of the 4 shares, with
    the shared experts counted once, give the uncut reference layer."""
    cfg = _cfg()
    T, D = 64, cfg.d_model
    key = jax.random.PRNGKey(0)
    x = jax.random.normal(key, (T, D), F32)
    w = _moe_weights(cfg, jax.random.fold_in(key, 1))
    bias = 0.1 * jax.random.normal(jax.random.fold_in(key, 2), (8,), F32)
    shared = LY.mlp(x, {k[7:]: v for k, v in w.items()
                        if k.startswith("shared_")}, cfg)
    total = shared
    for s in range(4):
        c = dataclasses.replace(cfg, experts_held=2, expert_offset=2 * s)
        out, aux, loads = MOE.share_moe(x, _share(w, 2 * s, 2 * s + 2), c,
                                        bias, 2)
        total = total + out - shared
    with jax.default_matmul_precision("highest"):
        want, aux_r, loads_r = REF._moe(x, w, bias, _ref_cfg(cfg), 2, F32)
    np.testing.assert_allclose(total, want, rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(aux, aux_r, rtol=1e-5)
    np.testing.assert_array_equal(loads, loads_r)
    assert int(loads.sum()) == T * cfg.top_k


def test_a_share_matches_the_reference_share():
    """One chip's share (experts 4..7 of 8) against the reference given the
    same share."""
    cfg = _cfg(experts_held=4, expert_offset=4)
    x = jax.random.normal(jax.random.PRNGKey(5), (64, cfg.d_model), F32)
    w = _moe_weights(cfg, jax.random.PRNGKey(6))
    out, _, _ = MOE.share_moe(x, _share(w, 4, 8), cfg, None, 1)
    with jax.default_matmul_precision("highest"):
        want, _, _ = REF._moe(x, _share(w, 4, 8), jnp.zeros((8,), F32),
                              _ref_cfg(cfg, held=4, offset=4), 1, F32)
    np.testing.assert_allclose(out, want, rtol=1e-4, atol=1e-4)


def test_dropless_when_every_token_picks_one_expert():
    """A selection bias that sends every token to held expert 5: its group
    holds all T rows, and nothing is dropped."""
    cfg = _cfg(experts_held=4, expert_offset=4)
    T = 256
    x = jax.random.normal(jax.random.PRNGKey(7), (T, cfg.d_model), F32)
    w = _moe_weights(cfg, jax.random.PRNGKey(8))
    bias = jnp.zeros((8,), F32).at[5].set(100.0)
    out, _, loads = MOE.share_moe(x, _share(w, 4, 8), cfg, bias, 1)
    assert int(loads[5]) == T
    with jax.default_matmul_precision("highest"):
        want, _, _ = REF._moe(x, _share(w, 4, 8), bias,
                              _ref_cfg(cfg, held=4, offset=4), 1, F32)
    np.testing.assert_allclose(out, want, rtol=1e-4, atol=1e-4)


def test_route_weights_are_the_unbiased_scores_normalised_and_scaled():
    cfg = _cfg()
    x = jax.random.normal(jax.random.PRNGKey(9), (16, cfg.d_model), F32)
    router = jax.random.normal(jax.random.PRNGKey(10), (cfg.d_model, 8), F32)
    bias = jnp.linspace(-1, 1, 8)
    idx, gate, _, loads = MOE.route(x, router, cfg, bias)
    s = jax.nn.sigmoid(x @ router)
    np.testing.assert_array_equal(idx, jax.lax.top_k(s + bias, 2)[1])
    g = jnp.take_along_axis(s, idx, 1)
    np.testing.assert_allclose(gate, g / g.sum(-1, keepdims=True) * 2.446,
                               rtol=1e-6)
    assert loads.dtype == jnp.int32 and int(loads.sum()) == 32


# ------------------------------------------------------------- attention

@pytest.mark.parametrize("S", [64, 1024])
def test_mla_matches_the_reference(S):
    """Latent attention against the reference's, one query chunk and
    several (S over ATTN_CHUNK)."""
    cfg = _cfg(n_heads=2)
    d = REF.dims(_ref_cfg(cfg))
    shapes = REF._mla_shapes(d)
    key = jax.random.PRNGKey(11)
    w = {n: jax.random.normal(jax.random.fold_in(key, i), s, F32)
         / np.sqrt(s[-2] if len(s) > 1 else 1)
         for i, (n, s) in enumerate(shapes.items())}
    x = jax.random.normal(jax.random.fold_in(key, 99), (1, S, cfg.d_model))
    with jax.default_matmul_precision("highest"):
        out = LY.mla_attention(x, w, cfg, positions=jnp.arange(S))
        want = REF._mla(x[0], w, _ref_cfg(cfg), F32)
    np.testing.assert_allclose(out[0], want, rtol=1e-4, atol=1e-4)


# ------------------------------------------------------- bias and counters

def test_bias_update_follows_the_rule():
    from repro.train.trainer import update_bias
    bias = jnp.zeros((2, 4), F32)
    loads = jnp.array([[4, 2, 2, 0], [1, 1, 1, 1]], jnp.int32)
    new = update_bias(bias, loads, 1e-3)
    np.testing.assert_allclose(new, [[-1e-3, 0, 0, 1e-3], [0, 0, 0, 0]])
    np.testing.assert_allclose(REF.update_bias(bias, loads.astype(F32), 1e-3),
                               new)


def test_loads_counters():
    import repro.obs as obs
    from repro.train.trainer import record_moe_loads
    cfg = _cfg(experts_held=2, expert_offset=2)
    obs.enable()
    obs.reset()
    try:
        loads = jnp.array([[1, 2, 3, 4, 0, 0, 0, 6], [0, 0, 5, 0, 1, 1, 1, 8]])
        assert record_moe_loads(loads, cfg) == (12, 32)
        record_moe_loads(loads, cfg)
        assert obs.registry().counter("moe.rows_held").value == 24
        assert obs.registry().counter("moe.rows_routed").value == 64
    finally:
        obs.disable()
        obs.reset()


def test_train_step_carries_loads_and_moves_the_bias():
    """The compiled step returns each MoE layer's loads and moves the
    selection bias by the rule; the gradient leaves the bias alone."""
    from repro.dist.collectives import QSyncConfig
    from repro.models.sharding import ShardCtx
    from repro.train import optim as O
    from repro.train.trainer import (TrainConfig, init_state,
                                     make_train_step)
    cfg = _cfg(experts_held=4, expert_offset=4)
    ctx = ShardCtx(tp=1, dp=1, qcfg=QSyncConfig(q=16, bucket=64))
    mesh = jax.make_mesh((1, 1), ("data", "model"),
                         axis_types=(jax.sharding.AxisType.Auto,) * 2)
    tc = TrainConfig(bias_rate=0.01)
    step, _, _ = make_train_step(cfg, ctx, mesh, O.OptConfig(), tc)
    state = init_state(cfg, ctx, O.OptConfig(), tc, jax.random.PRNGKey(0))
    B, S = 2, 32
    toks = jax.random.randint(jax.random.PRNGKey(1), (B, S), 0, cfg.vocab)
    batch = {"tokens": toks, "targets": toks, "mask": jnp.ones((B, S))}
    new, met = step(state, batch)
    loads = np.asarray(met["loads"])
    assert loads.shape == (cfg.n_layers - 1, 8)
    np.testing.assert_array_equal(loads.sum(-1), B * S * cfg.top_k)
    want = 0.01 * np.sign(loads.mean(-1, keepdims=True) - loads)
    np.testing.assert_allclose(new["moe_bias"], want, atol=1e-7)
    assert np.isfinite(float(met["loss"]))
