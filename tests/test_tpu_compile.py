"""Compile the main-path Pallas kernels, and the streaming drain's jitted
fold, for a described TPU v5e chip.

Nothing runs: each kernel is lowered at real width (N = 2^20 coordinates)
and compiled by the TPU compiler for one chip of a described ``v5e:2x2``
topology, which refuses what the chip would refuse (unsupported Mosaic
layouts, scoped-VMEM overflow) at no chip time.  The topology is described
inside a module fixture, never at import, and the fixture skips where it
cannot be described.  The persistent compilation cache is off around the
compiles: a program compiled for a described chip cannot be read back
without one.
"""
import functools

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import lattice as L
from repro.kernels.fwht import fwht_pallas
from repro.kernels.lattice_decode import (DEFAULT_BLOCK_SENDERS,
                                          lattice_decode_batched_pallas,
                                          lattice_decode_pallas)
from repro.kernels.lattice_encode import lattice_encode_pallas

N = 1 << 20


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.experimental.compilation_cache import compilation_cache as cc
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


def _compile(fn, sharding, *shapes):
    args = [jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)
            for shape, dtype in shapes]
    return jax.jit(fn).lower(*args).compile()


F32, U32 = jnp.float32, jnp.uint32


@pytest.mark.parametrize("q,per_coord", [(16, False), (16, True),
                                         (65536, True)])
def test_encode_compiles(one_chip, q, per_coord):
    """Scalar sides; per-coordinate sides + anchor + coords (the agg client
    and the collectives' encode); the q=2^16 escalation cap."""
    bits = L.bits_for_q(q)
    if per_coord:
        fn = functools.partial(lattice_encode_pallas, q=q, bits=bits,
                               return_coords=True, interpret=False)
        shapes = [((N,), F32)] * 4
    else:
        fn = functools.partial(lattice_encode_pallas, q=q, bits=bits,
                               interpret=False)
        shapes = [((N,), F32), ((N,), F32), ((), F32)]
    c = _compile(fn, one_chip, *shapes)
    assert "tpu_custom_call" in c.as_text()


@pytest.mark.parametrize("mode", ["point", "coords"])
def test_decode_compiles(one_chip, mode):
    bits = L.bits_for_q(16)
    fn = functools.partial(lattice_decode_pallas, q=16, bits=bits, n=N,
                           mode=mode, interpret=False)
    c = _compile(fn, one_chip, ((N * bits // 32,), U32), ((N,), F32),
                 ((N,), F32), ((N,), F32), ((N,), F32))
    assert "tpu_custom_call" in c.as_text()


@pytest.mark.parametrize("q", [16, 256, 65536])
def test_batched_decode_compiles(one_chip, q):
    """16 senders with per-sender sides at the agg drain's sender block, at
    the first color space and both escalation levels."""
    senders = 16
    assert senders % DEFAULT_BLOCK_SENDERS == 0
    bits = L.bits_for_q(q)
    fn = functools.partial(lattice_decode_batched_pallas, q=q, bits=bits,
                           n=N, mode="coords", interpret=False)
    c = _compile(fn, one_chip, ((senders, N * bits // 32), U32), ((N,), F32),
                 ((N,), F32), ((senders, N), F32))
    assert "tpu_custom_call" in c.as_text()


def test_fwht_compiles(one_chip):
    d = 4096
    c = _compile(functools.partial(fwht_pallas, interpret=False), one_chip,
                 ((N // d, d), F32))
    assert "tpu_custom_call" in c.as_text()


@pytest.mark.parametrize("q", [16, 65536])
def test_stream_fold_compiles_in_place(one_chip, q):
    """The streaming drain's per-range fold at a 64 KiB range writes into
    the donated (N,) int16 record in place, and the commit over the whole
    record compiles beside it."""
    from repro.agg.intake import _fold_range_math
    from repro.agg.server import _commit_math
    nw, bucket = (1 << 16) // 4, 4096
    n = nw * 32 // L.bits_for_q(q)

    def arg(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    fold = _fold_range_math.lower(
        arg((N,), jnp.int16), arg((nw,), U32), arg((N,), jnp.int32),
        arg((), jnp.int32), q=q, n=n).compile()
    assert fold.memory_analysis().alias_size_in_bytes == 2 * N
    _commit_math.lower(
        arg((N,), jnp.int16), arg((N // bucket, bucket), jnp.int32),
        arg((N,), jnp.int32), arg((N,), U32), arg((N,), F32), arg((N,), F32),
        arg((N,), F32), m=1, bucket=bucket).compile()


@pytest.mark.parametrize("senders", [256, 32])
def test_sealed_drain_compiles_from_staged_rows(one_chip, monkeypatch,
                                                senders):
    """The sealed drain at the fl-xdevice cells' size (d = 1.4M in 342
    buckets of 4096, q = 16) takes one (nw,) uint32 array a payload, as the
    server puts them on the device on arrival, and stacks them there: it
    compiles for the chip with the decode as its one Mosaic launch, at a
    full 256-sender round and at 32 senders (17 to 32 pad to it)."""
    from repro.agg.server import _drain_math
    from repro.kernels import ops
    monkeypatch.setattr(ops, "_interpret", lambda: False)
    nb, bucket = 342, 4096
    n = nb * bucket
    nw = n * L.bits_for_q(16) // 32

    def arg(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    rows = tuple(arg((nw,), U32) for _ in range(senders))
    text = _drain_math.lower(
        rows, arg((senders, nb), F32), arg((senders,), U32),
        arg((senders,), jnp.bool_), arg((n,), F32), arg((n,), F32),
        arg((n,), U32), arg((nb,), F32), arg((senders,), jnp.int32),
        arg((n,), jnp.int32), q=16, bucket=bucket).compile().as_text()
    assert text.count("tpu_custom_call") == 1
    assert "concatenate" in text


@pytest.mark.parametrize("k,n", [(2048, 1408), (1408, 2048)])
def test_expert_grouped_matmul_compiles(one_chip, k, n):
    """The held experts' grouped products at Moonlight's widths (D 2048 ->
    F 1408 and back) over 2 x 8192 tokens' top-6 rows, 8 held experts and
    the absent group, forward and both backward products: ragged group
    sizes are runtime values, so one compile covers every routing."""
    from repro.kernels.grouped_matmul import expert_gmm, expert_tgmm
    m, bf16 = 2 * 8192 * 6, jnp.bfloat16
    sizes = ((9,), jnp.int32)
    c = _compile(functools.partial(expert_gmm, interpret=False), one_chip,
                 ((m, k), bf16), ((8, k, n), bf16), sizes)
    assert "tpu_custom_call" in c.as_text()
    c = _compile(functools.partial(expert_gmm, transpose_rhs=True,
                                   interpret=False), one_chip,
                 ((m, n), bf16), ((8, k, n), bf16), sizes)
    assert "tpu_custom_call" in c.as_text()
    c = _compile(functools.partial(expert_tgmm, groups=8, interpret=False),
                 one_chip, ((k, m), bf16), ((m, n), bf16), sizes)
    assert "tpu_custom_call" in c.as_text()


# ------------------------------------------------------------ attention

def _on_chip(monkeypatch):
    """Steer the program's kernels to Mosaic, as on the chip: the backend
    here is the CPU, so ``ops`` would pick interpret mode and the models
    the materialized attention."""
    from repro.kernels import ops
    monkeypatch.setattr(ops, "_interpret", lambda: False)


def _attention_layer(name, one_chip):
    """(loss of the layer's output, its argument shapes) at a training
    cell's shape: moonlight.ep8-8k's latent attention (2 x 8192 tokens,
    16 heads, q/k 192, v 128) or granite.dp1's GQA (5 x 2048 tokens, 16
    query heads on 8 KV of 64)."""
    from repro.configs import registry
    from repro.models import layers as LY
    from repro.models.sharding import ShardCtx

    def arg(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    if name == "mla_attention":
        cfg = registry.config("moonlight-16b-a3b")
        B, S, D, H = 2, 8192, cfg.d_model, cfg.n_heads
        hd, r, vd = cfg.head_dim, cfg.qk_rope_dim, cfg.v_head_dim
        R = cfg.kv_lora_rank
        w = {"wq": arg((D, H * (hd + r))), "wkv_a": arg((D, R + r)),
             "kv_norm": arg((R,), F32), "wkv_b": arg((R, H * (hd + vd))),
             "wo": arg((H * vd, D))}

        def layer(x, w):
            return LY.mla_attention(x, w, cfg, positions=jnp.arange(S))
    else:
        cfg = registry.config("granite-moe-1b-a400m")
        B, S, D = 5, 2048, cfg.d_model
        q, kv = cfg.n_heads * cfg.head_dim, cfg.n_kv * cfg.head_dim
        w = {"wq": arg((D, q)), "wk": arg((D, kv)), "wv": arg((D, kv)),
             "wo": arg((q, D))}
        ctx = ShardCtx(tp=1, dp=1)

        def layer(x, w):
            return LY.attention(x, w, cfg, ctx, positions=jnp.arange(S))

    def loss(x, w):
        return jnp.sum(layer(x, w).astype(F32))

    return loss, (arg((B, S, D)), w)


@pytest.mark.parametrize("name", ["mla_attention", "attention"])
def test_attention_compiles_fused(one_chip, monkeypatch, name):
    """The forward and backward of both attention bodies at their cells'
    shapes go through the fused kernel's three Mosaic calls (forward, dq,
    dkv) and form no score row: no ``reduce-window`` (the row max the
    compiler made of the materialized softmax) is left."""
    _on_chip(monkeypatch)
    loss, args = _attention_layer(name, one_chip)
    text = jax.jit(jax.value_and_grad(loss, (0, 1))).lower(
        *args).compile().as_text()
    assert text.count("tpu_custom_call") >= 3
    assert "reduce-window" not in text


def _trace_step(topo, arch, B, S, **over):
    """Trace (not compile) the training step of ``arch`` at batch B of S
    tokens on one described chip; returns the attention dispatch counts."""
    import dataclasses

    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    import repro.obs as obs
    from repro.configs import registry
    from repro.dist.collectives import QSyncConfig
    from repro.models.sharding import ShardCtx
    from repro.train import optim as O
    from repro.train.trainer import TrainConfig, init_state, make_train_step

    cfg = dataclasses.replace(registry.config(arch), **over)
    mesh = Mesh(np.array(topo.devices[:1]).reshape(1, 1), ("data", "model"),
                axis_types=(jax.sharding.AxisType.Auto,) * 2)
    ctx = ShardCtx(tp=1, dp=1, qcfg=QSyncConfig(q=16, bucket=4096),
                   grad_sync="lq")
    oc, tc = O.OptConfig(name="adamw"), TrainConfig(steps=1, max_restarts=0)
    step, spec, _ = make_train_step(cfg, ctx, mesh, oc, tc)
    shapes = jax.eval_shape(lambda k: init_state(cfg, ctx, oc, tc, k),
                            jax.random.PRNGKey(0))
    state = jax.tree.map(
        lambda a, s: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                          sharding=NamedSharding(mesh, s)),
        shapes, spec, is_leaf=lambda x: isinstance(x, P))
    rows = NamedSharding(mesh, P("data"))
    batch = {k: jax.ShapeDtypeStruct((B, S), dt, sharding=rows)
             for k, dt in (("tokens", jnp.int32), ("targets", jnp.int32),
                           ("mask", F32))}
    paths = ("fused", "materialized")
    for p in paths:
        obs.registry().counter("attention_dispatch", path=p).reset()
    step.trace(state, batch)
    return {p: obs.registry().value("attention_dispatch", path=p)
            for p in paths}


@pytest.mark.parametrize("cell,arch,B,S,over,want", [
    # the leading dense layer and the scanned MoE layers' body
    ("moonlight.ep8-8k", "moonlight-16b-a3b", 2, 8192,
     {"n_layers": 5, "vocab": 20480, "experts_held": 8}, (2, 0)),
    ("granite.dp1", "granite-moe-1b-a400m", 5, 2048, {"n_layers": 8},
     (1, 0)),
    # one query chunk: materialized
    ("granite-512", "granite-moe-1b-a400m", 1, 512, {"n_layers": 8},
     (0, 1)),
    # a sliding window (2048) over 4096 tokens: materialized
    ("recurrentgemma-4k", "recurrentgemma-9b", 1, 4096, {"n_layers": 3},
     (0, 1)),
])
def test_step_attention_dispatch(topo, monkeypatch, cell, arch, B, S, over,
                                 want):
    """Which path every attention call of a training step traced for the
    chip takes: fused in both training cells' steps, materialized for one
    query chunk or a windowed attention."""
    _on_chip(monkeypatch)
    got = _trace_step(topo, arch, B, S, **over)
    assert (got["fused"], got["materialized"]) == want, got
