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
    from repro.agg.server import _commit_math, _fold_range_math
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
