"""Compile the main-path Pallas kernels for a described TPU v5e chip.

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
