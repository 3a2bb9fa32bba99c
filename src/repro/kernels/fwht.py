"""Pallas TPU kernel: fast Walsh-Hadamard transform (paper §6 rotation).

TPU adaptation (DESIGN §2/§5): instead of the GPU butterfly-shuffle FWHT, we
use the Kronecker factorization of Sylvester-Hadamard matrices

    H_d = H_a (x) H_b          (d = a*b, a,b <= 128 powers of two)

so the transform of a (rows, d) tile becomes two small MXU matmuls on the
reshaped (rows, a, b) tensor:

    Y = H_a @ X @ H_b    (per row)

This keeps the whole tile in VMEM, feeds the 128x128 MXU with dense
H-matrices, and needs no cross-lane shuffles — the TPU-native way to spend
O(d*(a+b)) MXU FLOPs instead of O(d log d) serial VPU stages.

Supported: d a power of two, 4 <= d <= 16384 (a,b <= 128).  Larger d is
handled by the caller (repro.kernels.ops) via bucketing — which the RLQ
compressor does anyway (paper §6 note on coordinate buckets).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

DEFAULT_BLOCK_ROWS = 8
MAX_D = 16384


def hadamard_matrix(n: int) -> np.ndarray:
    """Unnormalized Sylvester-Hadamard matrix H_n (n power of two)."""
    assert n & (n - 1) == 0 and n >= 1
    i = np.arange(n)
    # H[i,j] = (-1)^{popcount(i & j)}
    pc = np.vectorize(lambda v: bin(v).count("1"))(i[:, None] & i[None, :])
    return np.where(pc % 2 == 0, 1.0, -1.0).astype(np.float32)


def factor_d(d: int) -> tuple[int, int]:
    """Split d = a*b with a, b <= 128, both powers of two."""
    assert d & (d - 1) == 0 and 4 <= d <= MAX_D, f"bad fwht dim {d}"
    b = min(d, 128)
    a = d // b
    assert a <= 128
    return a, b


def _fwht_kernel(x_ref, ha_ref, hb_ref, o_ref, *, a: int, b: int, scale: float):
    x = x_ref[...].astype(jnp.float32)           # (bm, d)
    bm = x.shape[0]
    x3 = x.reshape(bm, a, b)
    # right-multiply by H_b  : (bm, a, b) x (b, b) -> (bm, a, b)
    # HIGHEST: the MXU's default single bf16 pass would round every input
    # and the intermediate t to 8 mantissa bits
    t = jax.lax.dot_general(x3, hb_ref[...],
                            (((2,), (0,)), ((), ())),
                            precision=jax.lax.Precision.HIGHEST,
                            preferred_element_type=jnp.float32)
    # left-multiply by H_a   : contract axis 1 (H symmetric) -> (bm, b, a)
    t = jax.lax.dot_general(t, ha_ref[...],
                            (((1,), (0,)), ((), ())),
                            precision=jax.lax.Precision.HIGHEST,
                            preferred_element_type=jnp.float32)
    t = jnp.swapaxes(t, 1, 2)                    # (bm, a, b)
    o_ref[...] = (t.reshape(bm, a * b) * scale).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block_rows", "interpret"))
def _fwht_2d(x: jax.Array, ha: jax.Array, hb: jax.Array,
             block_rows: int = DEFAULT_BLOCK_ROWS,
             interpret: bool = True) -> jax.Array:
    rows, d = x.shape
    a, b = ha.shape[0], hb.shape[0]
    assert a * b == d
    bm = min(block_rows, rows)
    pad = (-rows) % bm
    if pad:
        x = jnp.pad(x, ((0, pad), (0, 0)))
    grid = (x.shape[0] // bm,)
    out = pl.pallas_call(
        functools.partial(_fwht_kernel, a=a, b=b, scale=float(1.0 / np.sqrt(d))),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, d), lambda i: (i, 0)),
            pl.BlockSpec((a, a), lambda i: (0, 0)),
            pl.BlockSpec((b, b), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((bm, d), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
        interpret=interpret,
    )(x, ha, hb)
    return out[:rows]


def fwht_pallas(x: jax.Array, *, block_rows: int = DEFAULT_BLOCK_ROWS,
                interpret: bool = True) -> jax.Array:
    """Normalized FWHT over the last axis via the Pallas kernel.

    x: (..., d), d a power of two in [4, 16384].
    """
    d = x.shape[-1]
    a, b = factor_d(d)
    ha = jnp.asarray(hadamard_matrix(a))
    hb = jnp.asarray(hadamard_matrix(b))
    lead = x.shape[:-1]
    x2 = x.reshape((-1, d)) if lead else x.reshape((1, d))
    out = _fwht_2d(x2, ha, hb, block_rows=block_rows, interpret=interpret)
    return out.reshape(lead + (d,)) if lead else out.reshape((d,))
