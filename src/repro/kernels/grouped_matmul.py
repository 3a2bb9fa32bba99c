"""Grouped matrix products over ragged row groups (Pallas, TPU).

The rows of ``lhs`` are sorted by group: group ``g`` owns rows
``offsets[g] : offsets[g] + sizes[g]`` and is multiplied by ``rhs[g]``.
``sizes`` may have one entry more than ``rhs`` has groups: the rows of that
last group (an expert layer's assignments to experts another chip holds,
and the padding of the buffer to a whole tile) are never read, and their
output rows are zero.  The grid visits only the tiles the groups cover, so
the work follows the routing and not the buffer's static size.

The kernel bodies are ``jax.experimental.pallas.ops.tpu.megablox``'s
``gmm`` and ``tgmm``, called inside this module's own jitted wrappers
``expert_gmm`` and ``expert_tgmm``: those names are what a device trace
shows for the forward product and the two backward ones.
"""
from __future__ import annotations

import functools
import importlib

import jax
import jax.numpy as jnp

_mb = importlib.import_module("jax.experimental.pallas.ops.tpu.megablox.gmm")

TILE_M = 512


def _tile(dim: int, pref: int = 512) -> int:
    """``pref`` where it divides ``dim``; else ``dim`` whole where it is a
    multiple of 128 no wider than 1536 (Moonlight's 1408 is 11 x 128);
    else the largest multiple of 128 dividing it, or ``dim`` itself."""
    if dim % pref == 0:
        return pref
    if dim % 128 == 0 and dim <= 1536:
        return dim
    for t in range(min(pref, dim) // 128 * 128, 0, -128):
        if dim % t == 0:
            return t
    return dim


def tiling(m: int, k: int, n: int) -> tuple[int, int, int]:
    """(tm, tk, tn) of a product of (m, k) rows by (k, n) group weights."""
    return (min(TILE_M, m), _tile(k), _tile(n))


@functools.partial(jax.jit, static_argnames=("out_dtype", "transpose_rhs",
                                             "interpret"))
def expert_gmm(lhs, rhs, sizes, *, out_dtype=jnp.float32,
               transpose_rhs: bool = False, interpret: bool = False):
    """(m, k) sorted rows x (G, k, n) [or (G, n, k) transposed] -> (m, n)
    ``out_dtype`` (accumulated in float32); rows of a group past G are
    zero."""
    n = rhs.shape[1] if transpose_rhs else rhs.shape[2]
    return _mb.gmm.__wrapped__(
        lhs, rhs, sizes, out_dtype, tiling(lhs.shape[0], lhs.shape[1], n),
        transpose_rhs=transpose_rhs, interpret=interpret)


@functools.partial(jax.jit, static_argnames=("groups", "interpret"))
def expert_tgmm(lhs_t, grad, sizes, *, groups: int, interpret: bool = False):
    """(k, m) transposed rows x (m, n) cotangent rows -> (groups, k, n)
    float32: each group's rows' product, the weight gradient."""
    k, m = lhs_t.shape
    return _mb.tgmm.__wrapped__(
        lhs_t, grad, sizes, jnp.float32, tiling(m, k, grad.shape[1]),
        num_actual_groups=groups, interpret=interpret)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def grouped_matmul(lhs, rhs, sizes, out_dtype=jnp.float32,
                   interpret: bool = False):
    """lhs (m, k) rows sorted by group, rhs (G, k, n), sizes (G or G + 1,)
    int32 -> (m, n) ``out_dtype``.  ``m`` is a multiple of the row tile."""
    return expert_gmm(lhs, rhs, sizes, out_dtype=out_dtype,
                      interpret=interpret)


def _fwd(lhs, rhs, sizes, out_dtype, interpret):
    return (grouped_matmul(lhs, rhs, sizes, out_dtype, interpret),
            (lhs, rhs, sizes))


def _bwd(out_dtype, interpret, res, g):
    lhs, rhs, sizes = res
    g = g.astype(lhs.dtype)
    d_lhs = expert_gmm(g, rhs, sizes, out_dtype=lhs.dtype, transpose_rhs=True,
                       interpret=interpret)
    d_rhs = expert_tgmm(lhs.swapaxes(0, 1), g, sizes, groups=rhs.shape[0],
                        interpret=interpret)
    return d_lhs, d_rhs.astype(rhs.dtype), None


grouped_matmul.defvjp(_fwd, _bwd)
