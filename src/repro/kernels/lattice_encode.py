"""Pallas TPU kernel: fused lattice encode (paper §3.2 / §9.1 hot path).

Fuses: scale -> dither -> round -> mod-q color -> bit-pack, in one pass over
HBM.  Input x is read once; the packed output is d*log2(q)/32 uint32 words —
an 8x (q=16) to 32x (q=2) write-traffic reduction versus materializing f32
colors, and the exact payload that goes on the ICI wire.

Layout: the flat vector is viewed as (rows, COLS) tiles; each grid step
processes (BM, COLS) in VMEM and writes (BM, COLS/per) packed words, where
per = 32/bits colors per word.  COLS=2048 keeps the packed lanes >= 128 for
every supported bit-width (2,4,8,16).

The lattice side ``s`` is either a scalar (one bound for the whole vector)
or a per-coordinate (N,) array — the broadcast of per-*bucket* sides used by
the quantized collectives (repro.dist.collectives), whose buckets each carry
their own distance bound y and side s = 2y/(q-1).

With ``return_coords=True`` the kernel additionally writes the int32 lattice
coordinates ``k = round(x/s - u)`` — the butterfly collective needs both the
wire words (to send) and the local coordinates (to average in exact integer
space) from a single fused pass over x.

With ``anchor`` (the :class:`repro.core.qstate.QState` anchor, bucketized
and flattened like x) the subtraction is fused into the same pass:
``k = round((x - anchor)/s - u)``.  The wire still carries only the packed
mod-q colors; anchoring keeps ``|k| ~ y/s`` however large ``|x|`` grows
(the drifting large-norm regime), at zero extra HBM traffic beyond reading
the anchor once.  ``anchor=None`` is byte-for-byte the historical kernel.

q must be a power of two (the paper's experiments use q in {8, 16, 64});
mod-q of the two's-complement coordinate is a bitwise AND with q-1.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

COLS = 2048
DEFAULT_BLOCK_ROWS = 8


def _encode_kernel(x_ref, u_ref, s_ref, *refs, q: int, bits: int,
                   scalar_s: bool, with_coords: bool, with_anchor: bool):
    if with_anchor:
        a_ref, *o_refs = refs
        xv = x_ref[...].astype(jnp.float32) - a_ref[...]
    else:
        o_refs = refs
        xv = x_ref[...].astype(jnp.float32)
    *o_refs, ct_ref = o_refs
    s = s_ref[0, 0] if scalar_s else s_ref[...]
    t = xv / s - u_ref[...]
    k = jnp.round(t).astype(jnp.int32)
    # Pack: word j of a row holds colors j*per .. j*per+per-1.  Mosaic has
    # no lane-splitting reshape and no lane-strided load, so the colors are
    # transposed into VMEM scratch (lanes -> sublanes), field i of every
    # word is one sublane-strided load, and the OR of the shifted fields is
    # transposed back.  Fields are disjoint, so OR == the packed sum.
    ct_ref[...] = jnp.bitwise_and(k, q - 1).T        # mod q (q = 2^bits')
    per = 32 // bits
    n_words = ct_ref.shape[0] // per
    acc = ct_ref[pl.ds(0, n_words, stride=per), :]
    for i in range(1, per):
        acc = acc | (ct_ref[pl.ds(i, n_words, stride=per), :] << (i * bits))
    o_refs[0][...] = jax.lax.bitcast_convert_type(acc.T, jnp.uint32)
    if with_coords:
        o_refs[1][...] = k


@functools.partial(jax.jit,
                   static_argnames=("q", "bits", "return_coords",
                                    "block_rows", "interpret"))
def lattice_encode_pallas(x: jax.Array, u: jax.Array, s: jax.Array,
                          anchor: jax.Array = None,
                          *, q: int, bits: int, return_coords: bool = False,
                          block_rows: int = DEFAULT_BLOCK_ROWS,
                          interpret: bool = True):
    """Encode flat x (N,) with dither u (N,) and side s (scalar or (N,)).

    Returns packed uint32 words of length ceil(N/per) where per=32/bits —
    plus the int32 coordinates (N,) when ``return_coords``.  N is padded
    internally to a (rows, COLS) view; callers slice via
    repro.core.lattice.packed_len(N, bits).  ``anchor`` (N,), when given,
    is subtracted in-kernel: ``k = round((x - anchor)/s - u)``.
    """
    assert q & (q - 1) == 0 and 2 <= q <= (1 << bits), (q, bits)
    assert bits in (2, 4, 8, 16)
    n = x.shape[0]
    per = 32 // bits
    tile = block_rows * COLS
    pad = (-n) % tile
    xf = jnp.pad(x.astype(jnp.float32), (0, pad)).reshape(-1, COLS)
    uf = jnp.pad(u.astype(jnp.float32), (0, pad)).reshape(-1, COLS)
    scalar_s = jnp.ndim(s) == 0
    if scalar_s:
        sf = jnp.asarray(s, jnp.float32).reshape(1, 1)
        s_spec = pl.BlockSpec((1, 1), lambda i: (0, 0))
    else:
        # pad sides with ones so the padded tail encodes deterministic zeros
        sf = jnp.pad(s.astype(jnp.float32), (0, pad),
                     constant_values=1.0).reshape(-1, COLS)
        s_spec = pl.BlockSpec((block_rows, COLS), lambda i: (i, 0))
    rows = xf.shape[0]
    bm = block_rows
    grid = (rows // bm,)
    with_anchor = anchor is not None
    in_arrays = [xf, uf, sf]
    in_specs = [
        pl.BlockSpec((bm, COLS), lambda i: (i, 0)),
        pl.BlockSpec((bm, COLS), lambda i: (i, 0)),
        s_spec,
    ]
    if with_anchor:
        af = jnp.pad(anchor.astype(jnp.float32), (0, pad)).reshape(-1, COLS)
        in_arrays.append(af)
        in_specs.append(pl.BlockSpec((bm, COLS), lambda i: (i, 0)))
    out_shape = [jax.ShapeDtypeStruct((rows, COLS // per), jnp.uint32)]
    out_specs = [pl.BlockSpec((bm, COLS // per), lambda i: (i, 0))]
    if return_coords:
        out_shape.append(jax.ShapeDtypeStruct((rows, COLS), jnp.int32))
        out_specs.append(pl.BlockSpec((bm, COLS), lambda i: (i, 0)))
    out = pl.pallas_call(
        functools.partial(_encode_kernel, q=q, bits=bits, scalar_s=scalar_s,
                          with_coords=return_coords, with_anchor=with_anchor),
        grid=grid,
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=[pltpu.VMEM((COLS, bm), jnp.int32)],
        interpret=interpret,
    )(*in_arrays)
    n_words = (n + per - 1) // per
    words = out[0].reshape(-1)[:n_words]
    if return_coords:
        return words, out[1].reshape(-1)[:n]
    return words
