"""Pallas TPU kernel: fused lattice decode (paper Alg. 2 / §9.1 hot path).

Fuses: unpack -> anchor coordinates -> centered-mod nearest-color match ->
lattice point, in one pass.  Reads the packed uint32 words (the wire payload)
plus the anchor once, writes the decoded vector once.

    k_a   = round(anchor/s - u)
    k     = k_a + ((c - k_a + q/2) mod q) - q/2     [mod via AND, q = 2^bits']
    z     = (k + u) * s

The side ``s`` is a scalar or a per-coordinate (N,) array (the broadcast of
the collectives' per-bucket sides sidecar that rides the wire next to the
packed words).

Output modes:
  * mode="point"  — the decoded lattice point z (f32), optionally with the
    running-average epilogue ``out = (z + anchor*avg_cnt)/(avg_cnt+1)`` used
    by the ring reduce-scatter;
  * mode="coords" — the int32 coordinates k.  The butterfly collective
    averages own+partner coordinates in exact integer space (bit-identical
    outputs across ranks, the paper's common-output requirement), so it
    needs k rather than z.

Batched variant (:func:`lattice_decode_batched_pallas`): decodes ``senders``
independently-encoded payloads of the *same* vector length against one
shared anchor in a single ``pallas_call`` over a ``(senders, row_tiles)``
grid — the star collective's gathered wire words and the aggregation
server's drain path (repro.agg.server), which previously needed one kernel
launch per sender.  Each sender may carry its own per-coordinate sides (the
per-sender sidecar that rides the wire), while the anchor and the shared
dither ``u`` are read once per row tile.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

COLS = 2048
DEFAULT_BLOCK_ROWS = 8


def _unpack(w, ct_ref, *, q: int, bits: int):
    """Packed words (rows, COLS//per) -> int32 colors (rows, COLS).

    The inverse of the encode kernel's pack, built from the ops Mosaic
    lowers: the words are transposed (lanes -> sublanes), field i of every
    word is written to rows i, i+per, ... of the (COLS, rows) scratch by one
    sublane-strided store, and the scratch is transposed back.  The scratch
    holds one int32 per color, where a per-field broadcast would pad each
    word's ``per`` fields out to a full 128-lane row of VMEM."""
    per = 32 // bits
    wt = jax.lax.bitcast_convert_type(w, jnp.int32).T
    n_words = wt.shape[0]
    for i in range(per):
        ct_ref[pl.ds(i, n_words, stride=per), :] = \
            jnp.bitwise_and(wt >> (i * bits), q - 1)
    return ct_ref[...].T


def _decode_math(c, anchor, u, s, *, q: int, avg_cnt: Optional[int],
                 coords: bool, ref=None):
    """Shared decode body: int32 colors (..., COLS) -> k or z (..., COLS).

    anchor/u/s broadcast against the colors (the batched kernel passes
    (bs, bm, COLS) colors against a (bm, COLS) anchor block).  ``ref``
    is the QState anchor the sender subtracted before encoding: the
    coordinate frame becomes anchor-relative, ``k_a = round((a - ref)/s - u)``
    and the decoded point gets ``ref`` added back."""
    av = anchor - ref if ref is not None else anchor
    t = av / s - u
    k_a = jnp.round(t).astype(jnp.int32)
    delta = jnp.bitwise_and(c - k_a + (q // 2), q - 1) - (q // 2)
    k = k_a + delta
    if coords:
        return k
    z = (k.astype(jnp.float32) + u) * s
    if ref is not None:
        z = z + ref
    if avg_cnt is not None:
        z = (z + anchor * avg_cnt) * (1.0 / (avg_cnt + 1))
    return z


def _decode_kernel(w_ref, a_ref, u_ref, s_ref, *refs, q: int, bits: int,
                   avg_cnt: Optional[int], scalar_s: bool, coords: bool,
                   with_ref: bool):
    if with_ref:
        r_ref, o_ref, ct_ref = refs
        rv = r_ref[...]
    else:
        o_ref, ct_ref = refs
        rv = None
    s = s_ref[0, 0] if scalar_s else s_ref[...]
    c = _unpack(w_ref[...], ct_ref, q=q, bits=bits)
    out = _decode_math(c, a_ref[...].astype(jnp.float32), u_ref[...], s, q=q,
                       avg_cnt=avg_cnt, coords=coords, ref=rv)
    o_ref[...] = out.astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("q", "bits", "n", "avg_cnt",
                                             "mode", "block_rows",
                                             "interpret"))
def lattice_decode_pallas(words: jax.Array, anchor: jax.Array, u: jax.Array,
                          s: jax.Array, ref: jax.Array = None,
                          *, q: int, bits: int, n: int,
                          avg_cnt: Optional[int] = None, mode: str = "point",
                          block_rows: int = DEFAULT_BLOCK_ROWS,
                          interpret: bool = True) -> jax.Array:
    """Decode packed words against flat anchor (N,).

    mode="point": returns z (N,) f32; avg_cnt, if given, fuses the
    running-average epilogue out = (z + anchor*avg_cnt)/(avg_cnt+1).
    mode="coords": returns the int32 coordinates k (N,).
    ``ref`` (N,) is the QState anchor fused into the coordinate frame
    (the sender encoded x - ref); see :func:`_decode_math`.
    """
    assert q & (q - 1) == 0 and bits in (2, 4, 8, 16)
    assert mode in ("point", "coords")
    assert avg_cnt is None or mode == "point"
    per = 32 // bits
    tile = block_rows * COLS
    pad = (-n) % tile
    af = jnp.pad(anchor.astype(jnp.float32), (0, pad)).reshape(-1, COLS)
    uf = jnp.pad(u.astype(jnp.float32), (0, pad)).reshape(-1, COLS)
    rows = af.shape[0]
    wpad = rows * (COLS // per) - words.shape[0]
    wf = jnp.pad(words, (0, wpad)).reshape(rows, COLS // per)
    scalar_s = jnp.ndim(s) == 0
    if scalar_s:
        sf = jnp.asarray(s, jnp.float32).reshape(1, 1)
        s_spec = pl.BlockSpec((1, 1), lambda i: (0, 0))
    else:
        sf = jnp.pad(s.astype(jnp.float32), (0, pad),
                     constant_values=1.0).reshape(-1, COLS)
        s_spec = pl.BlockSpec((block_rows, COLS), lambda i: (i, 0))
    bm = block_rows
    out_dtype = jnp.int32 if mode == "coords" else jnp.float32
    with_ref = ref is not None
    in_arrays = [wf, af, uf, sf]
    in_specs = [
        pl.BlockSpec((bm, COLS // per), lambda i: (i, 0)),
        pl.BlockSpec((bm, COLS), lambda i: (i, 0)),
        pl.BlockSpec((bm, COLS), lambda i: (i, 0)),
        s_spec,
    ]
    if with_ref:
        rf = jnp.pad(ref.astype(jnp.float32), (0, pad)).reshape(-1, COLS)
        in_arrays.append(rf)
        in_specs.append(pl.BlockSpec((bm, COLS), lambda i: (i, 0)))
    out = pl.pallas_call(
        functools.partial(_decode_kernel, q=q, bits=bits, avg_cnt=avg_cnt,
                          scalar_s=scalar_s, coords=(mode == "coords"),
                          with_ref=with_ref),
        grid=(rows // bm,),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((bm, COLS), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((rows, COLS), out_dtype),
        scratch_shapes=[pltpu.VMEM((COLS, bm), jnp.int32)],
        interpret=interpret,
    )(*in_arrays)
    return out.reshape(-1)[:n]


DEFAULT_BLOCK_SENDERS = 16


def _decode_batched_kernel(w_ref, a_ref, u_ref, s_ref, *refs, q: int,
                           bits: int, s_kind: str, coords: bool,
                           with_ref: bool):
    if with_ref:
        r_ref, o_ref, ct_ref = refs
        rv = r_ref[...]                     # (bm, COLS), broadcasts over bs
    else:
        o_ref, ct_ref = refs
        rv = None
    if s_kind == "scalar":
        s = s_ref[0, 0]
    elif s_kind == "shared":
        s = s_ref[...]                      # (bm, COLS), broadcasts over bs
    else:                                   # per-sender: (bs, bm, COLS)
        s = s_ref[...]
    bs, bm, n_words = w_ref.shape
    c = _unpack(w_ref[...].reshape(bs * bm, n_words), ct_ref, q=q, bits=bits)
    out = _decode_math(c.reshape(bs, bm, -1), a_ref[...].astype(jnp.float32),
                       u_ref[...], s, q=q, avg_cnt=None, coords=coords, ref=rv)
    o_ref[...] = out.astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("q", "bits", "n", "mode",
                                             "block_rows", "block_senders",
                                             "interpret"))
def lattice_decode_batched_pallas(words: jax.Array, anchor: jax.Array,
                                  u: jax.Array, s: jax.Array,
                                  ref: jax.Array = None, *, q: int,
                                  bits: int, n: int, mode: str = "coords",
                                  block_rows: int = DEFAULT_BLOCK_ROWS,
                                  block_senders: int = DEFAULT_BLOCK_SENDERS,
                                  interpret: bool = True) -> jax.Array:
    """Decode (senders, n_words) packed payloads against one anchor (n,).

    One pallas_call over a (sender_tiles, row_tiles) grid; each step holds a
    (block_senders, block_rows, COLS) tile in VMEM, decoding
    ``block_senders`` payloads against one anchor block read once per
    tile.  The per-sender words (the 8x-compressed payload) dominate HBM
    traffic.  ``s`` is a scalar, a shared (n,) per-coordinate
    array, or a per-sender (senders, n) array (each sender's sides
    sidecar).  ``ref`` (n,) is the shared QState anchor all senders
    subtracted before encoding (fused like the anchor block, read once per
    row tile).  Returns (senders, n) int32 coords (mode="coords") or f32
    points (mode="point").
    """
    assert q & (q - 1) == 0 and bits in (2, 4, 8, 16)
    assert mode in ("point", "coords")
    senders = words.shape[0]
    per = 32 // bits
    tile = block_rows * COLS
    pad = (-n) % tile
    bs = min(block_senders, senders)
    spad = (-senders) % bs
    af = jnp.pad(anchor.astype(jnp.float32), (0, pad)).reshape(-1, COLS)
    uf = jnp.pad(u.astype(jnp.float32), (0, pad)).reshape(-1, COLS)
    rows = af.shape[0]
    wpad = rows * (COLS // per) - words.shape[1]
    wf = jnp.pad(words, ((0, spad), (0, wpad))).reshape(senders + spad, rows,
                                                        COLS // per)
    bm = block_rows
    if jnp.ndim(s) == 0:
        s_kind = "scalar"
        sf = jnp.asarray(s, jnp.float32).reshape(1, 1)
        s_spec = pl.BlockSpec((1, 1), lambda i, j: (0, 0))
    elif jnp.ndim(s) == 1:
        s_kind = "shared"
        sf = jnp.pad(s.astype(jnp.float32), (0, pad),
                     constant_values=1.0).reshape(-1, COLS)
        s_spec = pl.BlockSpec((bm, COLS), lambda i, j: (j, 0))
    else:
        s_kind = "sender"
        sf = jnp.pad(s.astype(jnp.float32), ((0, spad), (0, pad)),
                     constant_values=1.0).reshape(senders + spad, rows, COLS)
        s_spec = pl.BlockSpec((bs, bm, COLS), lambda i, j: (i, j, 0))
    out_dtype = jnp.int32 if mode == "coords" else jnp.float32
    with_ref = ref is not None
    in_arrays = [wf, af, uf, sf]
    in_specs = [
        pl.BlockSpec((bs, bm, COLS // per), lambda i, j: (i, j, 0)),
        pl.BlockSpec((bm, COLS), lambda i, j: (j, 0)),
        pl.BlockSpec((bm, COLS), lambda i, j: (j, 0)),
        s_spec,
    ]
    if with_ref:
        rf = jnp.pad(ref.astype(jnp.float32), (0, pad)).reshape(-1, COLS)
        in_arrays.append(rf)
        in_specs.append(pl.BlockSpec((bm, COLS), lambda i, j: (j, 0)))
    out = pl.pallas_call(
        functools.partial(_decode_batched_kernel, q=q, bits=bits,
                          s_kind=s_kind, coords=(mode == "coords"),
                          with_ref=with_ref),
        grid=((senders + spad) // bs, rows // bm),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((bs, bm, COLS), lambda i, j: (i, j, 0)),
        out_shape=jax.ShapeDtypeStruct((senders + spad, rows, COLS),
                                       out_dtype),
        scratch_shapes=[pltpu.VMEM((COLS, bs * bm), jnp.int32)],
        interpret=interpret,
    )(*in_arrays)
    return out.reshape(senders + spad, -1)[:senders, :n]
