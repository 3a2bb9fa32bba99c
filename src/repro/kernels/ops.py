"""Public jit'd wrappers over the Pallas kernels.

On this CPU container kernels run in interpret mode (the Pallas body executes
under the interpreter); on a real TPU backend they compile to Mosaic.  The
``interpret`` decision is made once per call from the default backend, and
every wrapper falls back to the jnp reference for shapes the kernels don't
cover (non-power-of-two FWHT dims, q not a power of two, tiny inputs).
"""
from __future__ import annotations

from functools import lru_cache, partial
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental.pallas.ops.tpu import splash_attention as _splash

import repro.obs as _obs
from repro.core import lattice as L
from repro.kernels import ref as _ref
from repro.kernels.fwht import fwht_pallas, MAX_D
from repro.kernels.lattice_encode import lattice_encode_pallas
from repro.kernels.lattice_decode import (lattice_decode_pallas,
                                          lattice_decode_batched_pallas)
from repro.kernels.flash_attention import flash_attention_pallas
from repro.kernels import grouped_matmul as _gmm

# Kernel-dispatch telemetry: how many decode launches each wrapper has issued
# (counted at trace time — one entry per kernel launch in the compiled
# program).  tests/test_agg.py asserts the star collective and the agg
# server drain stay single-dispatch however many senders they decode.
#
# The counts live in the repro.obs registry (always-registered counters, so
# they are exported whenever metrics are enabled); DISPATCH_COUNTS is kept
# as a read-only dict-shaped view over those counters for the existing
# callers and tests.
_DISPATCH = {
    "lattice_decode": _obs.registry().counter("kernel_dispatch",
                                              kernel="lattice_decode"),
    "lattice_decode_batched": _obs.registry().counter(
        "kernel_dispatch", kernel="lattice_decode_batched"),
}


class _DispatchCounts:
    """Dict-shaped live view over the registry dispatch counters."""
    __slots__ = ()

    def __getitem__(self, k: str) -> int:
        return _DISPATCH[k].value

    def get(self, k: str, default=None):
        c = _DISPATCH.get(k)
        return default if c is None else c.value

    def __contains__(self, k) -> bool:
        return k in _DISPATCH

    def __iter__(self):
        return iter(_DISPATCH)

    def __len__(self) -> int:
        return len(_DISPATCH)

    def keys(self):
        return _DISPATCH.keys()

    def values(self):
        return [c.value for c in _DISPATCH.values()]

    def items(self):
        return [(k, c.value) for k, c in _DISPATCH.items()]

    def __eq__(self, other):
        return dict(self.items()) == other

    def __repr__(self) -> str:
        return repr(dict(self.items()))


DISPATCH_COUNTS = _DispatchCounts()


def reset_dispatch_counts() -> None:
    for c in _DISPATCH.values():
        c.reset()


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


def on_tpu() -> bool:
    """The kernels compile to Mosaic (not interpret mode) here."""
    return not _interpret()


def _pow2(n: int) -> bool:
    return n >= 1 and (n & (n - 1)) == 0


def fwht(x: jax.Array) -> jax.Array:
    """Normalized Walsh-Hadamard over the last axis (kernel when possible)."""
    d = x.shape[-1]
    if not _pow2(d) or d < 4 or d > MAX_D:
        return _ref.fwht_ref(x)
    return fwht_pallas(x, interpret=_interpret())


def lattice_encode(x: jax.Array, u: jax.Array, s, *, q: int,
                   return_coords: bool = False,
                   anchor: Optional[jax.Array] = None):
    """Fused encode of flat x -> packed uint32 words (+ coords if asked).

    s is a scalar side or a per-coordinate (N,) array (per-bucket sides
    broadcast by the collectives).  ``anchor`` (N,), when given, is the
    QState anchor subtracted in-kernel: k = round((x - anchor)/s - u)."""
    bits = L.bits_for_q(q)
    if not _pow2(q) or bits not in (2, 4, 8, 16) or x.size < 32:
        return _ref.lattice_encode_ref(x, u, s, q=q, bits=bits,
                                       return_coords=return_coords,
                                       anchor=anchor)
    return lattice_encode_pallas(x, u, jnp.asarray(s), anchor, q=q, bits=bits,
                                 return_coords=return_coords,
                                 interpret=_interpret())


def lattice_decode(words: jax.Array, anchor: jax.Array, u: jax.Array, s,
                   *, q: int, avg_cnt: Optional[int] = None,
                   mode: str = "point",
                   ref: Optional[jax.Array] = None) -> jax.Array:
    """Fused decode: mode="point" (z, optional running-average epilogue)
    or mode="coords" (int32 lattice coordinates).  ``ref`` (N,) is the
    QState anchor the sender subtracted (fused anchor-relative frame)."""
    bits = L.bits_for_q(q)
    n = anchor.shape[0]
    _DISPATCH["lattice_decode"].inc()
    if not _pow2(q) or bits not in (2, 4, 8, 16) or n < 32:
        return _ref.lattice_decode_ref(words, anchor, u, s, q=q, bits=bits,
                                       n=n, avg_cnt=avg_cnt, mode=mode,
                                       ref=ref)
    return lattice_decode_pallas(words, anchor, u, jnp.asarray(s), ref, q=q,
                                 bits=bits, n=n, avg_cnt=avg_cnt, mode=mode,
                                 interpret=_interpret())


def lattice_decode_batched(words: jax.Array, anchor: jax.Array, u: jax.Array,
                           s, *, q: int, mode: str = "coords",
                           ref: Optional[jax.Array] = None) -> jax.Array:
    """One fused launch decoding (senders, n_words) payloads of the same
    vector against a shared anchor (n,) -> (senders, n).

    ``s`` is a scalar side, a shared per-coordinate (n,) array, or a
    per-sender (senders, n) array (each sender's sides sidecar); ``ref``
    (n,) the shared QState anchor all senders subtracted.  Used by the star
    collective (the gathered wire) and the aggregation server's drain
    (repro.agg.server) instead of one kernel call per sender.
    """
    bits = L.bits_for_q(q)
    n = anchor.shape[0]
    _DISPATCH["lattice_decode_batched"].inc()
    if not _pow2(q) or bits not in (2, 4, 8, 16) or n < 32:
        return _ref.lattice_decode_batched_ref(words, anchor, u,
                                               jnp.asarray(s), q=q, bits=bits,
                                               n=n, mode=mode, ref=ref)
    return lattice_decode_batched_pallas(words, anchor, u, jnp.asarray(s),
                                         ref, q=q, bits=bits, n=n, mode=mode,
                                         interpret=_interpret())


@partial(jax.jit, static_argnames=("q", "n"))
def _residuals_jit(words, k0, *, q: int, n: int):
    return _ref.lattice_residuals_ref(words, k0, q=q,
                                      bits=L.bits_for_q(q), n=n)


def lattice_residuals(words: jax.Array, k0: jax.Array, *,
                      q: int) -> jax.Array:
    """Centered mod-q residuals of packed payloads about reference coords.

    The integer-only half of proximity decode: ``r = centered_mod(c - k0,
    q)`` per coordinate, so ``k0 + r`` is EXACTLY what the batched decode's
    mode="coords" would produce for the same payload — without touching the
    float anchor/side/dither math and without a decode dispatch.  This is
    the tree tier's sum-without-decode primitive (repro.agg.tree): tiers
    sum residuals in int space and the root alone decodes.  words:
    (..., n_words) uint32; k0: (n,) int32 -> (..., n) int32.  Deliberately
    NOT counted in DISPATCH_COUNTS — the acceptance gate asserts tiers
    issue zero decode dispatches."""
    return _residuals_jit(words, k0, q=q, n=k0.shape[0])


@partial(jax.jit, static_argnames=("q",))
def _pack_coords_jit(k, *, q: int):
    return _ref.lattice_pack_coords_ref(k, q=q, bits=L.bits_for_q(q))


def lattice_pack_coords(k: jax.Array, *, q: int) -> jax.Array:
    """Pack int32 lattice coordinates as mod-q color words (the inverse of
    the unpack+lift in :func:`lattice_residuals`): the tier's repack after
    its in-place integer sum.  k: (..., n) int32 -> (..., n_words) uint32."""
    return _pack_coords_jit(k, q=q)


def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    causal: bool = True) -> jax.Array:
    """Flash attention fwd over (BH, S, D) tensors (pads to block multiples)."""
    BH, sq, d = q.shape
    sk = k.shape[1]
    bq = min(256, sq)
    bk = min(256, sk)
    if sq % bq or sk % bk or sq < 16:
        return _ref.flash_attention_ref(q, k, v, causal=causal)
    return flash_attention_pallas(q, k, v, causal=causal, bq=bq, bk=bk,
                                  interpret=_interpret())


@lru_cache(maxsize=None)
def _causal_kernel(heads: int, seq: int, interpret: bool):
    """The splash attention kernel (forward, dq and dkv) for ``heads``
    causal heads over ``seq`` tokens, in blocks of the largest power of two
    <= 512 that divides ``seq``."""
    b = 512
    while seq % b:
        b //= 2
    blocks = _splash.BlockSizes(
        block_q=b, block_kv=b, block_kv_compute=b, block_q_dkv=b,
        block_kv_dkv=b, block_kv_dkv_compute=b, block_q_dq=b, block_kv_dq=b)
    mask = _splash.MultiHeadMask([_splash.CausalMask((seq, seq))] * heads)
    return _splash.make_splash_mha(mask, block_sizes=blocks, head_shards=1,
                                   q_seq_shards=1, interpret=interpret)


def causal_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                     scale: float) -> jax.Array:
    """Causal softmax attention through the installed Pallas splash kernel,
    differentiable: an online softmax over key blocks that skips the blocks
    above the diagonal and keeps only the log-sum-exp for the backward, so
    no (S, S) score row is formed.  q, k: (B, S, H, dk); v: (B, S, H, dv),
    dv may differ from dk; S a multiple of 128 -> (B, S, H, dv) in q's
    dtype.  ``scale`` is folded into q (in f32, then rounded to q's dtype).
    """
    _, S, H, _ = q.shape
    if S % 128:
        raise ValueError(f"sequence {S} is not a multiple of 128")
    kernel = _causal_kernel(H, S, _interpret())
    qs = (q.astype(jnp.float32) * scale).astype(q.dtype)
    out = jax.vmap(kernel)(qs.swapaxes(1, 2), k.swapaxes(1, 2),
                           v.swapaxes(1, 2))
    return out.swapaxes(1, 2)


def grouped_matmul(lhs: jax.Array, rhs: jax.Array, sizes: jax.Array,
                   out_dtype=jnp.float32) -> jax.Array:
    """Rows sorted by group (m, k) x group weights (G, k, n) -> (m, n)
    ``out_dtype``, differentiable (``kernels/grouped_matmul.py``).
    ``sizes`` (G or G + 1,) int32; the rows of a last group past G come out
    zero."""
    return _gmm.grouped_matmul(lhs, rhs, sizes, jnp.dtype(out_dtype),
                               _interpret())
