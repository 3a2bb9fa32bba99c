"""Plain reference of one aggregation round: parse the clients' frames,
decode every payload's lattice coordinates, sum them and publish the mean.

Written from the protocol's definition, with no code of the system under
test: the frame layout (a 72-byte little-endian header, a CRC word, then a
``mtu``-sized slice of the body), the body (packed ``bits``-bit mod-q colors,
then one f32 side per bucket), the round's shared dither
``u = U[-1/2, 1/2)`` drawn from ``fold_in(PRNGKey(seed), round_id)``, and the
anchored decode: clients encoded ``x - anchor``, so every payload decodes
against the zero reference, where the nearest coordinate to a color c is the
centered residue of c mod q.  The mean is ``(sum_k / count + u) * s`` plus
the anchor, added as a separate operation.
"""
from __future__ import annotations

import struct

import jax
import jax.numpy as jnp
import numpy as np

HEADER = struct.Struct("<4sHH16I")
FIELDS = ("round_id", "client_id", "attempt", "q", "d", "bucket", "seed",
          "rot_seed", "n_words", "nb", "check", "anchor_digest", "n_chunks",
          "chunk_index", "payload_crc", "n_summed")


def parse_frame(data: bytes) -> "tuple[dict, bytes]":
    vals = HEADER.unpack_from(data, 0)
    h = dict(zip(FIELDS, vals[3:]))
    return h, data[HEADER.size + 4:]


def payload(frames: "list[bytes]") -> "tuple[dict, np.ndarray, np.ndarray]":
    """(header, words (n_words,) uint32, sides (nb,) f32) of one client."""
    parts = sorted((parse_frame(f) for f in frames),
                   key=lambda p: p[0]["chunk_index"])
    h = parts[0][0]
    body = b"".join(c for _, c in parts)
    words = np.frombuffer(body, "<u4", count=h["n_words"])
    sides = np.frombuffer(body, "<f4", offset=4 * h["n_words"],
                          count=h["nb"])
    return h, words, sides


def bits_for(q: int) -> int:
    raw = max(1, int(np.ceil(np.log2(q))))
    return next(b for b in (1, 2, 4, 8, 16) if b >= raw)


def dither(seed: int, round_id: int, nb: int, bucket: int) -> jax.Array:
    key = jax.random.fold_in(jax.random.PRNGKey(seed), round_id)
    return jax.random.uniform(key, (nb, bucket), jnp.float32, -0.5, 0.5)


def _coords(words: jax.Array, q: int, n: int) -> jax.Array:
    """(S, n_words) packed colors -> (S, n) int32 coordinates nearest 0."""
    bits = bits_for(q)
    per = 32 // bits
    shifts = jnp.arange(per, dtype=jnp.uint32) * bits
    c = (words[..., None] >> shifts) & jnp.uint32((1 << bits) - 1)
    c = c.reshape(words.shape[0], -1)[:, :n].astype(jnp.int32)
    return jnp.mod(c + q // 2, q) - q // 2


@jax.jit
def _mean_b(ksum, count, u, s_col):
    ksum = jax.lax.optimization_barrier(ksum)
    return (ksum.astype(jnp.float32) / count.astype(jnp.float32) + u) * s_col


@jax.jit
def _mean_b_low(ksum, count, u, s_col):
    """The control: the same epilogue one precision down (bfloat16)."""
    lo = jnp.bfloat16
    return ((ksum.astype(lo) / count.astype(lo) + u.astype(lo))
            * s_col.astype(lo)).astype(jnp.float32)


def round_mean(frames_by_client: "dict[int, list[bytes]]", anchor,
               q: int, y0: float, *, block: int = 32,
               low_precision: bool = False) -> "tuple[np.ndarray, int]":
    """(published mean (d,) f32, number of clients summed) of a round in
    which every client of ``frames_by_client`` is accepted."""
    cids = sorted(frames_by_client)
    h0, _, _ = payload(frames_by_client[cids[0]])
    d, nb, bucket = h0["d"], h0["nb"], h0["bucket"]
    n = nb * bucket
    s = np.float32(np.float32(y0) * np.float32(2.0 / (q - 1)))
    coords = jax.jit(lambda w: jnp.sum(_coords(w, q, n), axis=0,
                                       dtype=jnp.int32))
    ksum = jnp.zeros((n,), jnp.int32)
    for lo in range(0, len(cids), block):
        ws = []
        for cid in cids[lo:lo + block]:
            h, w, sd = payload(frames_by_client[cid])
            if h["q"] != q or not np.all(sd == s):
                raise ValueError(f"client {cid}: q={h['q']} or sides differ "
                                 f"from the round's (q={q}, s={s})")
            ws.append(w)
        ksum = ksum + coords(jnp.asarray(np.stack(ws)))
    u = dither(h0["seed"], h0["round_id"], nb, bucket)
    s_col = jnp.full((nb, 1), s, jnp.float32)
    fn = _mean_b_low if low_precision else _mean_b
    mean_b = fn(ksum.reshape(nb, bucket), jnp.int32(len(cids)), u, s_col)
    a = jnp.pad(jnp.asarray(anchor, jnp.float32), (0, n - d))
    mean_b = mean_b + a.reshape(nb, bucket)
    return np.asarray(mean_b).reshape(-1)[:d], len(cids)
