"""Server side: the flat aggregation round's accumulator + batched drain.

The client intake — frame validation, admission, reassembly, the streaming
fold, the escalation verdict and every response — is
:class:`repro.agg.intake.Intake`, shared with the tree tiers.  This module
adds what the flat server does with a verified client: decode and sum.

Streamed commit (:meth:`AggServer._commit_stream`, windowed rounds): one
dispatch over a completed stream's device record computes its §5 checksum,
its largest effective coordinate, its per-bucket distance telemetry and the
candidate accumulator, and one readback of the small outputs gives the
verdict.  ``RoundStats.fold_syncs`` counts these blocking readbacks (one
per completed stream).

Drain path (:meth:`AggServer.drain`): a sealed payload's words go to the
device as it enters the pending store (:meth:`AggServer._stage_pending`:
one asynchronous rank-1 put, a higher attempt's replacing the one it
supersedes, an expired client's dropped), so the copy overlaps the receive
of the clients after it.  The drain stacks those rows on the device, in
client-id order with zero rows padding the sender axis to the kernel's
block, and decodes all pending payloads of one color space q against the
server's decode reference in ONE batched Pallas launch
(repro.kernels.ops.lattice_decode_batched), verifies their §5 coordinate
checksums vectorized, and sums the accepted senders' integer lattice
coordinates into the round accumulator.  ``RoundStats.staged_on_arrival``
counts the payloads drained from rows put there on arrival,
``staged_at_drain`` those the drain had to put there itself.  Integer
addition is exact and commutative, so the accumulated sum — and therefore
the round mean — is bit-identical under any arrival order, any
receive/drain interleaving, and any drain batching.

Anchored rounds (RoundSpec v2, ``anchor_digest != 0``): clients encoded
``x - anchor``, so the server operates entirely in anchor-relative space —
its decode reference is the zero vector (the server's anchor *is* the round
anchor, digest-checked at construction) and the anchor is added back once
at finalize.  Coordinates and the accumulator stay ~y/s-sized however large
the drifting mean grows; with a zero anchor (digest 0) the path is
bit-identical to the historical server.

Per-bucket telemetry: every drain updates ``RoundStats.dist_b`` (max
|decoded - ref|_inf per bucket over accepted senders) and
``RoundStats.fails_b`` (decode failures attributed per bucket via the
distance surrogate on checksum-failed senders) — the inputs the multi-round
service feeds to :func:`repro.core.qstate.update_y` to produce round k+1's
per-bucket ``y``.

Finalize: mean = ((ksum / count) + u) * s_b (+ anchor), unbucketized — the
same integer-space averaging expression as ``allgather_allreduce_mean``,
against which the acceptance test pins bit-identity.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

import repro.obs as _obs
from repro.agg import rounds
from repro.agg.api import PublishedRound
from repro.agg.intake import Intake, _StreamFold
from repro.agg.transport import frame as wire
from repro.core import error_detect as ED
from repro.kernels import ops as K
from repro.kernels.lattice_decode import DEFAULT_BLOCK_SENDERS

Array = jax.Array


@dataclasses.dataclass
class RoundStats:
    """Per-round service telemetry."""
    received: int = 0
    queued: int = 0
    accepted: int = 0
    duplicates: int = 0
    rejected_wire: int = 0       # framing: truncated / corrupt / bad version
    rejected_spec: int = 0       # well-formed but wrong round/config/anchor
    decode_failures: int = 0     # §5 checksum detections across all drains
    nacks_sent: int = 0
    resends_sent: int = 0        # chunk-level RESEND responses (v3)
    retried: int = 0             # non-terminal RETRY responses (sealed round
                                 # / pending store full — admission control)
    expired: int = 0             # admitted clients dropped by the engine's
                                 # straggler deadline (state discarded; no
                                 # terminal verdict was sent)
    gave_up: int = 0             # clients dropped after escalation exhausted
    drains: int = 0
    bytes_in: int = 0
    peak_unvalidated_bytes: int = 0   # largest frame staged before its CRC
    peak_pending_store_bytes: int = 0  # high-water of staged payload bodies
                                       # + reassembly-retained bytes (the
                                       # streaming drain shrinks this far
                                       # below one body per pending client)
    fold_syncs: int = 0          # the streaming fold's blocking device-to-
                                 # host readbacks (one per completed stream)
    staged_on_arrival: int = 0   # drained payloads whose words were put on
                                 # the device as they entered the store
    staged_at_drain: int = 0     # drained payloads put there by the drain
    max_dist: float = 0.0        # max |decoded - ref|_inf over accepts
    dist_b: Optional[np.ndarray] = None    # (nb,) per-bucket max distance
    fails_b: Optional[np.ndarray] = None   # (nb,) per-bucket failure counts


@partial(jax.jit, static_argnames=("m", "bucket"))
def _commit_math(r: Array, ksum: Array, k0: Array, weights: Array, u: Array,
                 s: Array, ref: Array, *, m: int, bucket: int):
    """A completed stream's verdict inputs and its candidate accumulator.

    r: (padded,) int16 residual record; ksum: (nb, bucket) int32 the round
    accumulator; k0/weights/u/s/ref: (padded,) the decode context (``s``
    the sides repeated per coordinate); m: the payload's n_summed.  ``k = r
    + k0`` is what the batched decode produces, so every output is
    :func:`_drain_math`'s for a single sender: the §5 checksum of ``k``,
    ``max|k_eff|`` with ``k_eff = k + (m-1)*k0``, the per-bucket distance
    (the caller reads it for unit payloads only, m == 1), and ``ksum +
    k_eff``, which the caller keeps only if the checksum and the int32
    guard pass.

    Returns (verdict (nb + 2,) uint32, ksum_new (nb, bucket) int32): the
    verdict packs the checksum, ``max|k_eff|`` and the (nb,) float32
    distances bit for bit into one vector, so the host reads it back in one
    copy."""
    k = r.astype(jnp.int32) + k0
    check = ED.coord_checksum(k, weights)
    k_eff = k + (m - 1) * k0
    max_abs_k = jnp.max(jnp.abs(k_eff))
    z = (k.astype(jnp.float32) + u) * s
    dist_b = jnp.max(jnp.abs(z - ref).reshape(-1, bucket), axis=-1)
    verdict = jnp.concatenate([
        check[None], jax.lax.bitcast_convert_type(max_abs_k, jnp.uint32)[None],
        jax.lax.bitcast_convert_type(dist_b, jnp.uint32)])
    return verdict, ksum + k_eff.reshape(ksum.shape)


@partial(jax.jit, static_argnames=("q", "bucket"))
def _drain_math(rows: "tuple[Array, ...]", sides: Array, checks: Array,
                valid: Array, anchor: Array, u: Array, weights: Array,
                y_col: Array, m: Array, k0: Array, *, q: int, bucket: int):
    """Decode S payloads, verify checksums, sum accepted integer coords.

    rows: S (nw,) uint32 packed words, one array a payload, stacked here
    into the decode's (S, nw) input; sides: (S, nb) f32 sidecars; checks:
    (S,) uint32; valid: (S,) bool (False for the block-size padding rows the
    server adds so drain sizes hit a bounded set of compiled shapes);
    anchor/u/weights: (n,); y_col: (nb,) decode margins at this q; m: (S,)
    int32 n_summed of each payload (1 for an ordinary client); k0: (n,)
    int32 the round's decode-reference coordinates
    (:func:`repro.agg.rounds.decode_ref_coords`).

    A combined payload from a tree tier (m > 1) carries ``K' = k0 + sum_i
    r_i`` — the tier folded m clients' residuals about k0 — so the true
    integer sum it contributes is ``K' + (m-1) * k0`` (each of the m clients
    would have contributed its own ``k0 + r_i``).  For m == 1 the correction
    is identically zero and the math is bit-for-bit the flat server's.

    Returns (ok (S,), ksum_delta (n,) int32, count_delta () int32,
    max_dist () f32, dist_b (nb,), fails_b (nb,), max_abs_k () int32).
    The distance telemetry (max_dist/dist_b/fails_b) is masked to unit
    payloads (m == 1): a combined payload's distance-to-reference scales
    like m*y and would poison the y-tracking margins.
    """
    words = jnp.stack(rows)                                # (S, nw)
    s_sender = jnp.repeat(sides, bucket, axis=-1)          # (S, n)
    k = K.lattice_decode_batched(words, anchor, u, s_sender, q=q,
                                 mode="coords")            # (S, n) int32
    # pin the integer coords (like the collectives): everything below is
    # exact integer math or order-free, keeping the drain bit-deterministic
    k = jax.lax.optimization_barrier(k)
    ok = (ED.coord_checksum(k, weights, axis=-1) == checks) & valid
    k_eff = k + (m[:, None] - 1) * k0[None]                # (S, n) int32
    ksum_delta = jnp.sum(jnp.where(ok[:, None], k_eff, 0), axis=0,
                         dtype=jnp.int32)
    count_delta = jnp.sum(jnp.where(ok, m, 0), dtype=jnp.int32)
    # the largest accepted |effective coordinate|: the server bounds the
    # int32 accumulator with it (count * max|k| < 2^31) and fails loudly
    # instead of silently wrapping — only reachable with huge-norm
    # *unanchored* rounds, where raw coords scale like |x|/s; anchored
    # coords stay ~y/s
    max_abs_k = jnp.max(jnp.where(ok[:, None], jnp.abs(k_eff), 0))
    unit = ok & (m == 1)
    z = (k.astype(jnp.float32) + u[None]) * s_sender
    dist = jnp.abs(z - anchor[None]).reshape(z.shape[0], -1, bucket)
    dist_bk = jnp.max(dist, axis=-1)                       # (S, nb)
    max_dist = jnp.max(jnp.where(unit[:, None], dist_bk, 0.0))
    dist_b = jnp.max(jnp.where(unit[:, None], dist_bk, 0.0), axis=0)
    # failure attribution: for checksum-failed unit senders, buckets whose
    # decoded distance exceeds the margin carry the blame (the §5 distance
    # surrogate, per bucket)
    failed = valid & ~ok & (m == 1)
    over = dist_bk > 1.5 * y_col[None]
    fails_b = jnp.sum(jnp.where(failed[:, None] & over, 1.0, 0.0), axis=0)
    return (ok, ksum_delta, count_delta, max_dist, dist_b, fails_b,
            max_abs_k)


@jax.jit
def _mean_math(ksum: Array, count: Array, u: Array, s_col: Array) -> Array:
    """(nb, bucket) integer sum -> round mean in bucket space.

    Identical float structure to allgather_allreduce_mean's epilogue:
    pinned integer sum, one divide (a *runtime* count always compiles to a
    true IEEE division), add dither, scale by the pinned sides.
    """
    ksum = jax.lax.optimization_barrier(ksum)
    return (ksum.astype(jnp.float32) / count.astype(jnp.float32) + u) * s_col


class AggServer(Intake):
    """One aggregation round's coordinator.

    ``anchor`` doubles as the decode reference and — in anchored rounds —
    the round anchor itself (validated against ``spec.anchor_digest``).
    """

    def __init__(self, spec: wire.RoundSpec, anchor,
                 max_pending: "int | None" = None,
                 streaming: "bool | None" = None):
        """``max_pending`` and ``streaming``: see :class:`Intake`.
        ``streaming=False`` forces the sealed batched path, the bit-parity
        reference of the streaming drain."""
        super().__init__(
            spec, anchor, max_pending, streaming,
            obs=_obs.scope("agg_round", round=spec.round_id),
            stats=RoundStats(dist_b=np.zeros((spec.nb,), np.float32),
                             fails_b=np.zeros((spec.nb,), np.float32)))
        self._anchor_b = rounds.bucketize(jnp.asarray(anchor), spec)
        if spec.anchored:
            # clients encoded x - anchor: decode in anchor-relative space
            # (reference 0), add the anchor back at finalize
            self._ref_flat = jnp.zeros((spec.padded,), jnp.float32)
        else:
            self._ref_flat = self._anchor_b.reshape(-1)
        self._u = rounds.dither(spec)                     # (nb, bucket)
        self._weights = rounds.checksum_weights(spec)     # (padded,)
        self._sides = rounds.sides(spec)                  # (nb,)
        if self._streaming:
            # the decode context per coordinate, for the streamed commit
            self._u_flat = self._u.reshape(-1)
            self._s_flat = jnp.repeat(self._sides, spec.cfg.bucket)
        self._anchor_raw = np.asarray(anchor, np.float32).copy()
        self._published: list[PublishedRound] = []
        self._ksum = jnp.zeros((spec.nb, spec.cfg.bucket), jnp.int32)
        self._count = 0
        self._max_abs_k = 0
        self._publish_traced = False
        # sealed payloads' words on the device, put there as each entered
        # the pending store: client id -> (payload, (nw,) uint32 array)
        self._staged: "dict[int, tuple[wire.Payload, Array]]" = {}
        self._zero_rows: "dict[int, Array]" = {}   # padding row, by nw

    def _stage_pending(self, p: wire.Payload) -> None:
        """Start the words' copy to the device while the host goes on
        receiving: one asynchronous rank-1 put (no host-side tiling), which
        replaces the array of a payload this one supersedes.  ``p.words``
        views the sealed body, which nothing writes again."""
        with _obs.span("stage.put"):
            self._staged[p.client_id] = (p, jax.device_put(p.words))

    def expire_client(self, client_id: int) -> None:
        """:meth:`Intake.expire_client`, dropping the staged array too."""
        super().expire_client(client_id)
        self._staged.pop(client_id, None)

    def _commit_stream(self, h: wire.FrameHeader, p: wire.Payload,
                       rec: _StreamFold) -> wire.Response:
        """Verify the fold's §5 checksum and commit it into the round
        accumulator: one dispatch, one readback."""
        m = h.n_summed
        verdict, ksum = _commit_math(
            rec.r, self._ksum, self._k0, self._weights, self._u_flat,
            self._s_flat, self._ref_flat, m=m, bucket=self.spec.cfg.bucket)
        # the stream's one blocking readback: the verdict's small inputs
        verdict = np.asarray(verdict)
        self._obs.inc("fold_syncs")
        dist_b = verdict[2:].view(np.float32)
        if int(verdict[0]) != (h.check & 0xFFFFFFFF):
            if m == 1:
                y_col = np.asarray(wire.y_buckets_at_attempt(self.spec,
                                                             h.attempt))
                self._stats.fails_b = self._stats.fails_b + \
                    (dist_b > 1.5 * y_col).astype(np.float32)
            return self._escalate(h.client_id, h.attempt, h.q)
        self._max_abs_k = max(self._max_abs_k,
                              int(verdict[1:2].view(np.int32)[0]))
        if (self._count + m) * self._max_abs_k >= 2 ** 31:
            raise OverflowError(
                f"round {self.spec.round_id}: accumulating a streamed "
                f"sender with |coords| up to {self._max_abs_k} can "
                f"overflow the int32 sum ({self._count} accepted so far); "
                f"anchor the round (RoundSpec.anchor_digest) so "
                f"coordinates stay ~y/s instead of ~|x|/s")
        # exact: every partial sum stays under count * max|k| < 2^31
        self._ksum = ksum
        self._count += m
        self._obs.inc("queued")
        self._obs.inc("accepted")
        if m == 1:
            self._obs.set_max("max_dist", float(dist_b.max()))
            self._stats.dist_b = np.maximum(self._stats.dist_b, dist_b)
        self._accepted.add(h.client_id)
        return self._ack(h.client_id, ack=h.n_chunks)

    # ------------------------------------------------------------ AggNode
    def ingest_frame(self, data: bytes, now: float = 0.0) -> "list[bytes]":
        """AggNode verb: one frame in, its response out (``now`` unused —
        the flat server is purely event-driven)."""
        return [self.receive(data)]

    def tick(self, now: float = 0.0) -> "list[bytes]":
        """AggNode verb: drain pending payloads + chunk-level RESENDs."""
        return self.drain()

    def published(self) -> "list[PublishedRound]":
        """AggNode verb: the round's outcome, once it has one.

        Empty until the round is sealed and every admitted client is
        resolved; then the round finalizes lazily on first call and the
        :class:`~repro.agg.api.PublishedRound` is cached (timestamps are
        zero — the flat server keeps no clock; the engine's records carry
        real open/seal/publish times)."""
        if self._published:
            return list(self._published)
        if not self._sealed or self.unresolved:
            return []
        mean, stats = self.finalize()
        self._published.append(PublishedRound(
            round_id=self.spec.round_id, spec=self.spec,
            anchor=self._anchor_raw if self.spec.anchored else None,
            mean=mean, stats=stats, accepted=self.accepted_clients,
            opened_at=0.0, sealed_at=0.0, published_at=0.0,
            anchor_round=0, staleness=0.0))
        return list(self._published)

    # --------------------------------------------------------------- DRAIN
    def drain(self) -> list[bytes]:
        """Decode everything pending; returns ACK/NACK/REJECT responses.

        One batched kernel launch per distinct color space q among the
        pending payloads (a round at a single escalation level — the common
        case — drains in exactly one launch).
        """
        if not self._pending:
            return self._resend_requests()
        self._obs.inc("drains")
        with _obs.span("agg.drain", "drain",
                       parent=("round", self.spec.round_id),
                       round=self.spec.round_id,
                       payloads=len(self._pending)) as region:
            by_q: dict[int, list[wire.Payload]] = {}
            for p in self._take_pending():
                by_q.setdefault(p.q, []).append(p)
            staged, self._staged = self._staged, {}
            responses = []
            for q, plist in sorted(by_q.items()):
                responses += self._drain_q(q, plist, staged)
            region.note(accepted=len(self._accepted))
        return responses + self._resend_requests()

    def _drain_q(self, q: int, plist: "list[wire.Payload]",
                 staged: "dict[int, tuple[wire.Payload, Array]]"
                 ) -> "list[bytes]":
        """One batched decode of the pending payloads of color space ``q``
        (in client-id order), their words taken from ``staged`` (the
        device arrays put there on arrival, popped as used); returns their
        ACK/NACK/REJECT responses."""
        # pad the sender axis to the kernel's block size so drain sizes map
        # onto a bounded set of compiled shapes (padding rows carry
        # valid=False and never enter the sum)
        S = len(plist)
        pad = (-S) % DEFAULT_BLOCK_SENDERS
        attempt0 = plist[0].attempt
        with _obs.span("agg.stage"):
            rows, n_arrived = [], 0
            for p in plist:
                p_staged, row = staged.pop(p.client_id, (None, None))
                if p_staged is p:
                    n_arrived += 1
                else:       # no array of its own: to the device now
                    row = jax.device_put(p.words)
                rows.append(row)
            self._obs.inc("staged_on_arrival", n_arrived)
            self._obs.inc("staged_at_drain", S - n_arrived)
            nw = plist[0].words.shape[-1]
            zero = self._zero_rows.get(nw)
            if zero is None:
                zero = self._zero_rows[nw] = jnp.zeros((nw,), jnp.uint32)
            rows = tuple(rows) + (zero,) * pad
            sides = jnp.asarray(np.pad(
                np.stack([p.sides for p in plist]), ((0, pad), (0, 0)),
                constant_values=1.0))
            checks = jnp.asarray(np.pad(
                np.array([p.check for p in plist], np.uint32), (0, pad)))
            valid = jnp.asarray(np.arange(S + pad) < S)
            m = jnp.asarray(np.pad(
                np.array([p.n_summed for p in plist], np.int32), (0, pad),
                constant_values=1))
            y_col = jnp.asarray(wire.y_buckets_at_attempt(self.spec,
                                                          attempt0))
        with _obs.span("agg.decode"):
            (ok, ksum_delta, count_delta, max_dist, dist_b, fails_b,
             max_abs_k) = \
                _drain_math(rows, sides, checks, valid, self._ref_flat,
                            self._u.reshape(-1), self._weights, y_col, m,
                            self._k0, q=q, bucket=self.spec.cfg.bucket)
            del rows    # the decode's stack holds the words from here on
            ok = np.asarray(ok)[:S]
            n_clients = int(count_delta)    # accepts plus tier fan-in (m > 1)
            max_abs_k = int(max_abs_k)
            max_dist = float(max_dist)
            dist_b = np.asarray(dist_b)
            fails_b = np.asarray(fails_b)
        n_ok = int(ok.sum())
        # int32 accumulator guard: sum_i |k_i| <= count * max|k| must stay
        # below 2^31 or the exact integer sum may have wrapped — fail loudly
        # (an anchored round is the fix: coords stay ~y/s)
        self._max_abs_k = max(self._max_abs_k, max_abs_k)
        if (self._count + n_clients) * self._max_abs_k >= 2 ** 31:
            raise OverflowError(
                f"round {self.spec.round_id}: accumulating {n_ok} more "
                f"senders with |coords| up to {self._max_abs_k} can "
                f"overflow the int32 sum ({self._count} accepted so "
                f"far); anchor the round (RoundSpec.anchor_digest) so "
                f"coordinates stay ~y/s instead of ~|x|/s")
        self._ksum = self._ksum + ksum_delta.reshape(self._ksum.shape)
        self._count += n_clients
        self._obs.inc("accepted", n_ok)
        self._obs.set_max("max_dist", max_dist)
        self._stats.dist_b = np.maximum(self._stats.dist_b, dist_b)
        self._stats.fails_b = self._stats.fails_b + fails_b
        responses = []
        for p, good in zip(plist, ok):
            if good:
                self._accepted.add(p.client_id)
                self._rx.discard(p.client_id)   # stale chunk sessions
                responses.append(self._respond(self._ack(p.client_id)))
            else:
                responses.append(self._respond(
                    self._escalate(p.client_id, p.attempt, p.q)))
        return responses

    # ------------------------------------------------------------ FINALIZE
    def finalize(self) -> tuple[np.ndarray, RoundStats]:
        """Drain anything still pending and return (mean (d,), stats).

        The mean is over the accepted senders; with zero accepts it is the
        all-zeros vector (the round anchor in anchored rounds — the best
        available estimate when nobody reported).  Bit-identical for any
        arrival order of the same accepted payload set.
        """
        self.drain()
        if _obs.tracing_enabled() and not self._publish_traced:
            self._publish_traced = True
            tr = _obs.tracer()
            tr.event("publish", parent=("round", self.spec.round_id),
                     round=self.spec.round_id, accepted=len(self._accepted))
            # close the round span (the engine opened it; a standalone flat
            # server gets a synthetic one from the parent fallback above)
            tr.end(("round", self.spec.round_id))
        if self._count == 0:
            if not self.spec.anchored:
                return np.zeros((self.spec.d,), np.float32), self.stats
            return (np.asarray(rounds.unbucketize(self._anchor_b, self.spec)),
                    self.stats)
        mean_b = _mean_math(self._ksum, jnp.int32(self._count), self._u,
                            self._sides[:, None])
        if self.spec.anchored:
            mean_b = mean_b + self._anchor_b
        return np.asarray(rounds.unbucketize(mean_b, self.spec)), self.stats