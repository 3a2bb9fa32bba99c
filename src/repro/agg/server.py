"""Server side: streaming integer-space accumulator + batched drain.

Arrival path (:meth:`AggServer.receive`): parse/validate one transport
frame (framing errors and spec mismatches are counted and REJECTed —
including truncated, corrupt, version-mismatched, anchor-digest-mismatched
and MTU-geometry-violating frames), dedupe by client id, and route it by
its chunk coordinates: a single-frame payload is buffered directly, a chunk
of a larger payload goes through the transport session layer
(:class:`repro.agg.transport.session.Reassembler`) — out-of-order and
duplicate tolerant, committing each validated chunk in place so the
transport never stages more than one frame (header + MTU) of unvalidated
bytes, independent of d.  Either way the server buffers the *packed words*
— the 8x-compressed form — until a drain; a completed reassembly hands the
drain the same zero-copy Payload view a single frame would have.

**Streaming drain** (v5, on when ``RoundSpec.window > 0``): the
seal-then-stage path above is replaced for multi-chunk payloads.  The
session emits each stream's validated contiguous word prefix range by range
(``on_range_validated``) and frees the chunk bytes; the server
residual-folds every range on arrival (the residuals about the round's
decode-reference coordinates ``k0`` — the same integer identity the tree
tiers use, so ``k0 + r`` is bit-for-bit what the batched decode would have
produced) into a *speculative* per-stream record keyed by ``(client,
attempt, payload_crc)``: an int16 residual vector that lives on the device.
A range costs one asynchronous dispatch and no readback.  Nothing touches
the round accumulator until the stream completes AND its payload-CRC seal
holds; then one dispatch over the whole record computes its §5 checksum,
its largest effective coordinate, its per-bucket distance telemetry and the
candidate accumulator, and one readback of the small outputs gives the
verdict.  So "rollback" on a seal failure, escalation reset, eviction or
expiry is simply dropping the record (``on_stream_discarded``), and the
published mean stays bit-identical to the sealed drain under any arrival
order, loss, duplication or escalation.  ``RoundStats.fold_syncs`` counts
the streaming fold's blocking readbacks (one per completed stream).
``RoundStats.peak_pending_store_bytes`` gauges what the old path buffered:
staged bodies + reassembly bytes — with the window holding senders
near-in-order it stays far below one body per pending client.
Single-chunk payloads keep the batched path (they never had a body-sized
backlog).

Chunked rounds add one response status: a drain that finds a client's
reassembly still incomplete emits ``STATUS_RESEND`` naming exactly the
missing chunk indices, so a lost or corrupt chunk costs one chunk frame on
the retransmit wire — never the payload (asserted byte-for-byte in
``repro.agg.sim.run_chunked_lossy``).

Drain path (:meth:`AggServer.drain`): all pending payloads of one color
space q are decoded against the server's decode reference in ONE batched
Pallas launch (repro.kernels.ops.lattice_decode_batched), their §5
coordinate checksums verified vectorized, and the accepted senders' integer
lattice coordinates summed into the round accumulator.  Integer addition is
exact and commutative, so the accumulated sum — and therefore the round
mean — is bit-identical under any arrival order, any receive/drain
interleaving, and any drain batching.

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

Decode failures (checksum mismatch: the §5 detection event) are NACKed with
the next escalation level — RobustAgreement's r <- r^2 with the per-bucket
lattice granularity pinned, so a retried client's coordinates land on the
same lattice and stay summable; the NACK carries the per-bucket margins at
the directed level (v2).  When the color space is already at the 2^16
packing cap (or max_attempts is reached) the client is REJECTed and
excluded from the round.

Continuous-round intake (ISSUE 6): the server is no longer a lockstep batch
— :meth:`AggServer.seal` closes the round to NEW clients at cutover while
already-admitted clients keep full service (outstanding chunks, selective
retransmits, escalation retries — the overlapping drain), and ``max_pending``
bounds the pending store (staged payloads + open reassembly streams).  A
frame past the seal or the cap draws a non-terminal ``STATUS_RETRY`` naming
the round currently open for admission — never a verdict, so admission
timing can never flip an honest client to gave-up.
:meth:`AggServer.expire_client` lets the engine's straggler deadline drop an
unresolved client's state without a verdict, and :attr:`AggServer.unresolved`
is the drain condition the engine's round life-cycle machine watches.

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
from repro.agg.transport import frame as wire
from repro.agg.transport import session as S
from repro.core import error_detect as ED
from repro.core import lattice as L
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
    max_dist: float = 0.0        # max |decoded - ref|_inf over accepts
    dist_b: Optional[np.ndarray] = None    # (nb,) per-bucket max distance
    fails_b: Optional[np.ndarray] = None   # (nb,) per-bucket failure counts


def _reject(spec: wire.RoundSpec, client_id: int,
            round_id: "int | None" = None) -> wire.Response:
    """``round_id`` defaults to the server's round; spec-mismatch rejects
    echo the offending frame's round instead, so a REJECT provoked by a
    delayed previous-round frame is ignored by the same client's
    current-round protocol object (round_id filter) rather than read as a
    terminal verdict on the live round."""
    return wire.Response(status=wire.STATUS_REJECT,
                         round_id=spec.round_id if round_id is None
                         else round_id,
                         client_id=client_id, attempt_next=0, q_next=0,
                         y_next=0.0)


def _retry(round_id: int, client_id: int, attempt: int,
           open_round_id: int) -> wire.Response:
    """The non-terminal admission verdict: round sealed to new clients or
    pending store full.  Echoes the frame's round (so the sender's protocol
    object sees it) and names the round currently open for admission in
    ``q_next`` (0 = unknown) — the client re-sends after backoff or
    re-enrolls there.  NEVER terminal: ``gave_up`` cannot be provoked by
    timing, only by the client's own escalation exhausting (PR 5's
    invariant, extended to admission)."""
    return wire.Response(status=wire.STATUS_RETRY, round_id=round_id,
                         client_id=client_id, attempt_next=attempt,
                         q_next=open_round_id, y_next=0.0)


class _StreamFold:
    """Speculative per-stream fold for the streaming drain.

    One per open ``(client, attempt, payload_crc)`` stream identity: the
    residuals folded so far, on the device as a (padded,) int16 vector
    (|r| <= q/2 <= 2^15 at the q=2^16 packing cap, so int16 always fits),
    and how many coordinates the folded ranges cover.  Nothing here has
    touched the round accumulator — dropping the record IS the rollback."""
    __slots__ = ("r", "coords")

    def __init__(self, padded: int):
        self.r = jnp.zeros((padded,), jnp.int16)
        self.coords = 0


@partial(jax.jit, static_argnames=("q", "n"), donate_argnums=(0,))
def _fold_range_math(r: Array, words: Array, k0: Array, c0: Array, *,
                     q: int, n: int) -> Array:
    """Write the residuals of one validated word range into a stream record.

    r: (padded,) int16 record (donated); words: (nw,) uint32 the packed words
    covering coordinates ``[c0, c0 + n)``; k0: (padded,) int32 the round's
    decode-reference coordinates; c0: () int32.  The same residuals as
    :func:`repro.kernels.ops.lattice_residuals_range`, with the ``k0``
    window sliced inside the jit."""
    part = K.lattice_residuals(words, jax.lax.dynamic_slice(k0, (c0,), (n,)),
                               q=q)
    return jax.lax.dynamic_update_slice(r, part.astype(r.dtype), (c0,))


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
def _drain_math(words: Array, sides: Array, checks: Array, valid: Array,
                anchor: Array, u: Array, weights: Array, y_col: Array,
                m: Array, k0: Array, *, q: int, bucket: int):
    """Decode S payloads, verify checksums, sum accepted integer coords.

    words: (S, nw) uint32; sides: (S, nb) f32 sidecars; checks: (S,) uint32;
    valid: (S,) bool (False for the block-size padding rows the server adds
    so drain sizes hit a bounded set of compiled shapes); anchor/u/weights:
    (n,); y_col: (nb,) decode margins at this q; m: (S,) int32 n_summed of
    each payload (1 for an ordinary client); k0: (n,) int32 the round's
    decode-reference coordinates (:func:`repro.agg.rounds.decode_ref_coords`).

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


class AggServer:
    """One aggregation round's coordinator.

    ``anchor`` doubles as the decode reference and — in anchored rounds —
    the round anchor itself (validated against ``spec.anchor_digest``).
    """

    def __init__(self, spec: wire.RoundSpec, anchor,
                 max_pending: "int | None" = None,
                 streaming: "bool | None" = None):
        """``max_pending``: admission cap — the largest number of distinct
        un-drained clients allowed to hold buffered server state (pending
        payloads + open reassembly streams) at once.  A frame from a NEW
        client beyond the cap draws a non-terminal ``STATUS_RETRY``
        (backpressure), never a verdict; ``None`` = unbounded (the
        historical lockstep behavior).

        ``streaming``: enable the streaming drain for multi-chunk payloads
        (fold validated chunk ranges on arrival, commit at stream
        completion).  ``None`` (the default) resolves to ``spec.window >
        0`` — a windowed round streams, anything else keeps the historical
        seal-then-stage path bit-for-bit."""
        if np.shape(anchor) != (spec.d,):
            raise ValueError(
                f"anchor has shape {np.shape(anchor)}, spec.d={spec.d}")
        rounds.check_anchor(spec, anchor if spec.anchored else None)
        self.spec = spec
        self.max_pending = max_pending
        self._sealed = False
        self._next_round_id = 0     # admission hint for RETRY after seal
        self._admitted: set[int] = set()
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
        # the decode's reference coordinates (padded,) int32 — the lift
        # point tree tiers sum residuals about; (m-1)*k0 corrects their
        # combined payloads back to a per-client sum in _drain_math
        self._k0 = rounds.decode_ref_coords(
            spec, None if spec.anchored else anchor)
        self._anchor_raw = np.asarray(anchor, np.float32).copy()
        self._published: list[PublishedRound] = []
        self._pending: dict[int, wire.Payload] = {}
        self._pending_bytes = 0   # bodies staged for the batched drain
        self._folds: "dict[tuple, _StreamFold]" = {}
        self._streaming = ((spec.window > 0) if streaming is None
                           else bool(streaming)) and spec.mtu > 0
        if self._streaming:
            # the decode context per coordinate, for the streamed commit
            self._u_flat = self._u.reshape(-1)
            self._s_flat = jnp.repeat(self._sides, spec.cfg.bucket)
            self._offsets: "dict[int, Array]" = {}   # fold offsets on device
            self._rx = S.Reassembler(spec,
                                     on_range_validated=self._fold_range,
                                     on_stream_discarded=self._drop_stream)
        else:
            self._rx = S.Reassembler(spec)  # chunked-payload session layer
        self._accepted: set[int] = set()
        self._gave_up: set[int] = set()
        # per-client minimum live attempt (bumped by every NACK): a late
        # duplicate chunk of a NACKed attempt must not re-open a dead
        # reassembly stream it would then carry to the round's end
        self._attempt_floor: dict[int, int] = {}
        self._ksum = jnp.zeros((spec.nb, spec.cfg.bucket), jnp.int32)
        self._count = 0
        self._max_abs_k = 0
        # per-attempt per-bucket margin tuples for QUEUED/NACK responses
        # (attempts are bounded by max_attempts; don't rebuild per message)
        self._margins: dict[int, tuple] = {}
        # the round's accounting lives in an obs scope (registry counters
        # when metrics are enabled, a detached registry otherwise); the
        # RoundStats dataclass every caller reads is filled from it on
        # access.  Only the numpy per-bucket telemetry stays direct.
        self._obs = _obs.scope("agg_round", round=spec.round_id)
        self._stats = RoundStats(dist_b=np.zeros((spec.nb,), np.float32),
                                 fails_b=np.zeros((spec.nb,), np.float32))
        self._publish_traced = False

    @property
    def stats(self) -> RoundStats:
        """Per-round telemetry, materialized from the obs scope."""
        self._obs.fill(self._stats)
        return self._stats

    def _margin_tuple(self, attempt: int) -> tuple:
        t = self._margins.get(attempt)
        if t is None:
            t = tuple(float(v) for v in
                      wire.y_buckets_at_attempt(self.spec, attempt))
            self._margins[attempt] = t
        return t

    # ------------------------------------------------------------------ RX
    def receive(self, data: bytes) -> bytes:
        """Handle one arriving frame; returns the response bytes."""
        self._obs.inc("received")
        self._obs.inc("bytes_in", len(data))
        # the only bytes ever held before a CRC has vouched for them: this
        # one frame (<= header + MTU in a chunked round, whatever the d)
        self._obs.set_max("peak_unvalidated_bytes", len(data))
        h = None
        try:
            with _obs.span("agg.parse"):
                h, chunk = wire.decode_frame(data)
                wire.check_frame_against_spec(h, self.spec, len(chunk))
        except wire.WireError:
            if h is None:                   # the frame did not decode
                self._obs.inc("rejected_wire")
                return self._respond(_reject(self.spec, 0xFFFFFFFF))
            # a well-formed frame of another round or configuration
            self._obs.inc("rejected_spec")
            return self._respond(_reject(self.spec, h.client_id,
                                         round_id=h.round_id))
        if _obs.tracing_enabled():
            _obs.tracer().event("chunk",
                                parent=("client", h.round_id, h.client_id),
                                round=h.round_id, client=h.client_id,
                                chunk=h.chunk_index, n_chunks=h.n_chunks)
        if h.client_id in self._gave_up:
            return self._respond(_reject(self.spec, h.client_id))
        if h.client_id in self._accepted:
            # duplicate delivery of an already-accumulated client: ACK
            # idempotently, never double-count
            self._obs.inc("duplicates")
            return self._respond(self._ack(
                h.client_id, ack=h.n_chunks if self.spec.window else 0))
        if h.client_id not in self._admitted:
            # intake gate — BEFORE any buffered state is created for the
            # client, so a sealed or saturated round never opens a
            # reassembly stream it would have to carry
            if self._sealed:
                self._obs.inc("retried")
                return self._respond(_retry(h.round_id, h.client_id,
                                            h.attempt, self._next_round_id))
            if (self.max_pending is not None
                    and self.occupancy >= self.max_pending):
                self._obs.inc("retried")
                return self._respond(_retry(h.round_id, h.client_id,
                                            h.attempt, self.spec.round_id))
            self._admitted.add(h.client_id)
        if h.n_chunks == 1:
            p = wire.payload_from_body(h, chunk)
        else:
            if h.attempt < self._attempt_floor.get(h.client_id, 0):
                # stale chunk of an attempt this server already NACKed
                self._obs.inc("duplicates")
                return self._respond(self._queued(h, slim=True))
            with _obs.span("agg.reassemble"):
                event, p = self._rx.add(h, chunk)
            if event == S.REJECT:
                # the reassembled body failed its payload-CRC seal (a
                # forged chunk shared the stream's header): the stream is
                # dropped but the verdict is NOT terminal — direct a full
                # rebuild; a REJECT would flip the honest client to gave_up
                self._obs.inc("resends_sent")
                return self._respond(wire.Response(
                    status=wire.STATUS_RESEND,
                    round_id=self.spec.round_id, client_id=h.client_id,
                    attempt_next=h.attempt, q_next=h.q,
                    y_next=wire.y_at_attempt(self.spec, h.attempt),
                    missing=tuple(range(h.n_chunks)),
                    credit=self.spec.window))
            if p is None:                   # PROGRESS / DUPLICATE / STALE
                if event in (S.DUPLICATE, S.STALE):
                    self._obs.inc("duplicates")
                self._note_pending_store()
                # slim ack: mid-reassembly nobody consumes the per-bucket
                # margins or a missing list, so don't pay O(nb + n_chunks)
                # response bytes per chunk
                return self._respond(self._queued(h, slim=True))
            if p.streamed:
                # stream complete + payload-CRC sealed: verify and commit
                # the speculative fold NOW — no staged body, nothing for
                # the drain to carry; the commit is this client's drain
                with _obs.span("agg.commit", "drain",
                               parent=("round", self.spec.round_id),
                               round=self.spec.round_id,
                               client=h.client_id):
                    resp = self._finish_streamed(h, p)
                out = self._respond(resp)
                self._note_pending_store()
                return out
        try:
            # body-level spec check only — every header field was already
            # validated per frame by check_frame_against_spec
            wire.check_sides_against_spec(p, self.spec)
        except wire.HeaderMismatchError:
            self._obs.inc("rejected_spec")
            return self._respond(_reject(self.spec, p.client_id))
        prev = self._pending.get(p.client_id)
        if prev is not None and prev.attempt >= p.attempt:
            self._obs.inc("duplicates")
        else:
            if prev is not None:
                self._pending_bytes -= prev.words.nbytes + prev.sides.nbytes
            self._pending[p.client_id] = p
            self._pending_bytes += p.words.nbytes + p.sides.nbytes
            self._note_pending_store()
            self._obs.inc("queued")
            if _obs.tracing_enabled():
                # the payload's end-to-end CRC has vouched for the body and
                # it is staged for the drain: the client's seal point
                _obs.tracer().event(
                    "seal", parent=("client", h.round_id, p.client_id),
                    round=h.round_id, client=p.client_id, attempt=p.attempt)
        return self._respond(self._queued(h))

    def _queued(self, h: wire.FrameHeader,
                slim: bool = False) -> wire.Response:
        # no `missing` list here: only STATUS_RESEND consumes it, and
        # including it per chunk ack would cost O(n_chunks^2) per client
        # windowed rounds piggyback flow control on every response: the
        # cumulative contiguous-chunk ack + the static credit grant, so
        # RESEND recovery and window advance share one response path
        return wire.Response(
            status=wire.STATUS_QUEUED, round_id=self.spec.round_id,
            client_id=h.client_id, attempt_next=h.attempt, q_next=h.q,
            y_next=wire.y_at_attempt(self.spec, h.attempt),
            y_buckets=() if slim else self._margin_tuple(h.attempt),
            ack=self._rx.high_water(h.client_id) if self.spec.window else 0,
            credit=self.spec.window)

    def _ack(self, client_id: int, ack: int = 0) -> wire.Response:
        return wire.Response(status=wire.STATUS_ACK,
                             round_id=self.spec.round_id,
                             client_id=client_id, attempt_next=0, q_next=0,
                             y_next=0.0, ack=ack, credit=self.spec.window)

    def _respond(self, r: wire.Response) -> bytes:
        with _obs.span("agg.respond"):
            return wire.encode_response(r)

    # -------------------------------------------------------- STREAMING RX
    def _note_pending_store(self) -> None:
        """The pending-store byte gauge: staged drain bodies + everything
        the reassembly layer is holding (carry, held out-of-order chunks,
        sides, sealed-mode buffers).  The streaming drain's whole point is
        keeping this far below one body per pending client."""
        self._obs.set_max("peak_pending_store_bytes",
                          self._pending_bytes + self._rx.stats.buffer_bytes)

    def _fold_range(self, h: wire.FrameHeader, word_start: int,
                    words: np.ndarray) -> None:
        """``on_range_validated``: residual-fold one contiguous validated
        word range into the stream's speculative record on the device —
        one asynchronous dispatch, nothing read back; the session frees the
        chunk bytes as soon as this returns."""
        with _obs.span("agg.fold"):
            key = (h.client_id, h.attempt, h.payload_crc)
            rec = self._folds.get(key)
            if rec is None:
                rec = self._folds[key] = _StreamFold(self.spec.padded)
            per = 32 // L.bits_for_q(h.q)
            c0 = word_start * per
            if c0 >= self.spec.padded:
                raise ValueError(f"word_start {word_start} starts at "
                                 f"coordinate {c0}, past the "
                                 f"{self.spec.padded}-coordinate vector")
            n = min(words.shape[-1] * per, self.spec.padded - c0)
            c0_dev = self._offsets.get(c0)
            if c0_dev is None:
                # a spec's chunks fold at the same few offsets: keep each on
                # the device, or every range pays a scalar's own copy there
                c0_dev = self._offsets[c0] = jnp.asarray(c0, jnp.int32)
            with _obs.span("agg.fold.residuals"):
                # the words go to the device as the jit's own argument (one
                # copy, cheaper than a jnp.asarray first); nothing is read
                # back
                rec.r = _fold_range_math(rec.r, words, self._k0, c0_dev,
                                         q=h.q, n=n)
            rec.coords += n

    def _drop_stream(self, h: wire.FrameHeader) -> None:
        """``on_stream_discarded``: the rollback.  The record never touched
        the round accumulator, so dropping it IS the undo (seal failure,
        escalation reset, eviction, expiry)."""
        self._folds.pop((h.client_id, h.attempt, h.payload_crc), None)

    def _finish_streamed(self, h: wire.FrameHeader,
                         p: wire.Payload) -> wire.Response:
        """A stream completed and its payload-CRC seal held: verify the
        fold's §5 checksum and commit — the streaming path's per-client
        drain, minus the body that no longer exists."""
        rec = self._folds.pop((h.client_id, h.attempt, h.payload_crc), None)
        try:
            wire.check_sides_against_spec(p, self.spec)
        except wire.HeaderMismatchError:
            self._obs.inc("rejected_spec")
            return _reject(self.spec, p.client_id)
        if rec is None or rec.coords != self.spec.padded:
            # a fold record that never materialized (stream evicted and
            # rebuilt mid-flight): direct a full rebuild, non-terminal
            self._obs.inc("resends_sent")
            return wire.Response(
                status=wire.STATUS_RESEND, round_id=self.spec.round_id,
                client_id=h.client_id, attempt_next=h.attempt, q_next=h.q,
                y_next=wire.y_at_attempt(self.spec, h.attempt),
                missing=tuple(range(h.n_chunks)), credit=self.spec.window)
        if _obs.tracing_enabled():
            # the completed stream's checksum-verified fold is the
            # streaming path's seal point
            _obs.tracer().event(
                "seal", parent=("client", h.round_id, h.client_id),
                round=h.round_id, client=h.client_id, attempt=h.attempt)
        m = h.n_summed
        verdict, ksum = _commit_math(
            rec.r, self._ksum, self._k0, self._weights, self._u_flat,
            self._s_flat, self._ref_flat, m=m, bucket=self.spec.cfg.bucket)
        # the stream's one blocking readback: the verdict's small inputs
        verdict = np.asarray(verdict)
        self._obs.inc("fold_syncs")
        dist_b = verdict[2:].view(np.float32)
        if int(verdict[0]) != (h.check & 0xFFFFFFFF):
            return self._nack_streamed(h, dist_b)
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

    def _nack_streamed(self, h: wire.FrameHeader,
                       dist_b: np.ndarray) -> wire.Response:
        """§5 checksum mismatch on a completed stream: the same escalation
        verdict the batched drain would have produced."""
        self._obs.inc("decode_failures")
        if h.n_summed == 1:
            y_col = np.asarray(wire.y_buckets_at_attempt(self.spec,
                                                         h.attempt))
            self._stats.fails_b = self._stats.fails_b + \
                (dist_b > 1.5 * y_col).astype(np.float32)
        nxt = h.attempt + 1
        if h.q >= wire.Q_CAP or nxt >= self.spec.max_attempts:
            self._gave_up.add(h.client_id)
            self._obs.inc("gave_up")
            return _reject(self.spec, h.client_id)
        self._obs.inc("nacks_sent")
        self._attempt_floor[h.client_id] = nxt
        return wire.Response(
            status=wire.STATUS_NACK, round_id=self.spec.round_id,
            client_id=h.client_id, attempt_next=nxt,
            q_next=wire.q_at_attempt(self.spec.cfg.q, nxt),
            y_next=wire.y_at_attempt(self.spec, nxt),
            y_buckets=self._margin_tuple(nxt), credit=self.spec.window)

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

    # ----------------------------------------------------------- LIFECYCLE
    def seal(self, next_round_id: int = 0) -> None:
        """Stop admitting NEW clients (round cutover).

        Already-admitted clients keep full service — outstanding chunks,
        selective retransmits and escalation retries all still land (the
        overlapping drain); a frame from anyone else draws a non-terminal
        ``STATUS_RETRY`` pointing at ``next_round_id`` (the round now open
        for admission).  Idempotent."""
        self._sealed = True
        self._next_round_id = next_round_id

    @property
    def sealed(self) -> bool:
        return self._sealed

    @property
    def admitted_count(self) -> int:
        """Distinct clients admitted into the round (quorum input)."""
        return len(self._admitted)

    @property
    def unresolved(self) -> frozenset:
        """Admitted clients with no outcome yet (not accepted, not
        escalation-exhausted) — empty means the round is fully drained."""
        return frozenset(self._admitted - self._accepted - self._gave_up)

    @property
    def occupancy(self) -> int:
        """Distinct clients currently holding buffered server state (the
        bounded pending store: staged payloads + open reassembly streams).
        Accepted clients have been folded into the integer accumulator and
        hold nothing."""
        return len(set(self._pending) | self._rx.open_clients())

    def expire_client(self, client_id: int) -> None:
        """Drop a straggler's state without a verdict (engine deadline).

        The client's pending payload / reassembly streams are discarded and
        its admission slot freed, so the round can drain without it.  No
        response is generated — expiry is not a protocol outcome, and the
        client is free to enroll in a later round."""
        if (client_id not in self._admitted or client_id in self._accepted
                or client_id in self._gave_up):
            return                  # only unresolved stragglers expire
        prev = self._pending.pop(client_id, None)
        if prev is not None:
            self._pending_bytes -= prev.words.nbytes + prev.sides.nbytes
        self._rx.discard(client_id)   # fires the stream-fold rollback too
        self._admitted.discard(client_id)
        self._obs.inc("expired")
        if _obs.tracing_enabled():
            _obs.tracer().event("expire",
                                parent=("round", self.spec.round_id),
                                round=self.spec.round_id, client=client_id)

    # --------------------------------------------------------------- DRAIN
    @property
    def pending(self) -> int:
        return len(self._pending)

    @property
    def transport_stats(self) -> S.ReassemblyStats:
        """The session layer's reassembly telemetry (chunked rounds)."""
        return self._rx.stats

    @property
    def accepted_clients(self) -> frozenset:
        return frozenset(self._accepted)

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
            for p in self._pending.values():
                by_q.setdefault(p.q, []).append(p)
            self._pending.clear()
            self._pending_bytes = 0
            responses = []
            for q, plist in sorted(by_q.items()):
                responses += self._drain_q(q, plist)
            region.note(accepted=len(self._accepted))
        return responses + self._resend_requests()

    def _drain_q(self, q: int, plist: "list[wire.Payload]") -> "list[bytes]":
        """One batched decode of the pending payloads of color space ``q``;
        returns their ACK/NACK/REJECT responses."""
        plist.sort(key=lambda p: p.client_id)
        # pad the sender axis to the kernel's block size so drain sizes map
        # onto a bounded set of compiled shapes (padding rows carry
        # valid=False and never enter the sum)
        S = len(plist)
        pad = (-S) % DEFAULT_BLOCK_SENDERS
        attempt0 = plist[0].attempt
        with _obs.span("agg.stage"):
            words = jnp.asarray(np.pad(
                np.stack([p.words for p in plist]), ((0, pad), (0, 0))))
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
                _drain_math(words, sides, checks, valid, self._ref_flat,
                            self._u.reshape(-1), self._weights, y_col, m,
                            self._k0, q=q, bucket=self.spec.cfg.bucket)
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
                continue
            self._obs.inc("decode_failures")
            nxt = p.attempt + 1
            if p.q >= wire.Q_CAP or nxt >= self.spec.max_attempts:
                self._gave_up.add(p.client_id)
                self._rx.discard(p.client_id)
                self._obs.inc("gave_up")
                responses.append(
                    self._respond(_reject(self.spec, p.client_id)))
                continue
            self._obs.inc("nacks_sent")
            self._attempt_floor[p.client_id] = nxt
            responses.append(self._respond(wire.Response(
                status=wire.STATUS_NACK, round_id=self.spec.round_id,
                client_id=p.client_id, attempt_next=nxt,
                q_next=wire.q_at_attempt(self.spec.cfg.q, nxt),
                y_next=wire.y_at_attempt(self.spec, nxt),
                y_buckets=self._margin_tuple(nxt),
                credit=self.spec.window)))
        return responses

    def _resend_for(self, cid: int, attempt: int, missing: tuple) -> bytes:
        self._obs.inc("resends_sent")
        if _obs.metrics_enabled():
            _obs.counter("chunk_retransmits",
                         round=self.spec.round_id).inc(len(missing))
        return self._respond(wire.Response(
            status=wire.STATUS_RESEND, round_id=self.spec.round_id,
            client_id=cid, attempt_next=attempt,
            q_next=wire.q_at_attempt(self.spec.cfg.q, attempt),
            y_next=wire.y_at_attempt(self.spec, attempt),
            y_buckets=self._margin_tuple(attempt), missing=missing,
            ack=self._rx.high_water(cid) if self.spec.window else 0,
            credit=self.spec.window))

    def _resend_requests(self) -> list[bytes]:
        """Chunk-level NACKs for every still-incomplete reassembly: each
        names exactly the missing chunk indices, so the retransmit wire
        cost is per lost chunk, never per payload."""
        return [self._resend_for(cid, attempt, missing)
                for cid, (attempt, missing) in self._rx.incomplete().items()]

    def resend_request(self, client_id: int) -> "Optional[bytes]":
        """A targeted RESEND for ONE client's incomplete reassembly — the
        engine's straggler deadline taps the RESEND budget per client
        without re-NACKing everyone else mid-drain.  None when the client
        has no open incomplete stream (a staged payload just needs a
        drain; a NACKed-and-silent client has nothing to retransmit)."""
        info = self._rx.incomplete().get(client_id)
        if info is None:
            return None
        return self._resend_for(client_id, *info)

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