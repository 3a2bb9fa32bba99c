"""Client intake: the protocol every aggregation node speaks to its clients.

:class:`Intake` is the shared half of the flat server
(:class:`repro.agg.server.AggServer`) and the tree tier
(:class:`repro.agg.tree.TierAggregator`).  Each node adds only what it does
with a verified client: the server decodes and sums, the tier sums residuals
without decoding.

Arrival path (:meth:`Intake.receive`): parse/validate one transport frame
(framing errors and spec mismatches are counted and REJECTed — including
truncated, corrupt, version-mismatched, anchor-digest-mismatched and
MTU-geometry-violating frames), dedupe by client id, and route it by its
chunk coordinates: a single-frame payload is buffered directly, a chunk of a
larger payload goes through the transport session layer
(:class:`repro.agg.transport.session.Reassembler`) — out-of-order and
duplicate tolerant, committing each validated chunk in place so the
transport never stages more than one frame (header + MTU) of unvalidated
bytes, independent of d.  Either way the node buffers the *packed words* —
the 8x-compressed form — until a drain; a completed reassembly hands the
drain the same zero-copy Payload view a single frame would have.  As a
payload enters the pending store the node's :meth:`Intake._stage_pending`
hook runs: the flat server starts the words' copy to the device there, a
tier (which drains on the host) does nothing.

**Streaming drain** (on when ``RoundSpec.window > 0``): the
seal-then-stage path above is replaced for multi-chunk payloads.  The
session emits each stream's validated contiguous word prefix range by range
(``on_range_validated``) and frees the chunk bytes; the node residual-folds
every range on arrival (the residuals about the round's decode-reference
coordinates ``k0``, so ``k0 + r`` is bit-for-bit what the batched decode
would have produced) into a *speculative* per-stream record keyed by
``(client, attempt, payload_crc)``: an int16 residual vector that lives on
the device.  A range costs one asynchronous dispatch and no readback.
Nothing touches the node's sum until the stream completes AND its
payload-CRC seal holds; then the node's ``_commit_stream`` verifies the
record's §5 checksum and commits it.  So "rollback" on a seal failure,
escalation reset, eviction or expiry is simply dropping the record
(``on_stream_discarded``), and the published mean stays bit-identical to
the sealed drain under any arrival order, loss, duplication or escalation.
``peak_pending_store_bytes`` gauges what the sealed path buffers: staged
bodies + reassembly bytes — with the window holding senders near-in-order
it stays far below one body per pending client.  Single-chunk payloads keep
the batched path (they never had a body-sized backlog).

Chunked rounds add one response status: a drain that finds a client's
reassembly still incomplete emits ``STATUS_RESEND`` naming exactly the
missing chunk indices, so a lost or corrupt chunk costs one chunk frame on
the retransmit wire — never the payload (asserted byte-for-byte in
``repro.agg.sim.run_chunked_lossy``).

Decode failures (checksum mismatch: the §5 detection event) are NACKed with
the next escalation level — RobustAgreement's r <- r^2 with the per-bucket
lattice granularity pinned, so a retried client's coordinates land on the
same lattice and stay summable; the NACK carries the per-bucket margins at
the directed level.  When the color space is already at the 2^16 packing
cap (or max_attempts is reached) the client is REJECTed and excluded from
the round (:meth:`Intake._escalate`).

Continuous-round intake: :meth:`Intake.seal` closes the round to NEW
clients at cutover while already-admitted clients keep full service
(outstanding chunks, selective retransmits, escalation retries — the
overlapping drain), and ``max_pending`` bounds the pending store (staged
payloads + open reassembly streams).  A frame past the seal or the cap draws
a non-terminal ``STATUS_RETRY`` naming the round currently open for
admission — never a verdict, so admission timing can never flip an honest
client to gave-up.  :meth:`Intake.expire_client` lets the engine's
straggler deadline drop an unresolved client's state without a verdict, and
:attr:`Intake.unresolved` is the drain condition the engine's round
life-cycle machine watches.
"""
from __future__ import annotations

from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

import repro.obs as _obs
from repro.agg import rounds
from repro.agg.transport import frame as wire
from repro.agg.transport import session as S
from repro.core import lattice as L
from repro.kernels import ops as K

Array = jax.Array


def _reject(spec: wire.RoundSpec, client_id: int,
            round_id: "int | None" = None) -> wire.Response:
    """``round_id`` defaults to the node's round; spec-mismatch rejects
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
    timing, only by the client's own escalation exhausting."""
    return wire.Response(status=wire.STATUS_RETRY, round_id=round_id,
                         client_id=client_id, attempt_next=attempt,
                         q_next=open_round_id, y_next=0.0)


class _StreamFold:
    """Speculative per-stream fold for the streaming drain.

    One per open ``(client, attempt, payload_crc)`` stream identity: the
    residuals folded so far, on the device as a (padded,) int16 vector
    (|r| <= q/2 <= 2^15 at the q=2^16 packing cap, so int16 always fits),
    and how many coordinates the folded ranges cover.  Nothing here has
    touched the node's sum — dropping the record IS the rollback."""
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
    decode-reference coordinates; c0: () int32.  The residuals are
    :func:`repro.kernels.ops.lattice_residuals` of the range, with the ``k0``
    window sliced inside the jit."""
    part = K.lattice_residuals(words, jax.lax.dynamic_slice(k0, (c0,), (n,)),
                               q=q)
    return jax.lax.dynamic_update_slice(r, part.astype(r.dtype), (c0,))


class Intake:
    """Admission, reassembly, the streaming record and the responses of one
    round's aggregation node.

    ``anchor`` is the round's out-of-band reference (digest-validated for
    anchored rounds).  A node supplies ``obs`` (its instrument scope) and
    ``stats`` (the dataclass :attr:`stats` fills from it), and implements
    :meth:`_commit_stream` and its own drain of the pending store."""

    def __init__(self, spec: wire.RoundSpec, anchor,
                 max_pending: "int | None", streaming: "bool | None",
                 obs, stats):
        """``max_pending``: admission cap — the largest number of distinct
        un-drained clients allowed to hold buffered state (pending payloads
        + open reassembly streams) at once.  A frame from a NEW client
        beyond the cap draws a non-terminal ``STATUS_RETRY``
        (backpressure), never a verdict; ``None`` = unbounded.

        ``streaming``: enable the streaming drain for multi-chunk payloads
        (fold validated chunk ranges on arrival, commit at stream
        completion).  ``None`` resolves to ``spec.window > 0`` — a windowed
        round streams, anything else keeps the seal-then-stage path."""
        if np.shape(anchor) != (spec.d,):
            raise ValueError(
                f"anchor has shape {np.shape(anchor)}, spec.d={spec.d}")
        rounds.check_anchor(spec, anchor if spec.anchored else None)
        self.spec = spec
        self.max_pending = max_pending
        self._sealed = False
        self._next_round_id = 0     # admission hint for RETRY after seal
        self._admitted: set[int] = set()
        self._accepted: set[int] = set()
        self._gave_up: set[int] = set()
        # per-client minimum live attempt (bumped by every NACK): a late
        # duplicate chunk of a NACKed attempt must not re-open a dead
        # reassembly stream it would then carry to the round's end
        self._attempt_floor: dict[int, int] = {}
        # the decode's reference coordinates (padded,) int32: the lift point
        # of every residual (the root's decode output is k0 + r)
        self._k0 = rounds.decode_ref_coords(
            spec, None if spec.anchored else anchor)
        self._pending: dict[int, wire.Payload] = {}
        self._pending_bytes = 0   # bodies staged for the batched drain
        self._folds: "dict[tuple, _StreamFold]" = {}
        self._offsets: "dict[int, Array]" = {}   # fold offsets on device
        self._streaming = ((spec.window > 0) if streaming is None
                           else bool(streaming)) and spec.mtu > 0
        self._rx = S.Reassembler(
            spec,
            on_range_validated=self._fold_range if self._streaming else None,
            on_stream_discarded=self._drop_stream if self._streaming
            else None)
        # per-attempt per-bucket margin tuples for QUEUED/NACK responses
        # (attempts are bounded by max_attempts; don't rebuild per message)
        self._margins: dict[int, tuple] = {}
        # the node's accounting lives in an obs scope (registry counters
        # when metrics are enabled, a detached registry otherwise); the
        # stats dataclass every caller reads is filled from it on access
        self._obs = obs
        self._stats = stats

    @property
    def stats(self):
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
                # stale chunk of an attempt this node already NACKed
                self._obs.inc("duplicates")
                return self._respond(self._queued(h, slim=True))
            with _obs.span("agg.reassemble"):
                event, p = self._rx.add(h, chunk)
            if event == S.REJECT:
                # the reassembled body failed its payload-CRC seal (a
                # forged chunk shared the stream's header): the stream is
                # dropped but the verdict is NOT terminal — direct a full
                # rebuild; a REJECT would flip the honest client to gave_up
                return self._respond(self._rebuild(h))
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
            self._stage_pending(p)
            self._note_pending_store()
            self._obs.inc("queued")
            if _obs.tracing_enabled():
                # the payload's end-to-end CRC has vouched for the body and
                # it is staged for the drain: the client's seal point
                _obs.tracer().event(
                    "seal", parent=("client", h.round_id, p.client_id),
                    round=h.round_id, client=p.client_id, attempt=p.attempt)
        return self._respond(self._queued(h))

    # ----------------------------------------------------------- RESPONSES
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

    def _rebuild(self, h: wire.FrameHeader) -> wire.Response:
        """A non-terminal RESEND of every chunk: the stream is gone (its
        seal failed, or its fold record never materialized) and the client
        rebuilds it from scratch."""
        self._obs.inc("resends_sent")
        return wire.Response(
            status=wire.STATUS_RESEND, round_id=self.spec.round_id,
            client_id=h.client_id, attempt_next=h.attempt, q_next=h.q,
            y_next=wire.y_at_attempt(self.spec, h.attempt),
            missing=tuple(range(h.n_chunks)), credit=self.spec.window)

    def _escalate(self, client_id: int, attempt: int,
                  q: int) -> wire.Response:
        """The verdict on a §5 checksum mismatch: NACK to the next attempt
        (its floor bumped, so stale chunks of this one cannot reopen a
        stream), or a terminal REJECT that discards the client's session
        once the color space is at the packing cap or max_attempts is
        reached."""
        self._obs.inc("decode_failures")
        nxt = attempt + 1
        if q >= wire.Q_CAP or nxt >= self.spec.max_attempts:
            self._gave_up.add(client_id)
            self._rx.discard(client_id)
            self._obs.inc("gave_up")
            return _reject(self.spec, client_id)
        self._obs.inc("nacks_sent")
        self._attempt_floor[client_id] = nxt
        return wire.Response(
            status=wire.STATUS_NACK, round_id=self.spec.round_id,
            client_id=client_id, attempt_next=nxt,
            q_next=wire.q_at_attempt(self.spec.cfg.q, nxt),
            y_next=wire.y_at_attempt(self.spec, nxt),
            y_buckets=self._margin_tuple(nxt), credit=self.spec.window)

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
        the node's sum, so dropping it IS the undo (seal failure,
        escalation reset, eviction, expiry)."""
        self._folds.pop((h.client_id, h.attempt, h.payload_crc), None)

    def _finish_streamed(self, h: wire.FrameHeader,
                         p: wire.Payload) -> wire.Response:
        """A stream completed and its payload-CRC seal held: check its
        sides and its record, then hand the record to the node's commit —
        the streaming path's per-client drain, minus the body that no
        longer exists."""
        rec = self._folds.pop((h.client_id, h.attempt, h.payload_crc), None)
        try:
            wire.check_sides_against_spec(p, self.spec)
        except wire.HeaderMismatchError:
            self._obs.inc("rejected_spec")
            return _reject(self.spec, p.client_id)
        if rec is None or rec.coords != self.spec.padded:
            # a fold record that never materialized (stream evicted and
            # rebuilt mid-flight): direct a full rebuild, non-terminal
            return self._rebuild(h)
        if _obs.tracing_enabled():
            # the completed stream's fold is the streaming path's seal point
            _obs.tracer().event(
                "seal", parent=("client", h.round_id, h.client_id),
                round=h.round_id, client=h.client_id, attempt=h.attempt)
        return self._commit_stream(h, p, rec)

    def _commit_stream(self, h: wire.FrameHeader, p: wire.Payload,
                       rec: _StreamFold) -> wire.Response:
        """Verify a complete, sealed stream's record and commit it into the
        node's sum; returns the client's verdict."""
        raise NotImplementedError

    def _stage_pending(self, p: wire.Payload) -> None:
        """Called as ``p`` enters the pending store, first or as a higher
        attempt replacing the client's pending payload: a node that drains
        on the device starts moving the words there now.  Nothing here; a
        node that drains on the host stages nothing."""

    def _take_pending(self) -> "list[wire.Payload]":
        """Empty the pending store for a drain, in client-id order."""
        staged = sorted(self._pending.values(), key=lambda p: p.client_id)
        self._pending.clear()
        self._pending_bytes = 0
        return staged

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
        """Distinct clients currently holding buffered state (the bounded
        pending store: staged payloads + open reassembly streams).
        Accepted clients have been folded into the sum and hold nothing."""
        return len(set(self._pending) | self._rx.open_clients())

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
