"""Hierarchical sum-without-decode aggregation tree (ISSUE 7 tentpole).

Topology: clients -> edge tiers -> (regional tiers ->) root, every hop the
ordinary v4 transport (chunking, selective retransmit, escalation — the
identical stack a flat client/server pair uses):

    client 0 ─┐
    client 1 ─┼─> TierAggregator ─┐
    client 2 ─┘        (edge)     │
                                  ├─> TierAggregator ──> root AggServer
    client 3 ─┐        (edge)     │      (regional)      (ONE batched
    client 4 ─┼─> TierAggregator ─┘                       Pallas decode
    client 5 ─┘                                           per color space)

A :class:`TierAggregator` speaks the flat server's client protocol — it is
the same :class:`repro.agg.intake.Intake`: frame validation, admission,
reassembly, the streaming fold on the device, the escalation verdict and
every response — and **sums accepted payloads' packed integer coordinates
without ever decoding**:

* The round's decode-reference coordinates ``k0 = round(ref/s - u)``
  (:func:`repro.agg.rounds.decode_ref_coords`) are bit-identical to the
  ``k_a`` inside the root's batched proximity decode.  Each accepted child
  payload's colors lift to the residual ``r_i = centered_mod(c_i - k0, q)``
  (:func:`repro.kernels.ops.lattice_residuals` — integer-only, deliberately
  NOT a decode dispatch), so ``k0 + r_i`` IS the root's decode output for
  that payload, obtained in pure int math.
* The §5 checksum is verified per child in uint32 arithmetic:
  ``h(k0 + r_i) == check_i``.  A mismatch draws the flat server's own
  escalation verdict (q <- q^2, terminal REJECT at the cap), so the tier's
  accepted set equals the flat server's for the same traffic.
* Accepted residuals fold in place: ``R += r_i`` (int64 headroom),
  ``m += n_summed_i`` (children may themselves be tiers).  Admission is
  saturation-checked: a child whose fold would push ``max|R|`` past the
  coordinate range ``q_max/2`` implied by the escalation cap is REJECTed
  (counted in :attr:`TierStats.saturated`) instead of silently wrapping.
* Upstream, the tier is an ordinary client of the next tier: it forwards
  ONE combined payload ``K' = k0 + R`` packed as mod-q' colors at the
  smallest escalation attempt whose color space holds ``R``, with checksum
  ``h(K')`` and the additive header field ``n_summed = m``; retransmits
  reuse the chunk layer's cached frames and ``STATUS_RESEND`` selection,
  NACKs escalate by repacking the SAME coordinates at the next q.

The root corrects each combined payload by ``(m-1) * k0`` inside its
drain (see ``_drain_math`` in :mod:`repro.agg.server`):
``K' + (m-1)*k0 = sum_i (k0 + r_i)`` — exactly the integer sum the m
clients would have contributed individually, so the tree-published mean is
bit-identical to a flat drain over the same accepted clients, and the root
still performs exactly one batched Pallas decode per color space.

:class:`AggTree` wires tiers into the fanout^j topology behind the
:class:`repro.agg.api.AggNode` protocol: a driver cannot tell a tree from
a flat server — ``ingest_frame`` routes client frames to edge tiers,
``tick`` pumps the internal tier<->parent exchanges until quiescent, and
``published()`` reports the root's outcome with the accepted set mapped
back to real client ids.
"""
from __future__ import annotations

import dataclasses
from typing import Callable, Optional

import jax.numpy as jnp
import numpy as np

import repro.obs as _obs
from repro.agg import rounds
from repro.agg.api import PublishedRound
from repro.agg.intake import Intake, _reject, _StreamFold
from repro.agg.server import AggServer
from repro.agg.transport import chunks as C
from repro.agg.transport import frame as wire
from repro.kernels import ops as K

# tier node ids live far above any realistic client id so the two can share
# the transport's u32 client_id field without collisions; layer index and
# position are recoverable from the id for debugging
TIER_ID_BASE = 0xF0000000

# upper bound on tick-internal message exchange iterations (a persistent
# loss hook could otherwise ping-pong RESENDs forever within one tick)
_MAX_PUMP = 64

# a sealed-and-forwarded tier with no verdict after this many consecutive
# ticks re-sends its full upstream frame sequence (recovers total loss of
# the combined payload, where no reassembly exists upstream to RESEND)
_UP_RESEND_TICKS = 2


@dataclasses.dataclass
class TierStats:
    """One tier's child-side + upstream telemetry."""
    received: int = 0
    queued: int = 0
    accepted: int = 0            # child payloads folded into R
    clients_summed: int = 0      # sum of folded n_summed (== forwarded m)
    duplicates: int = 0
    rejected_wire: int = 0
    rejected_spec: int = 0
    decode_failures: int = 0     # §5 checksum mismatches (integer-verified)
    nacks_sent: int = 0
    resends_sent: int = 0
    retried: int = 0
    saturated: int = 0           # children REJECTed by the overflow guard
    gave_up: int = 0
    expired: int = 0
    drains: int = 0
    bytes_in: int = 0
    peak_unvalidated_bytes: int = 0   # largest frame staged before its CRC
    peak_pending_store_bytes: int = 0  # staged bodies + reassembly bytes
    fold_syncs: int = 0          # streamed records read back (one per stream)
    up_frames_sent: int = 0      # upstream chunk frames (incl. retransmits)
    up_escalations: int = 0      # upstream NACKs honored (repack at next q)
    up_resends: int = 0          # upstream RESEND/timer retransmissions


class TierAggregator(Intake):
    """One aggregation tier: a server to its children, a client upstream.

    Implements the :class:`repro.agg.api.AggNode` protocol.  ``anchor`` is
    the same out-of-band reference the root holds (digest-validated for
    anchored rounds); ``node_id`` is this tier's client id on the upstream
    wire.  The child side is the flat server's :class:`Intake`, streaming
    fold included: a windowed round folds each child stream's validated
    word ranges on the device as they land, and commits into ``R`` only at
    stream completion, after the §5 checksum and the saturation guard.
    """

    def __init__(self, spec: wire.RoundSpec, anchor, node_id: int,
                 max_pending: "int | None" = None):
        super().__init__(
            spec, anchor, max_pending, None,
            obs=_obs.scope("agg_tier", round=spec.round_id, node=node_id),
            stats=TierStats())
        self.node_id = node_id
        # the integer-space lift reference on the host: bit-identical to the
        # k_a inside the root's batched decode (anchored and unanchored)
        self._k0_host = np.asarray(self._k0, np.int64)
        self._weights = np.asarray(rounds.checksum_weights(spec), np.uint32)
        self._sides_np = spec.sides_np()
        # escalation headroom: the widest color space any attempt may use;
        # |R| must stay inside its centered range or the repacked colors
        # would alias and the root's decode would silently wrap
        self._q_max = wire.q_at_attempt(spec.cfg.q, spec.max_attempts - 1)
        # ---- the sum-without-decode accumulator ----
        self._R = np.zeros((spec.padded,), np.int64)
        self._m = 0
        # ---- upstream (client-of-the-next-tier) state ----
        self._up_attempt: Optional[int] = None
        self._up_frames: "dict[int, list[bytes]]" = {}
        self._up_sent = False
        self._up_acked = False
        self._up_gave_up = False
        self._up_idle_ticks = 0
        self.retry_round: Optional[int] = None

    @property
    def tier_index(self) -> int:
        """(layer, position) packed in the node id — for labels/debug."""
        return self.node_id & ~TIER_ID_BASE

    # ------------------------------------------------------------ AggNode
    def ingest_frame(self, data: bytes, now: float = 0.0) -> "list[bytes]":
        """One transport message in: a child's frame (returns its response)
        or an upstream response (returns the frames to send next)."""
        if data[:4] == wire.MAGIC_RESPONSE:
            return self.handle_upstream(data)
        return [self.receive(data)]

    def tick(self, now: float = 0.0) -> "list[bytes]":
        """Fold staged children, chase missing chunks, forward upstream."""
        out = self.drain_children()
        out.extend(self._upstream_tick())
        return out

    def published(self) -> "list[PublishedRound]":
        """Tiers never publish — the root owns the round outcome."""
        return []

    # ---------------------------------------------------------- CHILD SIDE
    def drain_children(self) -> "list[bytes]":
        """Verify + fold every staged child payload; returns verdicts.

        Per payload, residual-lift the packed colors about ``k0``
        (integer-only) and hand them to :meth:`_verify_and_fold`.  No decode
        dispatch is issued — asserted via ``ops.DISPATCH_COUNTS`` in the
        tests.
        """
        if not self._pending:
            return self._resend_requests()
        self._obs.inc("drains")
        fold_sp = _obs.tracer().begin(
            "fold", parent=("round", self.spec.round_id),
            round=self.spec.round_id, tier=self.node_id,
            payloads=len(self._pending)) if _obs.tracing_enabled() else None
        responses = []
        for p in self._take_pending():
            r = np.asarray(K.lattice_residuals(jnp.asarray(p.words),
                                               self._k0, q=p.q), np.int64)
            responses.append(self._respond(self._verify_and_fold(p, r)))
        if fold_sp is not None:
            _obs.tracer().end(fold_sp, folded=self._m)
        return responses + self._resend_requests()

    def _commit_stream(self, h: wire.FrameHeader, p: wire.Payload,
                       rec: _StreamFold) -> wire.Response:
        """A child stream completed and sealed: read its record back once
        and verify and fold it like a staged payload."""
        r = np.asarray(rec.r, np.int64)
        self._obs.inc("fold_syncs")
        return self._verify_and_fold(p, r, ack=h.n_chunks)

    def _verify_and_fold(self, p: wire.Payload, r: np.ndarray,
                         ack: int = 0) -> wire.Response:
        """The sum-without-decode core for one child whose residuals about
        ``k0`` are ``r`` (int64): verify the §5 checksum over ``k0 + r`` in
        uint32 math, saturation-check the fold against the escalation cap's
        coordinate range, and add the residuals into the int64
        accumulator.  Returns the child's verdict."""
        k_hat = (self._k0_host + r).astype(np.int32)
        chk = int(np.sum(k_hat.view(np.uint32) * self._weights,
                         dtype=np.uint32))
        if chk != (p.check & 0xFFFFFFFF):
            return self._escalate(p.client_id, p.attempt, p.q)
        cand = self._R + r
        half = self._q_max // 2
        if cand.max() >= half or cand.min() < -half:
            # folding this child would push the combined coordinates
            # outside the widest escalation attempt's centered range — the
            # repacked colors would alias.  Terminal for the child at THIS
            # tier (it may enroll flat in a later round).
            self._obs.inc("saturated")
            self._obs.inc("gave_up")
            self._gave_up.add(p.client_id)
            self._rx.discard(p.client_id)
            if _obs.tracing_enabled():
                _obs.tracer().event(
                    "saturation_reject",
                    parent=("round", self.spec.round_id),
                    round=self.spec.round_id, tier=self.node_id,
                    client=p.client_id)
            _obs.trigger("saturation_reject", at=_obs.tracer().now(),
                         round=self.spec.round_id, tier=self.node_id,
                         client=p.client_id)
            return _reject(self.spec, p.client_id)
        self._R = cand
        self._m += p.n_summed
        self._obs.inc("accepted")
        self._obs.inc("clients_summed", p.n_summed)
        self._accepted.add(p.client_id)
        self._rx.discard(p.client_id)
        return self._ack(p.client_id, ack=ack)

    # ----------------------------------------------------------- LIFECYCLE
    @property
    def n_summed(self) -> int:
        """Clients folded into the accumulator so far."""
        return self._m

    @property
    def forwarded_q(self) -> "int | None":
        """Color space of the forwarded combined payload (None: not yet
        forwarded).  The root issues one batched decode per distinct value
        of this across its children."""
        if self._up_attempt is None:
            return None
        return wire.q_at_attempt(self.spec.cfg.q, self._up_attempt)

    @property
    def upstream_done(self) -> bool:
        """This tier needs nothing more from its parent: combined payload
        accepted, escalation exhausted, or nothing to forward at all."""
        if self._up_acked or self._up_gave_up:
            return True
        return self._sealed and not self.unresolved and self._m == 0

    # ------------------------------------------------------------ UPSTREAM
    def _fits(self, q: int) -> bool:
        """Would the accumulated R survive a round trip through mod-q
        colors?  centered_mod maps onto [-q//2, q//2)."""
        half = q // 2
        return bool(self._R.max() < half and self._R.min() >= -half)

    def _forward_attempt(self) -> int:
        """Smallest escalation attempt whose color space holds R (exists by
        the saturation guard, which pinned |R| under q_max/2)."""
        for a in range(self.spec.max_attempts):
            if self._fits(wire.q_at_attempt(self.spec.cfg.q, a)):
                return a
        raise AssertionError("saturation guard violated: R exceeds q_max/2")

    def _frames_at(self, attempt: int) -> "list[bytes]":
        """The combined payload's chunk frames at an escalation level
        (cached: retransmits are byte-identical).  ``K' = k0 + R`` packs as
        mod-q' colors; the checksum is ``h(K')`` so the root's verification
        passes by construction; ``n_summed`` carries the fold count."""
        cached = self._up_frames.get(attempt)
        if cached is None:
            q = wire.q_at_attempt(self.spec.cfg.q, attempt)
            k_fwd = (self._k0_host + self._R).astype(np.int32)
            words = np.asarray(K.lattice_pack_coords(jnp.asarray(k_fwd),
                                                     q=q))
            check = int(np.sum(k_fwd.view(np.uint32) * self._weights,
                               dtype=np.uint32))
            cached = C.encode_chunks(self.spec, self.node_id, attempt, q,
                                     words, self._sides_np, check,
                                     n_summed=self._m)
            self._up_frames[attempt] = cached
        return list(cached)

    def _send_up(self, frames: "list[bytes]") -> "list[bytes]":
        self._obs.inc("up_frames_sent", len(frames))
        return frames

    def _upstream_tick(self) -> "list[bytes]":
        """Forward once everything below is resolved; re-send the full
        sequence if the parent has stayed silent (total-loss recovery —
        a partially-received payload is chased by the parent's RESEND)."""
        if (not self._sealed or self.unresolved or self._m == 0
                or self._up_acked or self._up_gave_up):
            return []
        if not self._up_sent:
            self._up_sent = True
            self._up_attempt = self._forward_attempt()
            self._up_idle_ticks = 0
            return self._send_up(self._frames_at(self._up_attempt))
        self._up_idle_ticks += 1
        if self._up_idle_ticks >= _UP_RESEND_TICKS:
            self._up_idle_ticks = 0
            self._obs.inc("up_resends")
            return self._send_up(self._frames_at(self._up_attempt))
        return []

    def handle_upstream(self, data: bytes) -> "list[bytes]":
        """Process the parent's response; returns the frames to send next
        (the :class:`repro.agg.client.AggClient` state machine, acting for
        the combined payload)."""
        try:
            r = wire.decode_response(data)
        except wire.WireError:
            return []
        if (r.client_id != self.node_id
                or r.round_id != self.spec.round_id):
            return []
        self._up_idle_ticks = 0
        if r.status in (wire.STATUS_ACK, wire.STATUS_QUEUED):
            self._up_acked = self._up_acked or r.status == wire.STATUS_ACK
            return []
        if r.status == wire.STATUS_RETRY:
            self.retry_round = r.q_next or None
            return []
        if r.status == wire.STATUS_REJECT:
            self._up_gave_up = True
            return []
        if self._up_acked or self._up_gave_up or self._up_attempt is None:
            return []
        if r.status == wire.STATUS_RESEND:
            if r.attempt_next != self._up_attempt:
                return []
            self._obs.inc("up_resends")
            return self._send_up(C.select(self._frames_at(self._up_attempt),
                                          r.missing))
        # NACK: escalate — repack the SAME coordinates at the directed q
        if r.attempt_next >= self.spec.max_attempts:
            self._up_gave_up = True
            return []
        if r.attempt_next <= self._up_attempt:
            return []
        self._obs.inc("up_escalations")
        self._up_attempt = r.attempt_next
        return self._send_up(self._frames_at(self._up_attempt))


# response client_id offset: magic 4s | version u16 | status u16 | round u32
_RESP_CID_OFF = 12


class AggTree:
    """A fanout^j tier tree behind one :class:`~repro.agg.api.AggNode`.

    ``tiers`` tier layers sit between the clients and the root: the layer
    feeding the root has ``fanout`` tiers, the next one down
    ``fanout**2``, and so on; clients hash onto the leaf layer by
    ``client_id % n_leaf`` and every internal hop is ordinary transport.
    ``root`` defaults to a flat :class:`~repro.agg.server.AggServer` and
    may be any AggNode-shaped server of the same round.

    ``loss`` (tests/bench): ``loss(src_id, dst_id, data) -> bytes | None``
    applied to every INTERNAL message (tier->parent frames and
    parent->tier responses); ``None`` drops the message.  Client-facing
    traffic is the driver's to mangle.
    """

    def __init__(self, spec: wire.RoundSpec, anchor, *, fanout: int = 8,
                 tiers: int = 1, max_pending: "int | None" = None,
                 root=None,
                 loss: "Optional[Callable]" = None):
        if fanout < 2:
            raise ValueError(f"fanout must be >= 2, got {fanout}")
        if tiers < 1:
            raise ValueError(f"tiers must be >= 1, got {tiers}")
        self.spec = spec
        self.fanout = fanout
        self.tiers = tiers
        self._loss = loss
        self.root = (root if root is not None
                     else AggServer(spec, anchor, max_pending=max_pending))
        # layers[0] feeds the root (fanout nodes), layers[-1] is the leaf
        # layer (fanout**tiers nodes) the clients talk to
        self.layers: "list[list[TierAggregator]]" = []
        self._by_node_id: "dict[int, TierAggregator]" = {}
        self._parent: "dict[int, object]" = {}      # node_id -> parent node
        for depth in range(tiers):
            n = fanout ** (depth + 1)
            layer = []
            for i in range(n):
                nid = TIER_ID_BASE | (depth << 20) | i
                t = TierAggregator(spec, anchor, nid,
                                   max_pending=max_pending)
                layer.append(t)
                self._by_node_id[nid] = t
                self._parent[nid] = (self.root if depth == 0
                                     else self.layers[depth - 1][i // fanout])
            self.layers.append(layer)
        self._leaf = self.layers[-1]
        self._sealing = False

    # ------------------------------------------------------------ ROUTING
    def _leaf_for(self, client_id: int) -> TierAggregator:
        return self._leaf[client_id % len(self._leaf)]

    def _route(self, src, msg: bytes):
        """None = external (a real client's response); else the internal
        destination node."""
        if msg[:4] == wire.MAGIC_PAYLOAD:
            # only tiers emit frames; they go to that tier's parent
            return self._parent[src.node_id]
        if len(msg) >= _RESP_CID_OFF + 4:
            cid = int.from_bytes(msg[_RESP_CID_OFF:_RESP_CID_OFF + 4],
                                 "little")
            return self._by_node_id.get(cid)
        return None

    def _deliver(self, src, dest, msg: bytes, now: float):
        if self._loss is not None:
            src_id = getattr(src, "node_id", 0)
            dst_id = getattr(dest, "node_id", 0)
            msg = self._loss(src_id, dst_id, msg)
            if msg is None:
                return []
        return [(dest, r) for r in dest.ingest_frame(msg, now)]

    # ------------------------------------------------------------ AggNode
    def ingest_frame(self, data: bytes, now: float = 0.0) -> "list[bytes]":
        """Route one client frame to its edge tier; returns the tier's
        response (the client's QUEUED/ACK/RESEND/... — edge tiers answer
        clients directly, the root never sees individual client traffic)."""
        peek = wire.peek_route(data)
        leaf = self._leaf_for(peek[1]) if peek else self._leaf[0]
        return [leaf.receive(data)]

    def tick(self, now: float = 0.0) -> "list[bytes]":
        """Fire every node's policy and pump internal traffic until
        quiescent; returns only the EXTERNAL messages (client verdicts and
        chunk RESENDs), deduplicated within the call — one tick emits each
        distinct external message once, the flat server's cadence.

        Layer-synchronized sealing keeps the root's intake a single wave
        (all tiers of a layer forward in the same pump iteration), so a
        loss-free round costs exactly one root drain — one batched decode
        dispatch per color space."""
        self._advance_seal()
        out: "list[bytes]" = []
        seen: "set[bytes]" = set()
        msgs = []
        for node in self._all_nodes():
            msgs.extend((node, m) for m in node.tick(now))
        for _ in range(_MAX_PUMP):
            internal = []
            routed_any = False
            for src, m in msgs:
                dest = self._route(src, m)
                if dest is None:
                    if m not in seen:
                        seen.add(m)
                        out.append(m)
                    continue
                routed_any = True
                internal.extend(self._deliver(src, dest, m, now))
            if not routed_any:
                break
            self._advance_seal()
            # re-fire every node's policy after the delivery wave: drains
            # fold the new payloads, newly-sealed layers forward, verdicts
            # flow back down
            msgs = internal
            for node in self._all_nodes():
                msgs.extend((node, m) for m in node.tick(now))
        return out

    def published(self) -> "list[PublishedRound]":
        """The root's outcome with ``accepted`` mapped from tier node ids
        back to the real client ids their chains folded in."""
        prs = self.root.published()
        return [dataclasses.replace(pr,
                                    accepted=self._map_accepted(pr.accepted))
                for pr in prs]

    # ----------------------------------------------------------- LIFECYCLE
    def seal(self, next_round_id: int = 0) -> None:
        """Cut the round over: leaf tiers stop admitting new clients now;
        each internal layer (and finally the root) seals automatically once
        everything below it has forwarded — so a tier is never refused
        admission by its own parent."""
        self._next_round_id = next_round_id
        self._sealing = True
        for t in self._leaf:
            t.seal(next_round_id)

    def _advance_seal(self) -> None:
        if not self._sealing:
            return
        # layer barrier: a layer seals only when the WHOLE layer below is
        # done with its upstream — so all of a layer's tiers forward in the
        # same pump iteration and the parent (ultimately the root) folds
        # their payloads in a single drain wave
        for depth in range(self.tiers - 2, -1, -1):      # above-leaf layers
            below = self.layers[depth + 1]
            if all(k.upstream_done for k in below):
                for t in self.layers[depth]:
                    if not t.sealed:
                        t.seal(self._next_round_id)
        if (not self.root_sealed
                and all(t.upstream_done for t in self.layers[0])):
            self.root.seal(self._next_round_id)

    @property
    def root_sealed(self) -> bool:
        return bool(getattr(self.root, "sealed", False))

    @property
    def accepted_clients(self) -> frozenset:
        """Real client ids in the (to-be-)published mean: a client counts
        iff its edge tier accepted it AND every combined payload on its
        path to the root was accepted."""
        accepted = getattr(self.root, "accepted_clients", frozenset())
        return self._map_accepted(accepted)

    def _map_accepted(self, accepted: frozenset) -> frozenset:
        out: set = set()
        for cid in accepted:
            tier = self._by_node_id.get(cid)
            if tier is None:
                out.add(cid)                 # a real client at the root
                continue
            out |= self._tier_clients(tier)
        return frozenset(out)

    def _tier_clients(self, tier: TierAggregator) -> set:
        out: set = set()
        for cid in tier.accepted_clients:
            child = self._by_node_id.get(cid)
            if child is None:
                out.add(cid)
            else:
                out |= self._tier_clients(child)
        return out

    def _all_nodes(self):
        """Leaf -> top -> root: children act before their parents so one
        tick moves data a full level upward."""
        for layer in reversed(self.layers):
            yield from layer
        yield self.root

    # ---------------------------------------------------------- TELEMETRY
    @property
    def root_ingress_payloads(self) -> int:
        """Complete payloads the root has staged+folded — the acceptance
        bound is <= fanout (one combined payload per top tier)."""
        st = getattr(self.root, "stats", None)
        return (st.queued if st is not None else 0)

    def tier_stats(self) -> "list[TierStats]":
        return [t.stats for layer in self.layers for t in layer]
