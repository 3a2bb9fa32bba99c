"""repro.agg — streaming federated-DME aggregation on the packed lattice wire.

The canonical DME deployment (Suresh et al. 2017): many clients ship
compressed vectors to a coordinator that estimates their mean.  This package
lifts the repo's packed lattice wire format (repro.kernels lattice encode/
decode, repro.dist.collectives payload layout) from shard_map collectives to
an actual request/response protocol over real ``bytes``:

* :mod:`repro.agg.transport` — the layered transport stack: ``frame`` (v3
  self-describing header + per-frame CRC + the RoundSpec contract),
  ``chunks`` (fixed-MTU splitting, idempotent chunk frames, selective
  retransmit), ``session`` (out-of-order duplicate-tolerant reassembly with
  transport staging bounded by one frame, independent of d);
* :mod:`repro.agg.api`    — the unified :class:`AggNode` protocol
  (``ingest_frame`` / ``tick`` / ``published``) every aggregation endpoint
  implements;
* :mod:`repro.agg.client` — encodes a local vector against a round's shared
  randomness, chunks it per the round MTU, and handles escalation +
  selective-retransmit responses;
* :mod:`repro.agg.intake` — the client intake the flat server and the
  tree tiers share: validates/reassembles arriving frames, folds streamed
  chunk ranges on the device, NACKs undecodable clients with an escalated
  bound (RobustAgreement r <- r^2, lattice granularity fixed) and
  incomplete reassemblies with their missing chunk indices;
* :mod:`repro.agg.server` — streaming accumulator: drains payloads through
  ONE batched Pallas decode per color space and sums in integer coordinate
  space (bit-deterministic under any arrival order);
* :mod:`repro.agg.service` — multi-round coordinator: round k+1's anchor is
  round k's published mean (digest-pinned in the RoundSpec) and its
  per-bucket y comes from round k's decode telemetry
  (repro.core.qstate.update_y) — the anchored QState, threaded across
  rounds — plus the round life-cycle state machine
  (OPEN -> SEALING -> DRAINED -> PUBLISHED);
* :mod:`repro.agg.engine` — the event-driven continuous-round loop over the
  service: several live rounds at once (frames routed by their
  self-describing header), quorum-or-deadline cutover, overlapping drain,
  straggler deadlines feeding the RESEND budget, and admission
  control/backpressure via non-terminal ``STATUS_RETRY``;
* :mod:`repro.agg.sim`    — in-process harness driving hundreds of simulated
  clients through a server with stragglers, drops, duplicates, corruption,
  out-of-bound adversarial inputs and chunk-level loss
  (:func:`repro.agg.sim.run_chunked_lossy` pins the selective-retransmit
  wire cost byte-for-byte); :func:`repro.agg.sim.run_rounds` drives the
  multi-round service over a drifting large-norm population.
"""
from repro.agg.transport import (RoundSpec, FrameHeader, Payload, Response,
                                 WireError, TruncatedPayloadError,
                                 BadMagicError, VersionMismatchError,
                                 CorruptPayloadError, HeaderMismatchError,
                                 encode_payload, decode_payload,
                                 encode_frame, decode_frame,
                                 encode_response, decode_response,
                                 q_at_attempt, y_at_attempt,
                                 y_buckets_at_attempt, payload_bytes,
                                 STATUS_QUEUED, STATUS_NACK, STATUS_REJECT,
                                 STATUS_ACK, STATUS_RESEND, STATUS_RETRY,
                                 peek_route, Reassembler, ReassemblyStats)
from repro.agg.api import AggNode, PublishedLog, PublishedRound
from repro.agg.client import AggClient
from repro.agg.server import AggServer, RoundStats
from repro.agg.service import (AggService, Round, RoundState, ServiceConfig)
from repro.agg.engine import AggEngine, EngineConfig
from repro.agg.tree import AggTree, TierAggregator, TierStats

__all__ = [
    "RoundSpec", "FrameHeader", "Payload", "Response", "WireError",
    "TruncatedPayloadError", "BadMagicError", "VersionMismatchError",
    "CorruptPayloadError", "HeaderMismatchError", "encode_payload",
    "decode_payload", "encode_frame", "decode_frame", "encode_response",
    "decode_response", "q_at_attempt", "y_at_attempt",
    "y_buckets_at_attempt", "payload_bytes", "AggClient", "AggServer",
    "RoundStats", "AggService", "Round", "RoundState", "ServiceConfig",
    "AggEngine", "EngineConfig", "PublishedRound", "Reassembler",
    "ReassemblyStats", "STATUS_QUEUED", "STATUS_NACK", "STATUS_REJECT",
    "STATUS_ACK", "STATUS_RESEND", "STATUS_RETRY", "peek_route",
    "AggNode", "PublishedLog", "AggTree", "TierAggregator",
    "TierStats",
]
