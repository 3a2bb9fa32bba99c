"""The unified aggregation-node surface (ISSUE 7 API redesign).

Every aggregation endpoint in the repo — the single-round flat server
(:class:`repro.agg.server.AggServer`), the continuous-round engine
(:class:`repro.agg.engine.AggEngine`) and the hierarchical tree
(:class:`repro.agg.tree.TierAggregator` / :class:`repro.agg.tree.AggTree`)
— speaks the same three-verb protocol:

* ``ingest_frame(data, now)`` — feed one transport message (a client frame,
  or — for a tier — an upstream response); returns the response bytes the
  node wants sent.
* ``tick(now)`` — fire time/batch-based policy (drains, deadlines,
  retransmit requests, upstream forwarding); returns outbound bytes.
* ``published()`` — the in-order list of :class:`PublishedRound` outcomes.

A driver written against :class:`AggNode` cannot tell a flat star from a
two-level tree from the overlapping-round engine: the sim and the examples
drive all three through these verbs and assert bit-identical means.
"""
from __future__ import annotations

import dataclasses
from typing import TYPE_CHECKING, Optional, Protocol, runtime_checkable

import numpy as np

from repro.agg.transport import frame as wire

if TYPE_CHECKING:                                    # no import cycle at
    from repro.agg.server import RoundStats          # runtime: hints only


@dataclasses.dataclass
class PublishedRound:
    """One published round's outcome + latency/staleness telemetry.

    Produced by every :class:`AggNode` implementation; historically defined
    in :mod:`repro.agg.engine` (which still re-exports it).
    """
    round_id: int
    spec: wire.RoundSpec
    anchor: Optional[np.ndarray]    # what clients encoded against (None:
                                    # unanchored round)
    mean: np.ndarray
    stats: "RoundStats"
    accepted: frozenset             # client ids in the published mean
    opened_at: float
    sealed_at: float
    published_at: float
    anchor_round: int               # round whose mean this round anchored
                                    # against (0 = warm start)
    staleness: float                # published_at - anchor's publish time
                                    # (0.0 for warm-start anchors): how old
                                    # the anchor was when this mean shipped

    @property
    def latency(self) -> float:
        """Open -> published round latency (driver clock units)."""
        return self.published_at - self.opened_at

    @property
    def staleness_rounds(self) -> int:
        """Anchor lag in rounds (0 for warm-start anchors)."""
        return self.round_id - self.anchor_round if self.anchor_round else 0


class PublishedLog(list):
    """A list of :class:`PublishedRound` that is also callable.

    :class:`~repro.agg.engine.AggEngine` predates the :class:`AggNode`
    protocol and exposes its history as the attribute ``engine.published``
    (indexed and iterated all over the tests and the sim).  The protocol
    verb is the *call* ``node.published()``.  This list subclass satisfies
    both spellings without breaking either caller.
    """

    def __call__(self) -> "list[PublishedRound]":
        return list(self)


@runtime_checkable
class AggNode(Protocol):
    """The structural protocol every aggregation endpoint implements.

    ``ingest_frame`` / ``tick`` return *outbound transport bytes* — each
    item is a complete frame or response message; the driver owns routing
    (responses go back to the sender named in their header, frames go to
    the node's upstream).  ``now`` is whatever monotonic clock the driver
    uses (the sim passes virtual seconds); nodes are clock-agnostic and
    fire all policy from these two entry points — no threads, no timers.
    """

    def ingest_frame(self, data: bytes, now: float = 0.0) -> "list[bytes]":
        """Feed one arriving transport message; returns responses/frames."""
        ...

    def tick(self, now: float = 0.0) -> "list[bytes]":
        """Fire due time-based policy; returns responses/frames."""
        ...

    def published(self) -> "list[PublishedRound]":
        """In-order outcomes of every round this node has published."""
        ...
