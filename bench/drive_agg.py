"""Traffic of the aggregation face: whole federated rounds, closed loop.

Set-up draws the round's data from the seed (the previous published mean,
which anchors the round, and each client's update close to it), has every
client of the cohort encode its update through the program's client
(``AggClient``), and keeps the frames.  The window replays that cohort
round after round, each round into a fresh ``AggServer`` driven through the
``AggNode`` calls (``ingest_frame``, ``tick``, ``published``): the cohort
uploads concurrently, frame by frame, and with a credit window each client
sends its next chunk as the server's response returns.  The window stops
starting rounds after ``seconds``; rates are taken over its whole rounds.

The traffic file sets ``window`` (chunks in flight; 0 pushes every chunk
without credit, which puts the server on its sealed batched drain).
"""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

from bench import counts
from bench.reference import agg as ref_agg

ROUND_ID = 1


def seed_key(seed: int) -> jax.Array:
    """A PRNG key from a seed of any size up to 62 bits."""
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF),
                              (seed >> 31) & 0x7FFFFFFF)


class Run:
    def __init__(self, cfg: dict, traffic: dict, seed: int, chips: int):
        self.cfg, self.traffic, self.seed = cfg, traffic, seed
        self.chips = chips
        self.window_chunks = int(traffic["window"])
        self.key = seed_key(seed)

    # --------------------------------------------------------------- data
    def _make_x_of(self):
        cfg = self.cfg
        d, noise = cfg["d"], cfg["update_noise"]

        @jax.jit
        def x_of(base, i):
            return base + noise * jax.random.normal(
                jax.random.fold_in(jax.random.fold_in(self.key, 2), i), (d,))
        return x_of

    def setup(self) -> None:
        from repro.agg import rounds as AR
        from repro.agg.client import AggClient
        from repro.agg.transport import frame as wire
        from repro.dist.collectives import QSyncConfig

        cfg = self.cfg
        d = cfg["d"]
        self.base = cfg["anchor_scale"] * jax.random.normal(
            jax.random.fold_in(self.key, 1), (d,))
        self.anchor = np.asarray(self.base)
        self.spec = wire.RoundSpec(
            round_id=ROUND_ID, d=d,
            cfg=QSyncConfig(q=cfg["q"], bucket=cfg["bucket"]),
            y0=cfg["y0"], seed=AR.fold_seed(self.seed, ROUND_ID),
            anchor_digest=AR.anchor_digest(self.anchor) if cfg["anchored"]
            else 0,
            max_attempts=cfg["max_attempts"], mtu=cfg["mtu"],
            window=self.window_chunks)
        x_of = self._x_of = self._make_x_of()
        self.frames = {}
        for cid in range(cfg["cohort"]):
            c = AggClient(self.spec, cid, x_of(self.base, cid),
                          anchor=self.anchor if cfg["anchored"] else None)
            self.frames[cid] = c.frames()
        self.means, self.accepted = [], []
        self.failed_clients = 0
        # one whole round warms up every shape the window uses
        self._round(record=False)

    # ------------------------------------------------------------- window
    def _round(self, record: bool = True) -> None:
        from repro.agg.server import AggServer
        from repro.agg.transport import chunks as C

        with TraceAnnotation("bench.round"):
            server = AggServer(self.spec, self.anchor)
            wins = {}
            outbox = []
            for cid, fr in self.frames.items():
                if self.window_chunks:
                    wins[cid] = C.SendWindow(fr, self.window_chunks)
                    outbox.extend((cid, f) for f in wins[cid].sendable())
                else:
                    outbox.extend((cid, f) for f in fr)
            failed = set()
            while outbox:
                nxt = []
                for cid, f in outbox:
                    with TraceAnnotation("bench.receive"):
                        resps = server.ingest_frame(f)
                    with TraceAnnotation("bench.client"):
                        for r in resps:
                            nxt.extend(self._handle(r, wins, failed))
                outbox = nxt
            with TraceAnnotation("bench.drain"):
                server.seal()
                for r in server.tick():
                    self._handle(r, wins, failed)
                pub = server.published()
        if not record:
            return
        if pub:
            self.means.append(pub[0].mean)
            self.accepted.append(len(pub[0].accepted))
        else:
            self.means.append(None)
            self.accepted.append(0)
        self.failed_clients += len(failed)

    def _handle(self, data: bytes, wins: dict, failed: set) -> list:
        from repro.agg.transport import frame as wire
        r = wire.decode_response(data)
        if r.status == wire.STATUS_ACK:
            return []
        if r.status == wire.STATUS_QUEUED:
            w = wins.get(r.client_id)
            if w is None:
                return []
            w.note_ack(r.ack)
            return [(r.client_id, f) for f in w.sendable()]
        # a lossless round at inputs close to the anchor never draws a
        # NACK, RESEND, RETRY or REJECT: count the client as failed
        failed.add(r.client_id)
        return []

    def window(self, seconds: float) -> dict:
        t0 = time.perf_counter()
        rounds = 0
        while True:
            self._round()
            rounds += 1
            if time.perf_counter() - t0 >= seconds:
                break
        window_s = time.perf_counter() - t0
        updates = int(sum(self.accepted))
        self.rounds = rounds
        return {
            "kind": "agg",
            "window_s": window_s,
            "rounds": rounds,
            "updates": updates,
            "attempted": rounds * self.cfg["cohort"],
            "failed": rounds * self.cfg["cohort"] - updates,
            "end_to_end": {"updates_per_s": updates / window_s},
            "decode_bytes_per_round": counts.agg_decode_bytes(
                self.cfg["cohort"], self.spec.padded,
                ref_agg.bits_for(self.cfg["q"]), self.spec.nb),
        }

    # -------------------------------------------------------------- check
    def exact_mean(self) -> np.ndarray:
        x_of = self._x_of
        acc = jnp.zeros((self.cfg["d"],), jnp.float32)
        for i in range(self.cfg["cohort"]):
            acc = acc + x_of(self.base, i)
        return np.asarray(acc / self.cfg["cohort"])

    def reference(self, low_precision: bool = False) -> np.ndarray:
        """The plain reference's published mean of the cohort's frames."""
        mean, _ = ref_agg.round_mean(self.frames, self.anchor, self.cfg["q"],
                                     self.cfg["y0"],
                                     low_precision=low_precision)
        return mean

    def check(self) -> list:
        """[(name, value, limit)]: every round's published mean against the
        plain reference's decode-and-sum of the same frames, bit for bit;
        its distance to the exact mean against the lattice bound; clients
        that were not accepted."""
        return readings(self.cfg, self.means, self.accepted,
                        self.failed_clients, self.reference(),
                        self.exact_mean())

    def release(self) -> None:
        """Each round's server is dropped when the round ends; the frames
        stay, since they are the reference's input."""


def readings(cfg: dict, means: list, accepted: list, failed: int,
             ref: np.ndarray, exact: np.ndarray) -> list:
    half_side = cfg["y0"] / (cfg["q"] - 1)
    differ, err = 0, 0.0
    for m in means:
        if m is None:
            differ, err = max(differ, cfg["d"]), float("inf")
            continue
        differ = max(differ, int(np.sum(
            m.view(np.uint32) != ref.view(np.uint32))))
        err = max(err, float(np.max(np.abs(m.astype(np.float64) - exact)))
                  / half_side)
    missing = max((cfg["cohort"] - a for a in accepted), default=cfg["cohort"])
    got = {"mean_bits_differ": differ, "err_over_half_side": err,
           "clients_not_accepted": missing + failed}
    return [(n, got[n], lim) for n, lim in LIMITS.items()]


# Exact comparisons have the limit 0; the distance to the exact mean is
# held to the quantizer's own guarantee, half a lattice side (PERF.md).
LIMITS = {"mean_bits_differ": 0, "err_over_half_side": 1.0,
          "clients_not_accepted": 0}
