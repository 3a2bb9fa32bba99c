"""repro.agg: wire-codec fuzzing + rejection, batched-decode parity and
single-dispatch guarantees, server determinism/escalation, and the >=512-
client simulation round (ISSUE 3 acceptance).  The server-vs-star bit-parity
check runs on 8 emulated devices in a subprocess (XLA_FLAGS must be set
before jax initializes), like tests/test_multidevice.py."""
import dataclasses
import os
import struct
import subprocess
import sys
import textwrap
import zlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.agg import rounds, sim
from repro.agg.transport import frame as wire
from repro.agg.client import AggClient
from repro.agg.server import AggServer
from repro.core import lattice as L
from repro.dist.collectives import QSyncConfig
from repro.kernels import ops as K
from repro.kernels import ref


def _spec(d=2048, q=16, bucket=256, rotate=False, y0=1.0, seed=3,
          round_id=7, max_attempts=4):
    return wire.RoundSpec(round_id=round_id, d=d,
                          cfg=QSyncConfig(q=q, bucket=bucket, rotate=rotate),
                          y0=y0, seed=seed, max_attempts=max_attempts)


# ---------------------------------------------------------------------------
# Wire codec: round-trip fuzz + rejection of damaged frames
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("d,q,bucket", [
    (2048, 16, 256),      # aligned
    (1000, 16, 128),      # odd d, partial final bucket
    (4096, 256, 512),     # 8-bit colors
    (96, 2, 32),          # 1-bit colors packed at 2 bits, tiny buckets
    (5000, 65536, 1024),  # the q cap (16-bit colors)
])
def test_wire_roundtrip_fuzz(d, q, bucket):
    rng = np.random.RandomState(d + q)
    spec = wire.RoundSpec(round_id=rng.randint(1 << 31), d=d,
                          cfg=QSyncConfig(q=min(q, 256), bucket=bucket),
                          seed=rng.randint(1 << 31))
    nb = spec.nb
    nw = L.packed_len(spec.padded, L.bits_for_q(q))
    for trial in range(5):
        words = rng.randint(0, 1 << 32, nw, dtype=np.uint64).astype(np.uint32)
        sides = rng.rand(nb).astype(np.float32) + 1e-3
        check = int(rng.randint(0, 1 << 32, dtype=np.uint64))
        attempt = int(rng.randint(0, 4))
        cid = int(rng.randint(0, 1 << 31))
        data = wire.encode_payload(spec, cid, attempt, q, words, sides, check)
        assert len(data) == 76 + 4 * nw + 4 * nb      # 72B header + 4B CRC
        if attempt == 0 and q == spec.cfg.q:
            assert len(data) == wire.payload_bytes(spec, 0)
        p = wire.decode_payload(data)
        assert (p.round_id, p.client_id, p.attempt, p.q) == \
            (spec.round_id, cid, attempt, q)
        assert (p.d, p.bucket, p.seed, p.rotate) == \
            (d, bucket, spec.seed, False)
        assert p.check == check
        np.testing.assert_array_equal(p.words, words)
        np.testing.assert_array_equal(p.sides, sides)


def _payload():
    spec = _spec()
    x = np.random.RandomState(0).randn(spec.d).astype(np.float32)
    return spec, AggClient(spec, 5, x).payload()


def test_wire_rejects_truncation():
    _, data = _payload()
    for cut in (0, 10, 51, 75, 76, len(data) - 1):
        with pytest.raises(wire.TruncatedPayloadError):
            wire.decode_payload(data[:cut])


def test_wire_rejects_trailing_garbage():
    _, data = _payload()
    with pytest.raises(wire.CorruptPayloadError):
        wire.decode_payload(data + b"\x00")


def test_wire_rejects_corruption():
    _, data = _payload()
    rng = np.random.RandomState(1)
    for _ in range(20):                       # random single-byte flips
        b = bytearray(data)
        b[rng.randint(4, len(b))] ^= 1 + rng.randint(255)
        with pytest.raises(wire.WireError):
            wire.decode_payload(bytes(b))


def test_wire_rejects_bad_magic_and_version():
    _, data = _payload()
    with pytest.raises(wire.BadMagicError):
        wire.decode_payload(b"XXXX" + data[4:])
    bad = bytearray(data)
    bad[4:6] = struct.pack("<H", wire.WIRE_VERSION + 1)
    with pytest.raises(wire.VersionMismatchError):
        wire.decode_payload(bytes(bad))


def test_wire_rejects_inconsistent_header():
    spec, data = _payload()
    # lie about n_words (offset 40 in the 72-byte header), recomputing the
    # CRC so only the header consistency check can catch it
    b = bytearray(data)
    b[40:44] = struct.pack("<I", 7)
    body = bytes(b[76:])
    crc = zlib.crc32(body, zlib.crc32(bytes(b[:72])))
    b[72:76] = struct.pack("<I", crc)
    with pytest.raises(wire.CorruptPayloadError):
        wire.decode_payload(bytes(b))


def test_wire_rejects_anchored_flag_digest_mismatch():
    """The anchored flag and the anchor digest must agree: a digest with no
    flag (or vice versa) is a corrupt header even if the CRC is fixed up."""
    spec, data = _payload()
    b = bytearray(data)
    b[52:56] = struct.pack("<I", 0xDEADBEEF)      # digest without the flag
    body = bytes(b[76:])
    crc = zlib.crc32(body, zlib.crc32(bytes(b[:72])))
    b[72:76] = struct.pack("<I", crc)
    with pytest.raises(wire.CorruptPayloadError):
        wire.decode_payload(bytes(b))


def test_check_against_spec_mismatches():
    spec, data = _payload()
    p = wire.decode_payload(data)
    wire.check_against_spec(p, spec)          # no raise
    for other in (dataclasses.replace(spec, round_id=8),
                  dataclasses.replace(spec, d=1024),
                  dataclasses.replace(spec, seed=99),
                  dataclasses.replace(spec, y0=5.0),   # sides != round s0
                  dataclasses.replace(spec,
                                      cfg=QSyncConfig(q=16, bucket=512))):
        with pytest.raises(wire.HeaderMismatchError):
            wire.check_against_spec(p, other)


def test_server_rejects_y0_mismatched_client():
    """A client built against a different y0 encodes on a different lattice;
    its checksum is self-consistent, so only the sidecar-vs-round-s0 check
    keeps it from silently corrupting the mean."""
    spec = _spec(y0=1.0)
    x = np.random.RandomState(0).randn(spec.d).astype(np.float32)
    server = AggServer(spec, x)
    bad = AggClient(dataclasses.replace(spec, y0=5.0), 1, x)
    r = wire.decode_response(server.receive(bad.payload()))
    assert r.status == wire.STATUS_REJECT
    assert server.stats.rejected_spec == 1


def test_response_roundtrip_and_crc():
    r = wire.Response(status=wire.STATUS_NACK, round_id=7, client_id=12,
                      attempt_next=2, q_next=65536, y_next=3.5)
    data = wire.encode_response(r)
    assert wire.decode_response(data) == r
    bad = bytearray(data)
    bad[8] ^= 0xFF
    with pytest.raises(wire.CorruptPayloadError):
        wire.decode_response(bytes(bad))


def test_escalation_schedule():
    assert [wire.q_at_attempt(16, a) for a in range(4)] == \
        [16, 256, 65536, 65536]
    spec = _spec(q=16, y0=1.0)
    assert spec.side == pytest.approx(2.0 / 15.0)
    # margins grow like (q_a - 1) * s0 / 2 with s0 fixed
    ys = [wire.y_at_attempt(spec, a) for a in range(3)]
    assert ys[0] == pytest.approx(1.0)
    assert ys[1] == pytest.approx((256 - 1) / 15.0)
    assert ys[2] == pytest.approx((65536 - 1) / 15.0)
    assert wire.payload_bytes(spec, 1) > wire.payload_bytes(spec, 0)


# ---------------------------------------------------------------------------
# Batched decode: bit-parity with the per-sender kernel and the jnp oracle
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("n,q,S", [
    (5000, 16, 6),        # odd n
    (4096, 256, 17),      # 8-bit colors, sender count not a block multiple
    (2048, 16, 1),        # single sender
    (1024, 65536, 3),     # 16-bit colors (the escalation cap)
])
def test_batched_decode_parity(n, q, S):
    bits = L.bits_for_q(q)
    anchor = jax.random.normal(jax.random.PRNGKey(0), (n,)) * 10
    u = L.shared_offset(jax.random.PRNGKey(1), (n,))
    xs = anchor[None] + 0.05 * jax.random.normal(jax.random.PRNGKey(2),
                                                 (S, n))
    sides = jnp.stack([jnp.full((n,), 0.01 * (i + 1)) for i in range(S)])
    words = jnp.stack([K.lattice_encode(xs[i], u, sides[i], q=q)
                       for i in range(S)])
    for mode in ("coords", "point"):
        kb = K.lattice_decode_batched(words, anchor, u, sides, q=q,
                                      mode=mode)
        kr = ref.lattice_decode_batched_ref(words, anchor, u, sides, q=q,
                                            bits=bits, n=n, mode=mode)
        kloop = jnp.stack([K.lattice_decode(words[i], anchor, u, sides[i],
                                            q=q, mode=mode)
                           for i in range(S)])
        np.testing.assert_array_equal(np.asarray(kb), np.asarray(kr))
        np.testing.assert_array_equal(np.asarray(kb), np.asarray(kloop))


def test_star_collective_single_batched_dispatch():
    """allgather_allreduce_mean's packed path must issue exactly one
    (batched) decode launch, not one per sender."""
    from functools import partial
    from jax.sharding import PartitionSpec as P
    from repro.dist.collectives import allgather_allreduce_mean
    mesh = jax.make_mesh((1,), ("data",),
                         axis_types=(jax.sharding.AxisType.Auto,))
    cfg = QSyncConfig(q=16, bucket=256, packed=True)
    x = jax.random.normal(jax.random.PRNGKey(0), (512,))
    y_b = jnp.full((2,), 1.0)

    @partial(jax.shard_map, mesh=mesh, in_specs=(P(),), out_specs=P(),
             check_vma=False)
    def f(xl):
        out, _ = allgather_allreduce_mean(xl, y_b, jax.random.PRNGKey(7),
                                          "data", cfg)
        return out

    K.reset_dispatch_counts()
    jax.jit(f).lower(x)                      # trace: wrappers run once
    assert K.DISPATCH_COUNTS["lattice_decode_batched"] == 1
    assert K.DISPATCH_COUNTS["lattice_decode"] == 0


def test_server_drain_single_batched_dispatch():
    # a d/bucket/sender-count combination no other test uses, so the jitted
    # drain must trace here — and the trace issues exactly one batched
    # decode launch for the whole pending set
    spec = _spec(d=2560, bucket=256)
    rng = np.random.RandomState(0)
    base = rng.randn(spec.d).astype(np.float32)
    xs = base[None] + 0.02 * rng.randn(40, spec.d).astype(np.float32)
    payloads = sim.fleet_payloads(spec, xs)
    server = AggServer(spec, base)
    for p in payloads:
        server.receive(p)
    K.reset_dispatch_counts()
    server.drain()
    assert K.DISPATCH_COUNTS["lattice_decode_batched"] == 1
    assert K.DISPATCH_COUNTS["lattice_decode"] == 0
    assert sorted(server.accepted_clients) == list(range(40))
    # drain sizes are padded to the kernel's sender-block multiple, so a
    # nearby client count reuses the compiled drain (no retrace at all)
    server2 = AggServer(spec, base)
    for p in payloads[:39]:
        server2.receive(p)
    K.reset_dispatch_counts()
    server2.drain()
    assert K.DISPATCH_COUNTS["lattice_decode_batched"] == 0
    assert sorted(server2.accepted_clients) == list(range(39))


# ---------------------------------------------------------------------------
# Server semantics
# ---------------------------------------------------------------------------

def _fleet(spec, S, seed=0, spread=0.02):
    rng = np.random.RandomState(seed)
    base = rng.randn(spec.d).astype(np.float32)
    xs = base[None] + spread * rng.randn(S, spec.d).astype(np.float32)
    return base, xs, sim.fleet_payloads(spec, xs)


def test_server_mean_invariant_to_arrival_order_and_drain_batching():
    spec = _spec(d=2048, bucket=256)
    base, xs, payloads = _fleet(spec, 24)
    means = []
    for order_seed, drain_every in ((0, 100), (1, 5), (2, 1)):
        server = AggServer(spec, base)
        order = np.random.RandomState(order_seed).permutation(24)
        for j, i in enumerate(order):
            server.receive(payloads[i])
            if (j + 1) % drain_every == 0:
                server.drain()
        means.append(server.finalize()[0])
    assert np.array_equal(means[0], means[1])
    assert np.array_equal(means[0], means[2])
    exact = xs.astype(np.float64).mean(0)
    assert float(np.abs(means[0] - exact).max()) <= spec.y0


def test_server_duplicates_never_double_count(intake_node):
    """Duplicates of accepted clients are ACKed and never counted twice, by
    the flat server and by a tree tier alike; in a windowed round the ACK
    covers every chunk (``ack=n_chunks``)."""
    spec = _spec(d=1024, bucket=128)
    base, xs, payloads = _fleet(spec, 8)
    node = intake_node(spec, base)
    for p in payloads:
        node.receive(p)
    node.tick()
    for p in payloads[:5]:                  # post-accept duplicates: ACKed
        r = wire.decode_response(node.receive(p))
        assert r.status == wire.STATUS_ACK
    node.receive(payloads[6])               # one more accepted duplicate
    mean = intake_node.publish(node, base)
    stats = node.stats
    ref_server = AggServer(spec, base)
    for p in payloads:
        ref_server.receive(p)
    mean_ref, _ = ref_server.finalize()
    assert np.array_equal(mean, mean_ref)
    assert stats.duplicates == 6
    assert stats.accepted == 8

    windowed = dataclasses.replace(spec, mtu=200, window=2)
    frames = sim.fleet_frames(windowed, xs[:2])
    node = intake_node(windowed, base)
    for fs in frames:
        for f in fs:
            node.receive(f)
    assert node.accepted_clients == frozenset({0, 1})
    r = wire.decode_response(node.receive(frames[0][1]))
    assert r.status == wire.STATUS_ACK
    assert r.ack == len(frames[0]) > 1
    assert node.stats.duplicates == 1 and node.stats.accepted == 2


def test_server_escalation_recovers_and_gives_up(intake_node):
    spec = _spec(d=1024, bucket=128, y0=1.0, max_attempts=4)
    rng = np.random.RandomState(0)
    base = rng.randn(spec.d).astype(np.float32)
    clients = {
        0: AggClient(spec, 0, base + 0.01),
        1: AggClient(spec, 1, base + 8.0),     # needs q=256 (margin 17*y0)
        2: AggClient(spec, 2, base + 1e6),     # beyond the q cap: dropped
    }
    server = intake_node(spec, base)
    for c in clients.values():
        server.receive(c.payload())
    resps = server.tick()
    while resps:
        retries = [p for rb in resps
                   for p in clients[wire.decode_response(rb).client_id]
                   .handle_response(rb)]
        if not retries:
            break
        for p in retries:
            server.receive(p)
        resps = server.tick()
    mean = intake_node.publish(server, base)
    stats = server.stats
    assert sorted(server.accepted_clients) == [0, 1]
    assert clients[1].attempt == 1 and not clients[1].gave_up
    assert clients[2].gave_up and stats.gave_up == 1
    assert stats.decode_failures >= 2 and stats.nacks_sent >= 1
    exact = (np.asarray(base + 0.01, np.float64)
             + np.asarray(base + 8.0, np.float64)) / 2
    # attempt-1 margin is ~17*y0; the lattice cell is still s0
    assert float(np.abs(mean - exact).max()) <= spec.y0


def test_server_zero_accepts_returns_zeros():
    spec = _spec(d=512, bucket=64)
    server = AggServer(spec, np.zeros(512, np.float32))
    mean, stats = server.finalize()
    assert mean.shape == (512,)
    assert np.all(mean == 0) and stats.accepted == 0


def test_client_payload_matches_fleet_encoder():
    for rotate in (False, True):
        spec = _spec(d=1000, bucket=128, rotate=rotate)
        _, xs, payloads = _fleet(spec, 4)
        assert AggClient(spec, 2, xs[2]).payload() == payloads[2]


def test_client_handles_ack_nack_reject():
    spec = _spec(max_attempts=3)
    x = np.zeros(spec.d, np.float32)
    c = AggClient(spec, 9, x)

    def resp(status, attempt_next=0, nb=None):
        nb = spec.nb if nb is None else nb
        return wire.encode_response(wire.Response(
            status=status, round_id=spec.round_id, client_id=9,
            attempt_next=attempt_next,
            q_next=wire.q_at_attempt(16, attempt_next),
            y_next=wire.y_at_attempt(spec, attempt_next),
            y_buckets=tuple(
                float(v) for v in
                wire.y_buckets_at_attempt(spec, attempt_next))[:nb]))

    assert c.handle_response(resp(wire.STATUS_ACK)) == [] and c.acked
    c.acked = False
    retry = c.handle_response(resp(wire.STATUS_NACK, 1))
    assert len(retry) == 1 and c.attempt == 1
    assert wire.decode_payload(retry[0]).q == 256
    # a duplicated/stale NACK must not flip gave_up: its retry is in flight
    assert c.handle_response(resp(wire.STATUS_NACK, 1)) == []
    assert not c.gave_up and c.attempt == 1
    assert c.handle_response(resp(wire.STATUS_NACK, 3)) == []  # >= max
    assert c.gave_up


def test_client_rejects_nack_with_wrong_y_vector_length():
    """ISSUE 4 satellite fix: a NACK whose per-bucket y vector length does
    not match the round's nb is corrupt — the client re-sends its current
    payload instead of truncating/broadcasting and escalating off it."""
    spec = _spec(max_attempts=4)
    x = np.zeros(spec.d, np.float32)
    c = AggClient(spec, 9, x)
    current = c.payload()

    def nack(attempt_next, nb):
        return wire.encode_response(wire.Response(
            status=wire.STATUS_NACK, round_id=spec.round_id, client_id=9,
            attempt_next=attempt_next,
            q_next=wire.q_at_attempt(16, attempt_next),
            y_next=wire.y_at_attempt(spec, attempt_next),
            y_buckets=(1.0,) * nb))

    for bad_nb in (0, spec.nb - 1, spec.nb + 3):
        out = c.handle_response(nack(1, bad_nb))
        assert out == [current]               # retransmit, don't escalate
        assert c.attempt == 0 and not c.gave_up
    # a well-formed NACK still escalates
    out = c.handle_response(nack(1, spec.nb))
    assert len(out) == 1 and c.attempt == 1


# ---------------------------------------------------------------------------
# The simulation acceptance: >=512 clients with escalation + drops
# ---------------------------------------------------------------------------

def test_sim_512_client_round():
    cfg = sim.SimConfig(clients=512, d=4096, bucket=512, drop=0.02,
                        duplicate=0.05, straggle=0.25, corrupt=2, truncate=1,
                        adversarial=4, extreme=1, seed=0)
    rep = sim.run_round(cfg)
    s = rep.stats
    n_drop = int(round(cfg.drop * cfg.clients))
    assert len(rep.accepted_clients) == cfg.clients - n_drop - cfg.extreme
    assert len(rep.escalated_clients) == cfg.adversarial   # all recovered
    assert s.gave_up == cfg.extreme
    assert s.rejected_wire == cfg.corrupt + cfg.truncate
    assert s.duplicates >= int(round(cfg.duplicate * cfg.clients))
    assert s.drains >= 2                                   # straggler wave
    assert rep.max_err <= 2 * cfg.y0
    # wire cost: ~d/2 bytes at q=16 plus sidecar/header overhead
    assert rep.bytes_per_client < 4 * cfg.d / 7


# ---------------------------------------------------------------------------
# Multi-round anchored service (ISSUE 4): convergence + per-bucket y
# ---------------------------------------------------------------------------

def test_per_bucket_y_uniform_matches_scalar_y_bitwise():
    """RoundSpec v2 with y_buckets=(y0,)*nb must produce bit-identical
    payloads, responses and round mean as the scalar-y0 spec."""
    base_spec = _spec(d=2048, bucket=256, y0=0.75)
    vec_spec = dataclasses.replace(
        base_spec, y_buckets=(0.75,) * base_spec.nb)
    rng = np.random.RandomState(0)
    anchor = rng.randn(base_spec.d).astype(np.float32)
    xs = anchor[None] + 0.02 * rng.randn(12, base_spec.d).astype(np.float32)
    p_scalar = sim.fleet_payloads(base_spec, xs)
    p_vec = sim.fleet_payloads(vec_spec, xs)
    assert p_scalar == p_vec
    means = []
    for spec, payloads in ((base_spec, p_scalar), (vec_spec, p_vec)):
        server = AggServer(spec, anchor)
        for p in payloads:
            server.receive(p)
        means.append(server.finalize()[0])
    assert np.array_equal(means[0], means[1])
    # the per-client protocol object agrees too
    assert AggClient(base_spec, 3, xs[3]).payload() == \
        AggClient(vec_spec, 3, xs[3]).payload()


def test_server_rejects_anchor_digest_mismatch():
    """An anchored round REJECTs payloads built against a different anchor
    (self-consistent checksum, wrong lattice frame)."""
    rng = np.random.RandomState(0)
    d = 1024
    anchor = rng.randn(d).astype(np.float32)
    stale = anchor + 1.0
    spec = wire.RoundSpec(round_id=3, d=d,
                          cfg=QSyncConfig(q=16, bucket=128), y0=1.0,
                          anchor_digest=rounds.anchor_digest(anchor))
    stale_spec = dataclasses.replace(
        spec, anchor_digest=rounds.anchor_digest(stale))
    server = AggServer(spec, anchor)
    bad = AggClient(stale_spec, 1, anchor + 0.01, anchor=stale)
    r = wire.decode_response(server.receive(bad.payload()))
    assert r.status == wire.STATUS_REJECT
    assert server.stats.rejected_spec == 1
    # constructing a client/server with the wrong anchor vector raises
    with pytest.raises(ValueError):
        AggClient(spec, 2, anchor + 0.01, anchor=stale)
    with pytest.raises(ValueError):
        AggServer(spec, stale)


def test_multi_round_convergence_256_clients():
    """ISSUE 4 satellite: 256 clients, 8 anchored rounds over a
    concentrating population — per-round MSE shrinks as the tracked
    per-bucket y tightens, and the anchor digest chain holds."""
    cfg = sim.MultiRoundConfig(clients=256, d=1024, bucket=128, rounds=8,
                               anchored=True, norm_scale=100.0, y0=1.0,
                               spread0=0.3, concentrate=0.6, y_decay=0.5,
                               drift=0.0, seed=1)
    outs = sim.run_rounds(cfg)
    assert len(outs) == 8
    assert all(o.accepted == cfg.clients for o in outs)
    # inputs concentrate => the tracked y tightens round over round once
    # the round-1 escalation transient settles, and MSE comes down with it:
    # strictly decreasing over the closing rounds and well below the peak
    assert outs[-1].y_mean < 0.5 * max(o.y_mean for o in outs)
    mses = [o.mse for o in outs]
    assert mses[-1] < mses[-2] < mses[-3], [f"{m:.3e}" for m in mses]
    assert mses[-1] < 0.5 * max(mses), [f"{m:.3e}" for m in mses]
    # every anchored round pins a (changing) anchor digest
    assert all(o.anchor_digest != 0 for o in outs)
    assert outs[0].anchor_digest != outs[1].anchor_digest


def test_multi_round_anchored_beats_unanchored_at_equal_bytes():
    """The acceptance criterion's protocol-level form: over a drifting
    large-norm population, anchored rounds achieve strictly lower MSE than
    unanchored rounds at identical attempt-0 wire bytes."""
    kw = dict(clients=32, d=2048, bucket=256, rounds=4, norm_scale=1e6,
              y0=0.5, spread0=0.05, concentrate=0.7, seed=0)
    anchored = sim.run_rounds(sim.MultiRoundConfig(anchored=True, **kw))
    plain = sim.run_rounds(sim.MultiRoundConfig(anchored=False, **kw))
    for a, u in zip(anchored, plain):
        assert a.bytes_per_client == u.bytes_per_client
        assert a.mse < u.mse, (a.round_id, a.mse, u.mse)


def test_server_overflow_guard_unanchored_large_norm():
    """Unanchored huge-norm rounds produce raw coords ~|x|/s; enough
    accepted senders would wrap the int32 accumulator — the server must
    fail loudly (pointing at anchoring) instead of silently corrupting the
    mean.  The equivalent anchored round accumulates fine."""
    rng = np.random.RandomState(0)
    d, bucket, S = 512, 64, 40
    mu = 2e6 * np.abs(rng.randn(d)).astype(np.float32) + 1e6
    xs = mu[None] + 0.01 * rng.randn(S, d).astype(np.float32)
    spec = wire.RoundSpec(round_id=1, d=d,
                          cfg=QSyncConfig(q=16, bucket=bucket), y0=0.5)
    # coords ~ |mu|/s ~ 1e6/(1/15) = 1.5e7..4.5e7; 40 senders * 4.5e7 > 2^31
    server = AggServer(spec, mu)
    with pytest.raises(OverflowError, match="anchor the round"):
        for p in sim.fleet_payloads(spec, xs):
            server.receive(p)
        server.finalize()
    a_spec = dataclasses.replace(spec,
                                 anchor_digest=rounds.anchor_digest(mu))
    a_server = AggServer(a_spec, mu)
    for p in sim.fleet_payloads(a_spec, xs, anchor=mu):
        a_server.receive(p)
    mean, stats = a_server.finalize()
    assert stats.accepted == S
    exact = xs.astype(np.float64).mean(0)
    assert float(np.abs(mean - exact).max()) <= 2 * spec.y0
def test_streamed_commit_overflow_guard_leaves_accumulator_untouched():
    """The streaming commit applies the int32 guard before it keeps the
    candidate sum: the frame that completes the offending stream raises
    OverflowError, and the accumulator and count are those of the streams
    committed before it."""
    rng = np.random.RandomState(0)
    d, bucket, S = 512, 64, 40
    mu = 2e6 * np.abs(rng.randn(d)).astype(np.float32) + 1e6
    xs = mu[None] + 0.01 * rng.randn(S, d).astype(np.float32)
    spec = wire.RoundSpec(round_id=1, d=d,
                          cfg=QSyncConfig(q=16, bucket=bucket), y0=0.5,
                          mtu=96, window=2)
    server = AggServer(spec, mu)
    assert server._streaming and spec.n_chunks() > 1
    frames = [f for fs in sim.fleet_frames(spec, xs) for f in fs]
    for f in frames:
        ksum, count = np.asarray(server._ksum), server._count
        try:
            server.receive(f)
        except OverflowError as e:
            assert "anchor the round" in str(e)
            break
    else:
        pytest.fail("the streamed commits never tripped the int32 guard")
    assert 0 < count < S
    assert server._count == count
    assert np.array_equal(np.asarray(server._ksum), ksum)




def test_service_anchor_chain_digests():
    """Round k+1's spec digest == digest of round k's published mean."""
    from repro.agg.service import AggService, ServiceConfig
    rng = np.random.RandomState(0)
    d = 512
    svc = AggService(ServiceConfig(d=d, bucket=64, y0=1.0),
                     anchor0=np.zeros(d, np.float32))
    means = []
    for _ in range(3):
        spec, anchor = svc.begin_round()
        if means:
            assert spec.anchor_digest == rounds.anchor_digest(means[-1])
        server = svc.make_server()
        xs = 0.1 * rng.randn(4, d).astype(np.float32)
        if anchor is not None:
            xs = xs + anchor[None]
        for i, p in enumerate(sim.fleet_payloads(spec, xs, anchor=anchor)):
            server.receive(p)
        mean, _ = svc.end_round(server)
        means.append(mean)


# ---------------------------------------------------------------------------
# Server mean == star collective, bit for bit (8 emulated devices)
# ---------------------------------------------------------------------------

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run_8dev(code: str, timeout=900):
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = os.path.join(_ROOT, "src")
    r = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                       capture_output=True, text=True, timeout=timeout,
                       env=env)
    assert r.returncode == 0, f"stdout:\n{r.stdout}\nstderr:\n{r.stderr}"
    return r.stdout


def test_server_mean_bit_identical_to_star_8dev():
    """ISSUE 3 acceptance: the aggregation server's round mean equals
    allgather_allreduce_mean bitwise for the same inputs/seeds (rotated and
    unrotated), invariant to client arrival order — and (ISSUE 5) the
    mtu-chunked transport is bit-identical to both: the same round carried
    as out-of-order interleaved chunk frames yields the same mean — as does
    (v5) the streaming server folding credit-windowed chunk ranges on
    arrival."""
    out = _run_8dev("""
        import dataclasses
        from functools import partial
        import numpy as np, jax, jax.numpy as jnp
        from jax.sharding import PartitionSpec as P
        from repro.dist.collectives import (QSyncConfig,
            allgather_allreduce_mean, flat_size_padded)
        from repro.agg import rounds
        from repro.agg.transport import frame as wire
        from repro.agg.client import AggClient
        from repro.agg.server import AggServer
        mesh = jax.make_mesh((8,), ("data",),
                             axis_types=(jax.sharding.AxisType.Auto,))
        for rotate in (False, True):
            n, bucket = 8192, 1024
            cfg = QSyncConfig(q=16, bucket=bucket, rotate=rotate)
            spec = wire.RoundSpec(round_id=11, d=n, cfg=cfg, y0=2.0, seed=5)
            base = jax.random.normal(jax.random.PRNGKey(0), (n,)) * 50.0
            xs = base + 0.05 * jax.random.normal(jax.random.PRNGKey(1),
                                                 (8, n))
            nb = flat_size_padded(n, cfg) // bucket
            y_b = jnp.full((nb,), spec.y0)
            key = rounds.round_key(spec)
            @partial(jax.shard_map, mesh=mesh, in_specs=(P("data"),),
                     out_specs=P("data"), check_vma=False)
            def f(xl):
                out, _ = allgather_allreduce_mean(xl.reshape(-1), y_b, key,
                                                  "data", cfg)
                return out.reshape(1, -1)
            star = np.asarray(jax.jit(f)(xs))
            assert np.all(star == star[0]), rotate
            server = AggServer(spec, np.asarray(xs[3]))
            for i in np.random.RandomState(1).permutation(8):
                server.receive(AggClient(spec, int(i),
                                         np.asarray(xs[i])).payload())
            mean, _ = server.finalize()
            assert np.array_equal(mean, star[0]), rotate
            # the same round over the chunked transport (>= 4 chunks per
            # client, frames interleaved across clients and shuffled)
            cspec = dataclasses.replace(spec, mtu=1024)
            frames = [(int(i), f) for i in range(8)
                      for f in AggClient(cspec, int(i),
                                         np.asarray(xs[i])).frames()]
            assert len(frames) >= 4 * 8, len(frames)
            cserver = AggServer(cspec, np.asarray(xs[3]))
            for j in np.random.RandomState(2).permutation(len(frames)):
                cserver.receive(frames[int(j)][1])
            cmean, cstats = cserver.finalize()
            assert cstats.accepted == 8, cstats
            assert np.array_equal(cmean, star[0]), rotate
            # (v5) the same round again through the streaming server:
            # credit-windowed clients, ranges folded on arrival — still
            # bit-identical to the star collective
            sspec = dataclasses.replace(spec, mtu=1024, window=2)
            sserver = AggServer(sspec, np.asarray(xs[3]))
            scli = [AggClient(sspec, i, np.asarray(xs[i])) for i in range(8)]
            outbox = [(c, f) for c in scli for f in c.send_frames()]
            while outbox:
                nxt = []
                for c, f in outbox:
                    for rb in sserver.ingest_frame(f):
                        nxt.extend((c, g) for g in c.handle_response(rb))
                outbox = nxt
            assert all(c.acked for c in scli)
            sserver.drain()
            smean, sstats = sserver.finalize()
            assert sstats.accepted == 8, sstats
            assert np.array_equal(smean, star[0]), rotate
            assert sstats.peak_pending_store_bytes < \
                cstats.peak_pending_store_bytes, (sstats, cstats)
        print("SERVER_STAR_PARITY_OK")
    """)
    assert "SERVER_STAR_PARITY_OK" in out


def test_anchored_server_mean_bit_identical_to_anchored_star_8dev():
    """The anchored acceptance: with the same QState anchor (round k-1's
    mean), the v2 server's round mean equals the anchored star collective
    bitwise — in the drifting large-norm regime where the unanchored frames
    could not even represent the coordinates."""
    out = _run_8dev("""
        from functools import partial
        import numpy as np, jax, jax.numpy as jnp
        from jax.sharding import PartitionSpec as P
        from repro.core.qstate import QState
        from repro.dist.collectives import (QSyncConfig,
            allgather_allreduce_mean, flat_size_padded)
        from repro.agg import rounds
        from repro.agg.transport import frame as wire
        from repro.agg.client import AggClient
        from repro.agg.server import AggServer
        mesh = jax.make_mesh((8,), ("data",),
                             axis_types=(jax.sharding.AxisType.Auto,))
        n, bucket = 8192, 1024
        cfg = QSyncConfig(q=16, bucket=bucket)
        anchor = np.asarray(
            jax.random.normal(jax.random.PRNGKey(0), (n,)) * 1e6, np.float32)
        spec = wire.RoundSpec(round_id=11, d=n, cfg=cfg, y0=2.0, seed=5,
                              anchor_digest=rounds.anchor_digest(anchor))
        xs = jnp.asarray(anchor) + 0.05 * jax.random.normal(
            jax.random.PRNGKey(1), (8, n))
        nb = flat_size_padded(n, cfg) // bucket
        qs = QState(y=jnp.full((nb,), spec.y0), anchor=jnp.asarray(anchor))
        key = rounds.round_key(spec)
        @partial(jax.shard_map, mesh=mesh, in_specs=(P("data"),),
                 out_specs=P("data"), check_vma=False)
        def f(xl):
            out, _ = allgather_allreduce_mean(xl.reshape(-1), qs, key,
                                              "data", cfg)
            return out.reshape(1, -1)
        star = np.asarray(jax.jit(f)(xs))
        assert np.all(star == star[0])
        server = AggServer(spec, anchor)
        for i in np.random.RandomState(1).permutation(8):
            server.receive(AggClient(spec, int(i), np.asarray(xs[i]),
                                     anchor=anchor).payload())
        mean, stats = server.finalize()
        assert stats.accepted == 8, stats
        assert np.array_equal(mean, star[0])
        print("ANCHORED_PARITY_OK")
    """)
    assert "ANCHORED_PARITY_OK" in out


# ---------------------------------------------------------------------------
# Streaming tiers (v5): windowed tree == flat sealed server, bit for bit
# ---------------------------------------------------------------------------

def test_streaming_tree_windowed_bit_identical_to_flat_sealed():
    """A windowed round through a 2-tier AggTree (every edge tier folding
    validated chunk ranges as they land) publishes the same accepted set
    and a bit-identical mean as the flat SEALED server — under a fully
    permuted chunk blast AND under credit-paced windowed clients."""
    from repro.agg.tree import AggTree

    d, n_clients = 2048, 12
    spec = dataclasses.replace(_spec(d=d, seed=11, round_id=9),
                               mtu=300, window=2)
    rng = np.random.RandomState(11)
    base = 2.0 * rng.randn(d).astype(np.float32)
    xs = base[None] + 0.02 * rng.randn(n_clients, d).astype(np.float32)
    clients = [AggClient(spec, cid, xs[cid]) for cid in range(n_clients)]
    all_frames = [c.frames() for c in clients]
    assert len(all_frames[0]) >= 3

    flat = AggServer(spec, base, streaming=False)
    for fs in all_frames:
        for f in fs:
            flat.ingest_frame(f)
    flat.tick()
    flat.seal()
    pf = flat.published()[0]
    assert len(pf.accepted) == n_clients

    # permuted blast: tiers stream ranges out of order, roll nothing back
    tree = AggTree(spec, base, fanout=4, tiers=2)
    deliveries = [f for fs in all_frames for f in fs]
    for i in rng.permutation(len(deliveries)):
        tree.ingest_frame(deliveries[int(i)])
    tree.tick()
    tree.seal()
    for _ in range(8):
        tree.tick()
        if tree.published():
            break
    pt = tree.published()[0]
    assert pt.accepted == pf.accepted
    assert np.array_equal(np.asarray(pt.mean).view(np.uint32),
                          np.asarray(pf.mean).view(np.uint32))
    assert all(t._streaming for t in tree.layers[0])

    # credit-paced windowed clients against the streaming tree
    tree2 = AggTree(spec, base, fanout=4, tiers=2)
    cl2 = [AggClient(spec, cid, xs[cid]) for cid in range(n_clients)]
    outbox = [(c, f) for c in cl2 for f in c.send_frames()]
    for _ in range(60):
        nxt = []
        for c, f in outbox:
            for rb in tree2.ingest_frame(f):
                nxt.extend((c, g) for g in c.handle_response(rb))
        for m in tree2.tick():
            r = wire.decode_response(m)
            for c in cl2:
                if c.client_id == r.client_id:
                    nxt.extend((c, g) for g in c.handle_response(m))
        outbox = nxt
        if all(c.acked for c in cl2):
            break
    assert all(c.acked for c in cl2)
    tree2.seal()
    for _ in range(8):
        tree2.tick()
        if tree2.published():
            break
    pt2 = tree2.published()[0]
    assert pt2.accepted == pf.accepted
    assert np.array_equal(np.asarray(pt2.mean).view(np.uint32),
                          np.asarray(pf.mean).view(np.uint32))


def test_streaming_server_expire_rolls_back_fold_and_store():
    """expire_client on a half-streamed client drops its speculative fold
    and its held bytes: the published mean is over the others only, and
    the pending store returns to zero."""
    spec = dataclasses.replace(_spec(d=2048, seed=4), mtu=300, window=2)
    rng = np.random.RandomState(0)
    base = rng.randn(spec.d).astype(np.float32)
    xs = base[None] + 0.02 * rng.randn(3, spec.d).astype(np.float32)
    fleets = [AggClient(spec, i, xs[i]).frames() for i in range(3)]
    server = AggServer(spec, base)
    for f in fleets[0]:
        server.receive(f)
    for f in fleets[1]:
        server.receive(f)
    for f in fleets[2][:2]:                  # client 2: half a stream
        server.receive(f)
    assert server._folds                     # its speculative fold is open
    server.expire_client(2)
    assert not any(k[0] == 2 for k in server._folds)
    server.drain()
    mean, stats = server.finalize()
    assert server.accepted_clients == frozenset({0, 1})
    ref_srv = AggServer(spec, base, streaming=False)
    for f in fleets[0] + fleets[1]:
        ref_srv.receive(f)
    mean_ref, _ = ref_srv.finalize()
    assert np.array_equal(mean.view(np.uint32), mean_ref.view(np.uint32))
