"""Layered transport stack (ISSUE 5): chunk-layer fuzzing, reassembly
state machine, selective retransmit cost, and the one-wire-accounting
cross-checks against actual payload/collective byte sizes."""
import dataclasses
import struct
import zlib

import numpy as np
import pytest

from repro.agg import rounds, sim
from repro.agg.transport import frame as wire
from repro.agg.client import AggClient
from repro.agg.server import AggServer
from repro.agg.transport import chunks as C
from repro.agg.transport import frame as F
from repro.agg.transport import session as S
from repro.core import lattice as L
from repro.core import wire_accounting as WA
from repro.dist.collectives import (QSyncConfig, _payload_bytes,
                                    flat_size_padded, wire_bytes_allgather,
                                    wire_bytes_butterfly, wire_bytes_rh)
from repro.dist.fsdp import FSDPConfig, wire_bytes_bwd


def _spec(d=2048, q=16, bucket=256, mtu=300, y0=1.0, seed=3, round_id=7,
          max_attempts=4, **kw):
    return wire.RoundSpec(round_id=round_id, d=d,
                          cfg=QSyncConfig(q=q, bucket=bucket), y0=y0,
                          seed=seed, max_attempts=max_attempts, mtu=mtu,
                          **kw)


def _fleet(spec, n, seed=0, spread=0.02):
    rng = np.random.RandomState(seed)
    base = rng.randn(spec.d).astype(np.float32)
    xs = base[None] + spread * rng.randn(n, spec.d).astype(np.float32)
    return base, xs, sim.fleet_frames(spec, xs)


# ---------------------------------------------------------------------------
# Wire accounting: the one definition, cross-checked against len()
# ---------------------------------------------------------------------------

def test_agg_payload_bytes_match_actual_frames():
    """payload_bytes == sum(len(frame)) for chunked AND unchunked rounds,
    at every escalation level."""
    for mtu in (0, 300, 1024):
        spec = _spec(d=2000, bucket=256, mtu=mtu)
        x = np.random.RandomState(0).randn(spec.d).astype(np.float32)
        c = AggClient(spec, 1, x)
        for attempt in range(3):
            frames = c.frames(attempt)
            assert sum(len(f) for f in frames) == \
                wire.payload_bytes(spec, attempt), (mtu, attempt)
            assert len(frames) == spec.n_chunks(attempt), (mtu, attempt)


def test_frame_header_constant_matches_struct():
    spec = _spec(mtu=0, d=512, bucket=64)
    x = np.zeros(512, np.float32)
    data = AggClient(spec, 1, x).payload()
    body = WA.packed_body_bytes(spec.padded, spec.cfg.bits, spec.nb)
    assert len(data) == WA.FRAME_HEADER_BYTES + body
    assert WA.frame_bytes(body) == len(data)


def test_chunk_span_geometry():
    assert WA.n_chunks(1000, 0) == 1
    assert WA.n_chunks(1000, 300) == 4
    assert WA.n_chunks(900, 300) == 3
    spans = [WA.chunk_span(1000, 300, i) for i in range(4)]
    assert spans == [(0, 300), (300, 300), (600, 300), (900, 100)]
    assert sum(ln for _, ln in spans) == 1000
    with pytest.raises(ValueError):
        WA.chunk_span(1000, 300, 4)
    assert WA.framed_payload_bytes(1000, 300) == 4 * 76 + 1000
    assert WA.chunk_overhead_pct(1000, 300) == pytest.approx(
        100.0 * 3 * 76 / 1076)


def test_collective_accounting_delegates_to_wire_accounting():
    """collectives.wire_bytes_* and fsdp.wire_bytes_bwd agree with the
    core.wire_accounting formulas they delegate to."""
    n, world = 5000, 8
    cfg = QSyncConfig(q=16, bucket=512)
    padded = flat_size_padded(n, cfg)
    nb = padded // cfg.bucket
    assert _payload_bytes(n, cfg) == \
        WA.collective_payload_bytes(padded, cfg.bits, nb, True) == \
        L.wire_bytes(padded, cfg.bits) + 4 * nb
    assert wire_bytes_butterfly(n, world, cfg) == \
        WA.butterfly_bytes(padded, cfg.bits, nb, world)
    assert wire_bytes_allgather(n, world, cfg) == \
        WA.allgather_bytes(padded, cfg.bits, nb, world)
    assert wire_bytes_rh(n, world, cfg) == \
        WA.rh_bytes(padded, cfg.bits, nb, world)
    m = 1 << 16
    fp32 = FSDPConfig(sync="fp32")
    assert wire_bytes_bwd(m, [8], fp32) == \
        WA.fp32_ring_reduce_scatter_bytes(m, 8)
    # the agg body is byte-for-byte the collective payload
    spec = _spec(d=n, bucket=512, mtu=0)
    assert spec.body_bytes() == _payload_bytes(n, cfg)


# ---------------------------------------------------------------------------
# Chunk-layer fuzzing: damaged / duplicated / reordered / stale chunks
# ---------------------------------------------------------------------------

def test_chunk_frames_are_self_describing_and_idempotent():
    spec = _spec()
    _, xs, fleets = _fleet(spec, 1)
    frames = fleets[0]
    assert len(frames) == spec.n_chunks() >= 3
    pcrc = None
    for i, f in enumerate(frames):
        h, chunk = wire.decode_frame(f)
        assert (h.n_chunks, h.chunk_index) == (len(frames), i)
        assert h.body_len == spec.body_bytes()
        pcrc = h.payload_crc if pcrc is None else pcrc
        assert h.payload_crc == pcrc            # all chunks seal one body
        wire.check_frame_against_spec(h, spec, len(chunk))
    # re-encoding yields byte-identical frames (idempotent retransmit)
    c = AggClient(spec, 0, np.asarray(xs[0]))
    assert c.frames() == frames


def test_truncated_and_corrupt_chunks_rejected():
    spec = _spec()
    _, _, fleets = _fleet(spec, 1)
    rng = np.random.RandomState(0)
    for f in fleets[0]:
        for cut in (0, 10, 75, 76, len(f) - 1):
            with pytest.raises(wire.WireError):
                wire.decode_frame(f[:cut])
        with pytest.raises(wire.CorruptPayloadError):
            wire.decode_frame(f + b"\\x00")
        for _ in range(10):
            b = bytearray(f)
            b[rng.randint(4, len(b))] ^= 1 + rng.randint(255)
            with pytest.raises(wire.WireError):
                wire.decode_frame(bytes(b))


def test_server_counts_damaged_chunks_as_wire_rejects():
    spec = _spec(d=1024, bucket=128, mtu=200)
    base, _, fleets = _fleet(spec, 2)
    server = AggServer(spec, base)
    bad = bytearray(fleets[0][1])
    bad[-1] ^= 0xFF
    r = wire.decode_response(server.receive(bytes(bad)))
    assert r.status == wire.STATUS_REJECT
    assert server.stats.rejected_wire == 1
    assert server.transport_stats.chunks == 0    # never reached the session


def test_chunk_mtu_geometry_enforced_per_spec():
    """A client chunking with a foreign MTU violates the round contract:
    every frame is self-consistent but n_chunks/chunk length disagree with
    the spec's geometry -> HeaderMismatch, counted as a spec reject."""
    spec = _spec(d=1024, bucket=128, mtu=200)
    foreign = dataclasses.replace(spec, mtu=400)
    base, xs, _ = _fleet(spec, 1)
    server = AggServer(spec, base)
    for f in AggClient(foreign, 0, np.asarray(xs[0])).frames():
        r = wire.decode_response(server.receive(f))
        assert r.status == wire.STATUS_REJECT
    assert server.stats.rejected_spec >= 1
    assert server.transport_stats.chunks == 0


def test_cross_round_stale_chunks_rejected():
    """Chunks of round k must never enter round k+1's reassembly."""
    old = _spec(round_id=7)
    new = dataclasses.replace(old, round_id=8)
    base, xs, old_fleet = _fleet(old, 2)
    server = AggServer(new, base)
    cur = AggClient(new, 0, np.asarray(xs[0]))
    for f in old_fleet[0]:
        rb = server.receive(f)
        r = wire.decode_response(rb)
        assert r.status == wire.STATUS_REJECT
        assert r.round_id == old.round_id    # echoes the stale frame's round
        assert cur.handle_response(rb) == []
        assert not cur.gave_up               # current round unharmed
    assert server.stats.rejected_spec == len(old_fleet[0])
    assert server.transport_stats.chunks == 0
    # the current round's chunks still assemble fine afterwards
    for f in sim.fleet_frames(new, xs)[1]:
        server.receive(f)
    server.drain()
    assert server.accepted_clients == frozenset({1})


def test_duplicate_and_reordered_chunks_reassemble():
    spec = _spec(d=2048, bucket=256, mtu=300)
    base, xs, fleets = _fleet(spec, 4)
    # reference: in-order, no duplicates
    ref = AggServer(spec, base)
    for fs in fleets:
        for f in fs:
            ref.receive(f)
    mean_ref, _ = ref.finalize()
    rng = np.random.RandomState(1)
    flat = [(c, k) for c, fs in enumerate(fleets) for k in range(len(fs))]
    # interleave across clients, shuffle order, duplicate ~half the chunks
    order = [flat[i] for i in rng.permutation(len(flat))]
    order += [flat[i] for i in
              rng.choice(len(flat), len(flat) // 2, replace=False)]
    server = AggServer(spec, base)
    for c, k in order:
        server.receive(fleets[c][k])
    mean, stats = server.finalize()
    assert np.array_equal(mean, mean_ref)
    assert stats.accepted == 4
    ts = server.transport_stats
    assert ts.chunks == len(order)       # every frame reached the session
    assert ts.buffer_bytes == 0          # ... and every session was closed
    # duplicate deliveries were absorbed at some layer (identical-index
    # chunks in an open session, or whole-payload dedupe at the server)
    assert ts.duplicates + stats.duplicates > 0 or stats.accepted == 4


def test_any_chunk_arrival_permutation_bit_identical_mean():
    """Property: ANY permutation of the round's chunk frames (interleaved
    across clients, duplicates included) yields a bit-identical mean."""
    spec = _spec(d=1024, bucket=128, mtu=128, seed=11)
    base, _, fleets = _fleet(spec, 3)
    flat = [f for fs in fleets for f in fs]
    means = []
    for trial in range(6):
        rng = np.random.RandomState(trial)
        order = list(rng.permutation(len(flat)))
        if trial % 2:                       # mix in duplicate deliveries
            order += list(rng.choice(len(flat), 5))
        server = AggServer(spec, base)
        for i in order:
            server.receive(flat[i])
        server.drain()
        assert server.accepted_clients == frozenset(range(3)), trial
        means.append(server.finalize()[0])
    for m in means[1:]:
        assert np.array_equal(means[0], m)


def test_chunked_round_bit_identical_to_single_frame_round():
    """The acceptance bit-parity: chunked == v3 single-frame for the same
    inputs/seeds (the 8-dev suite additionally pins both to the star
    collective)."""
    plain = _spec(d=2048, bucket=256, mtu=0)
    chunked = dataclasses.replace(plain, mtu=256)
    base, xs, _ = _fleet(plain, 6)
    means = []
    for spec in (plain, chunked):
        server = AggServer(spec, base)
        for fs in sim.fleet_frames(spec, xs):
            for f in fs:
                server.receive(f)
        mean, stats = server.finalize()
        assert stats.accepted == 6
        means.append(mean)
    assert np.array_equal(means[0], means[1])


def test_conflicting_payload_never_merges():
    """Two CRC-valid chunk streams for the same client with different
    payload bodies must not be spliced together."""
    spec = _spec(d=1024, bucket=128, mtu=200)
    base, xs, fleets = _fleet(spec, 2)
    # re-key client 1's frames as client 0 (a CRC-valid foreign stream)
    foreign = []
    for f in fleets[1]:
        h, chunk = wire.decode_frame(f)
        foreign.append(wire.encode_frame(
            dataclasses.replace(h, client_id=0), chunk))
    server = AggServer(spec, base)
    server.receive(fleets[0][0])
    for f in foreign[1:]:
        r = wire.decode_response(server.receive(f))
        # its own doomed stream, NOT terminal: must not kill client 0
        assert r.status == wire.STATUS_QUEUED
    assert server.transport_stats.conflicts >= 1
    # the original stream still completes
    for f in fleets[0][1:]:
        server.receive(f)
    server.drain()
    assert server.accepted_clients == frozenset({0})


def test_forged_first_frame_cannot_capture_session():
    """Regression (review finding): a forged frame arriving BEFORE the
    honest client's chunks must not capture the client's reassembly —
    payload_crc keys the streams, so the honest stream merges into its
    own and completes regardless of arrival order."""
    spec = _spec(d=1024, bucket=128, mtu=200)
    base, xs, fleets = _fleet(spec, 2)
    h1, chunk1 = wire.decode_frame(fleets[1][0])
    forged_first = wire.encode_frame(
        dataclasses.replace(h1, client_id=0), chunk1)
    server = AggServer(spec, base)
    server.receive(forged_first)          # imposter opens a doomed stream
    for f in fleets[0]:                   # honest stream still completes
        server.receive(f)
    server.drain()
    assert server.accepted_clients == frozenset({0})
    assert server.transport_stats.conflicts >= 1


def test_forged_outprogressing_stream_cannot_capture_resend():
    """Regression (review finding): RESEND names the UNION of missing
    indices across a client's open streams — a forged same-attempt stream
    with more progress than the honest one must not monopolize the
    client's RESEND slot (the honest gaps would never be requested)."""
    spec = _spec(d=2048, bucket=256, mtu=300)
    base, xs, fleets = _fleet(spec, 1)
    frames = fleets[0]
    nc = len(frames)
    assert nc >= 4
    lost = {2, 3}
    # forged stream under the same header but a fabricated payload_crc,
    # missing only index 0 — more complete than the honest stream
    forged = []
    for f in frames[1:]:
        h, chunk = wire.decode_frame(f)
        forged.append(wire.encode_frame(
            dataclasses.replace(h, payload_crc=h.payload_crc ^ 1),
            bytes(len(chunk))))
    c = AggClient(spec, 0, np.asarray(xs[0]))
    server = AggServer(spec, base)
    for f in forged:
        server.receive(f)
    for k, f in enumerate(frames):
        if k not in lost:
            server.receive(f)
    for _ in range(4):                    # RESEND loop must converge
        resend = [rb for rb in server.drain()
                  if wire.decode_response(rb).status == wire.STATUS_RESEND]
        if not resend:
            break
        (rb,) = resend
        assert set(lost) <= set(wire.decode_response(rb).missing)
        for f in c.handle_response(rb):
            server.receive(f)
    assert server.accepted_clients == frozenset({0})
    assert not c.gave_up


def test_fleet_payloads_refuses_chunked_spec():
    spec = _spec(d=2048, bucket=256, mtu=300)
    xs = np.zeros((2, spec.d), np.float32)
    with pytest.raises(ValueError, match="fleet_frames"):
        sim.fleet_payloads(spec, xs)


def test_multi_round_service_runs_chunked():
    """ServiceConfig.mtu threads the chunked transport through the
    anchored multi-round service without losing clients."""
    cfg = sim.MultiRoundConfig(clients=8, d=1024, bucket=128, rounds=2,
                               norm_scale=10.0, y0=1.0, spread0=0.05,
                               mtu=200, seed=0)
    outs = sim.run_rounds(cfg)
    assert [o.accepted for o in outs] == [cfg.clients] * 2
    # bytes_per_client accounts the per-chunk headers
    spec = wire.RoundSpec(round_id=1, d=cfg.d,
                          cfg=QSyncConfig(q=cfg.q, bucket=cfg.bucket),
                          y0=cfg.y0, mtu=cfg.mtu)
    assert outs[0].bytes_per_client == wire.payload_bytes(spec)


def test_payload_crc_seal_failure_is_retryable():
    """Regression (review finding): a forged chunk that shares the honest
    stream's exact header and poisons the body draws a RESEND-all, never a
    terminal REJECT — the honest client rebuilds and is accepted."""
    spec = _spec(d=1024, bucket=128, mtu=200)
    base, xs, fleets = _fleet(spec, 1)
    frames = fleets[0]
    h1, chunk1 = wire.decode_frame(frames[1])
    poisoned = wire.encode_frame(h1, bytes(len(chunk1)))   # garbage body
    c = AggClient(spec, 0, np.asarray(xs[0]))
    server = AggServer(spec, base)
    server.receive(poisoned)              # commits garbage at index 1
    last = None
    for f in frames:                      # honest index 1 drops as dup
        last = server.receive(f)
    r = wire.decode_response(last)
    assert r.status == wire.STATUS_RESEND
    assert r.missing == tuple(range(len(frames)))
    assert server.transport_stats.rejects == 1
    resend = c.handle_response(last)      # not terminal: full rebuild
    assert not c.gave_up and len(resend) == len(frames)
    for f in resend:
        server.receive(f)
    server.drain()
    assert server.accepted_clients == frozenset({0})


def test_escalated_attempt_resets_partial_session():
    """A higher-attempt chunk supersedes a partial lower-attempt session;
    stale lower-attempt chunks afterwards are dropped, not merged."""
    spec = _spec(d=1024, bucket=128, mtu=200)
    base, xs, _ = _fleet(spec, 1)
    c = AggClient(spec, 0, np.asarray(xs[0]))
    f0, f1 = c.frames(0), c.frames(1)
    server = AggServer(spec, base)
    server.receive(f0[0])                      # partial attempt 0
    server.receive(f1[0])                      # escalation supersedes
    r = wire.decode_response(server.receive(f0[1]))   # stale: dropped
    assert r.status == wire.STATUS_QUEUED      # ... but never terminal
    ts = server.transport_stats
    assert ts.resets == 1 and ts.stale == 1
    for f in f1[1:]:
        server.receive(f)
    server.drain()
    assert server.accepted_clients == frozenset({0})
    assert wire.decode_frame(f1[0])[0].q == 256


def test_stale_chunks_cannot_capture_resend_targeting():
    """Regression (review finding): network-duplicated attempt-0 chunks
    arriving after escalation must not open a live stream — an
    out-progressing stale stream would capture the client's RESEND slot
    (attempt_next=0, which the attempt-1 client ignores) and deadlock it
    out of the round."""
    spec = _spec(d=2048, bucket=256, mtu=300)
    base, xs, _ = _fleet(spec, 1)
    c = AggClient(spec, 0, np.asarray(xs[0]))
    f0, f1 = c.frames(0), c.frames(1)
    c.attempt = 1
    server = AggServer(spec, base)
    server.receive(f1[0])                     # attempt-1 partial: 1 chunk
    for f in f0:                              # a full stale replay arrives
        server.receive(f)
    assert server.transport_stats.stale == len(f0)
    resend = [wire.decode_response(rb) for rb in server.drain()]
    assert len(resend) == 1
    assert resend[0].attempt_next == 1        # targets the LIVE attempt
    out = c.handle_response(wire.encode_response(resend[0]))
    assert out                                # client answers; no deadlock
    for f in out:
        server.receive(f)
    server.drain()
    assert server.accepted_clients == frozenset({0})


def test_stale_duplicate_chunk_never_kills_escalating_client():
    """Regression (review finding): a network-duplicated attempt-0 chunk
    arriving after the client escalated must not draw a terminal REJECT —
    the honest client would set gave_up and drop out of the round."""
    spec = _spec(d=1024, bucket=128, mtu=200)
    base, xs, _ = _fleet(spec, 1)
    c = AggClient(spec, 0, np.asarray(xs[0]))
    f0, f1 = c.frames(0), c.frames(1)
    c.attempt = 1                              # escalated (NACK handled)
    server = AggServer(spec, base)
    server.receive(f1[0])                      # attempt-1 reassembly open
    rb = server.receive(f0[0])                 # duplicated stale chunk
    assert c.handle_response(rb) == []
    assert not c.gave_up                       # still in the round
    for f in f1[1:]:
        server.receive(f)
    server.drain()
    assert server.accepted_clients == frozenset({0})


# ---------------------------------------------------------------------------
# Selective retransmit: RESEND carries exactly the missing chunks
# ---------------------------------------------------------------------------

def test_drain_emits_resend_with_missing_indices():
    spec = _spec(d=2048, bucket=256, mtu=300)
    base, xs, fleets = _fleet(spec, 2)
    server = AggServer(spec, base)
    lost = {1, 3}
    for k, f in enumerate(fleets[0]):
        if k not in lost:
            server.receive(f)
    for f in fleets[1]:
        server.receive(f)
    resps = [wire.decode_response(rb) for rb in server.drain()]
    by_status = {r.status for r in resps}
    assert wire.STATUS_ACK in by_status        # client 1 decoded
    resend = [r for r in resps if r.status == wire.STATUS_RESEND]
    assert len(resend) == 1
    assert resend[0].client_id == 0
    assert resend[0].missing == tuple(sorted(lost))
    # the client answers with exactly those frames, nothing more
    c = AggClient(spec, 0, np.asarray(xs[0]))
    out = c.handle_response(wire.encode_response(resend[0]))
    assert [wire.decode_frame(f)[0].chunk_index for f in out] == \
        sorted(lost)
    for f in out:
        server.receive(f)
    server.drain()
    assert server.accepted_clients == frozenset({0, 1})


def test_client_ignores_stale_resend_and_bad_missing():
    spec = _spec(d=1024, bucket=128, mtu=200)
    _, xs, _ = _fleet(spec, 1)
    c = AggClient(spec, 0, np.asarray(xs[0]))
    nc = len(c.frames())

    def resend(attempt_next, missing):
        return wire.encode_response(wire.Response(
            status=wire.STATUS_RESEND, round_id=spec.round_id, client_id=0,
            attempt_next=attempt_next, q_next=16, y_next=1.0,
            missing=missing))

    assert c.handle_response(resend(1, (0,))) == []     # foreign attempt
    # out-of-range indices: fall back to the full (idempotent) sequence
    assert len(c.handle_response(resend(0, (0, nc + 5)))) == nc
    assert len(c.handle_response(resend(0, (2,)))) == 1


def test_run_chunked_lossy_wire_delta():
    """ISSUE 5 satellite: the lossy scenario's wire-byte delta is exactly
    the lost chunks' frames (the asserts live inside run_chunked_lossy)."""
    rep = sim.run_chunked_lossy(clients=6, d=2048, bucket=256, mtu=300,
                                n_drop=2, n_corrupt=1, seed=2)
    assert rep.n_chunks_per_client >= 4
    assert rep.retransmit_bytes == rep.lost_frame_bytes
    assert rep.retransmit_bytes < rep.full_resend_bytes / 3
    assert np.array_equal(rep.mean, rep.mean_clean)


def test_sim_full_failure_mix_chunked():
    """The 512-client acceptance scenario runs chunked too, with the same
    recovery guarantees."""
    cfg = sim.SimConfig(clients=128, d=2048, bucket=256, drop=0.02,
                        duplicate=0.05, straggle=0.25, corrupt=2, truncate=1,
                        adversarial=2, extreme=1, seed=0, mtu=300)
    rep = sim.run_round(cfg)
    n_drop = int(round(cfg.drop * cfg.clients))
    assert len(rep.accepted_clients) == cfg.clients - n_drop - cfg.extreme
    assert len(rep.escalated_clients) == cfg.adversarial
    assert rep.stats.gave_up == cfg.extreme
    assert rep.stats.rejected_wire == cfg.corrupt + cfg.truncate
    assert rep.max_err <= 2 * cfg.y0


# ---------------------------------------------------------------------------
# Session-layer memory: transport staging bounded by one frame, not d
# ---------------------------------------------------------------------------

def test_peak_unvalidated_bytes_bounded_by_mtu_not_d():
    """The transport never stages more than one frame (header + MTU) of
    unvalidated bytes, whatever the vector length — the acceptance bound
    (bench_agg asserts the same across inflight clients at large d)."""
    mtu = 256
    peaks = []
    for d in (1 << 11, 1 << 13):
        spec = _spec(d=d, bucket=256, mtu=mtu)
        base, _, fleets = _fleet(spec, 3)
        server = AggServer(spec, base)
        # worst-case interleave: every client's session open at once
        for k in range(len(fleets[0])):
            for fs in fleets:
                server.receive(fs[k])
        server.drain()
        assert server.accepted_clients == frozenset(range(3))
        peaks.append(server.stats.peak_unvalidated_bytes)
        assert server.stats.peak_unvalidated_bytes <= \
            WA.FRAME_HEADER_BYTES + mtu
    assert peaks[0] == peaks[1]                 # independent of d
    # v2's monolithic frame would have staged the whole payload
    assert peaks[0] < wire.payload_bytes(_spec(d=1 << 13, bucket=256,
                                               mtu=0)) / 10


def test_reassembly_buffer_accounting():
    spec = _spec(d=2048, bucket=256, mtu=300)
    base, _, fleets = _fleet(spec, 2)
    server = AggServer(spec, base)
    body = spec.body_bytes()
    server.receive(fleets[0][0])
    ts = server.transport_stats
    assert ts.buffer_bytes == body              # one open session
    server.receive(fleets[1][0])
    assert ts.buffer_bytes == 2 * body
    for f in fleets[0][1:]:
        server.receive(f)
    assert ts.buffer_bytes == body              # client 0 completed
    assert ts.peak_buffer_bytes == 2 * body


# ---------------------------------------------------------------------------
# Response codec v3 (missing list) and facade compatibility
# ---------------------------------------------------------------------------

def test_response_roundtrip_with_missing():
    r = wire.Response(status=wire.STATUS_RESEND, round_id=7, client_id=12,
                      attempt_next=1, q_next=256, y_next=3.5,
                      y_buckets=(1.0, 2.0), missing=(0, 5, 7))
    data = wire.encode_response(r)
    assert wire.decode_response(data) == r
    assert len(data) == WA.RESPONSE_HEAD_BYTES + 4 * 2 + 4 * 3 + 4
    bad = bytearray(data)
    bad[10] ^= 0xFF
    with pytest.raises(wire.CorruptPayloadError):
        wire.decode_response(bytes(bad))


def test_v2_frames_are_refused():
    """Migration contract: a v2 (version=2) frame gets a clean
    VersionMismatchError, never a silent partial parse."""
    spec = _spec(mtu=0, d=512, bucket=64)
    data = bytearray(AggClient(spec, 1, np.zeros(512, np.float32)).payload())
    data[4:6] = struct.pack("<H", 2)
    with pytest.raises(wire.VersionMismatchError):
        wire.decode_payload(bytes(data))


def test_wire_facade_is_removed():
    """The deprecated ``repro.agg.wire`` facade is GONE (its deprecation
    window closed in this wire revision): importing it must fail loudly,
    and the layered transport remains the one surface."""
    import importlib
    import sys

    sys.modules.pop("repro.agg.wire", None)      # force a fresh import
    with pytest.raises(ModuleNotFoundError):
        importlib.import_module("repro.agg.wire")
    assert wire.WIRE_VERSION == 5
    assert C.encode_chunks is not None and S.Reassembler is not None
    # single-frame chunk encode is byte-identical to encode_payload
    spec = _spec(mtu=0, d=512, bucket=64)
    w = np.arange(L.packed_len(spec.padded, 4), dtype=np.uint32)
    sides = spec.sides_np()
    a = wire.encode_payload(spec, 3, 0, 16, w, sides, 99)
    b = C.encode_chunks(spec, 3, 0, 16, w, sides, 99)
    assert b == [a]
    crc = zlib.crc32(a)                       # exports stay live
    assert isinstance(crc, int) and rounds is not None and F is not None


# ---------------------------------------------------------------------------
# Streaming decode + windowed flow control (v5)
# ---------------------------------------------------------------------------

def test_response_ack_credit_roundtrip():
    """The v5 additive flow-control fields survive the codec and default
    to zero (a v4-shaped response decodes with ack=credit=0)."""
    r = wire.Response(status=wire.STATUS_QUEUED, round_id=7, client_id=3,
                      attempt_next=0, q_next=16, y_next=0.5,
                      missing=(1, 4), y_buckets=(0.5, 0.25),
                      ack=2, credit=4)
    got = wire.decode_response(wire.encode_response(r))
    assert (got.ack, got.credit) == (2, 4)
    assert got == r
    plain = wire.Response(status=wire.STATUS_ACK, round_id=7, client_id=3,
                          attempt_next=0, q_next=0, y_next=0.0)
    got = wire.decode_response(wire.encode_response(plain))
    assert (got.ack, got.credit) == (0, 0)


def test_roundspec_window_requires_mtu():
    with pytest.raises(ValueError):
        _spec(mtu=0, window=4)
    with pytest.raises(ValueError):
        _spec(window=-1)
    assert _spec(window=4).window == 4


def test_streaming_bit_parity_any_permutation_with_duplicates():
    """Property (the tentpole's correctness gate): the streaming server's
    published mean is bit-identical to the SEALED batched-decode drain
    under any chunk arrival permutation, duplicate storms included — and
    its pending store never approaches one body per in-flight client."""
    spec = _spec(d=2048, bucket=256, mtu=300, window=3)
    base, _, fleets = _fleet(spec, 4)
    sealed = AggServer(spec, base, streaming=False)
    for fs in fleets:
        for f in fs:
            sealed.receive(f)
    mean_ref, _ = sealed.finalize()
    body = spec.body_bytes()
    assert sealed.stats.peak_pending_store_bytes >= 4 * body  # one body each
    flat = [f for fs in fleets for f in fs]
    for trial in range(6):
        rng = np.random.RandomState(trial)
        order = list(rng.permutation(len(flat)))
        if trial % 2:                        # duplicate storm
            order += list(rng.choice(len(flat), len(flat)))
        server = AggServer(spec, base)       # window>0 => streaming on
        assert server._streaming
        for i in order:
            server.receive(flat[i])
        server.drain()
        mean, stats = server.finalize()
        assert server.accepted_clients == frozenset(range(4)), trial
        assert np.array_equal(mean.view(np.uint32),
                              mean_ref.view(np.uint32)), trial
        # chunk bytes are freed as ranges fold: even under an adversarial
        # arrival permutation (held out-of-order chunks can approach one
        # body) the store stays strictly below the sealed path's staged
        # bodies; the windowed mostly-in-order regime — where it drops to
        # ~one chunk — is pinned by the loop test below and the bench's
        # < 0.5x gate
        assert stats.peak_pending_store_bytes < \
            sealed.stats.peak_pending_store_bytes, \
            (trial, stats.peak_pending_store_bytes)


def test_streaming_seal_failure_rolls_back_speculative_fold():
    """A stream whose payload-CRC seal fails (forged body byte under a
    recomputed frame CRC) must contribute NOTHING: the speculative fold is
    dropped, the client is RESENT the whole sequence, and the rebuilt
    stream commits a mean bit-identical to the clean round."""
    spec = _spec(d=1024, bucket=128, mtu=200, window=2)
    base, _, fleets = _fleet(spec, 2)
    clean = AggServer(spec, base, streaming=False)
    for fs in fleets:
        for f in fs:
            clean.receive(f)
    mean_ref, _ = clean.finalize()
    h1, chunk1 = wire.decode_frame(fleets[0][1])
    forged_body = bytearray(chunk1)
    forged_body[3] ^= 0xFF
    forged = wire.encode_frame(h1, bytes(forged_body))  # valid frame CRC,
    server = AggServer(spec, base)                      # lying body
    server.receive(fleets[0][0])
    server.receive(forged)
    for f in fleets[0][2:]:
        r = wire.decode_response(server.receive(f))
    # stream complete but seal failed: RESEND everything, nothing folded
    assert r.status == wire.STATUS_RESEND
    assert tuple(r.missing) == tuple(range(len(fleets[0])))
    assert r.credit == spec.window
    assert not server._folds                 # speculative record dropped
    assert server.accepted_clients == frozenset()
    for f in fleets[0]:                      # honest rebuild commits
        server.receive(f)
    for f in fleets[1]:
        server.receive(f)
    server.drain()
    mean, _ = server.finalize()
    assert server.accepted_clients == frozenset(range(2))
    assert np.array_equal(mean.view(np.uint32), mean_ref.view(np.uint32))


def test_streaming_mid_stream_escalation_resets_fold():
    """Chunks of a half-delivered attempt are abandoned when the client
    escalates: the session discards the stale stream, the stream-fold
    rollback fires, and the escalated attempt alone is committed —
    bit-identical to the clean round (coordinates are attempt-invariant)."""
    spec = _spec(d=1024, bucket=128, mtu=200, window=2)
    base, xs, fleets = _fleet(spec, 1)
    clean = AggServer(spec, base, streaming=False)
    for f in fleets[0]:
        clean.receive(f)
    mean_ref, _ = clean.finalize()
    c = AggClient(spec, 0, xs[0])
    a0, a1 = c.frames(0), c.frames(1)
    server = AggServer(spec, base)
    for f in a0[: len(a0) // 2]:             # half of attempt 0 ...
        server.receive(f)
    assert server._folds                     # speculative fold is open
    for f in a1:                             # ... then the escalation
        server.receive(f)
    server.drain()
    assert server.accepted_clients == frozenset({0})
    # only the attempt-1 stream's record remains committed; the abandoned
    # attempt-0 fold was dropped by the discard callback
    assert not server._folds
    mean, _ = server.finalize()
    assert np.array_equal(mean.view(np.uint32), mean_ref.view(np.uint32))


def test_streaming_fold_reads_back_once_per_update():
    """The streaming fold keeps each record on the device: a lossless
    streamed round makes exactly one blocking readback per accepted update
    (the commit's verdict), however many ranges each stream folds; the
    sealed drain makes none."""
    spec = _spec(d=2048, bucket=256, mtu=300, window=2)
    base, _, fleets = _fleet(spec, 5)
    assert len(fleets[0]) > 2
    for streaming, syncs in ((True, 5), (False, 0)):
        server = AggServer(spec, base, streaming=streaming)
        for fs in fleets:
            for f in fs:
                server.receive(f)
        _, stats = server.finalize()
        assert stats.accepted == 5
        assert stats.fold_syncs == syncs, streaming


def test_streaming_distance_telemetry_matches_sealed_drain():
    """The streamed commit computes the per-bucket distance telemetry by
    the sealed drain's own expression: ``dist_b`` and ``max_dist`` agree
    with the batched decode's for the same payloads, to float32
    rounding."""
    spec = _spec(d=2048, bucket=256, mtu=300, window=2)
    base, _, fleets = _fleet(spec, 4, spread=0.2)
    out = {}
    for streaming in (True, False):
        server = AggServer(spec, base, streaming=streaming)
        for fs in fleets:
            for f in fs:
                server.receive(f)
        _, out[streaming] = server.finalize()
    st, se = out[True], out[False]
    assert st.accepted == se.accepted == 4
    assert se.max_dist > 0
    np.testing.assert_allclose(st.dist_b, se.dist_b, rtol=1e-6, atol=0)
    np.testing.assert_allclose(st.max_dist, se.max_dist, rtol=1e-6, atol=0)
    assert not st.fails_b.any() and not se.fails_b.any()


def test_send_window_paces_and_counts_stalls():
    """SendWindow unit behavior: at most ``window`` in flight, cumulative
    acks release more, RESENDs below the sent prefix are the lost set,
    and a response that releases nothing counts a stall."""
    frames = [bytes([i]) * 8 for i in range(5)]
    w = C.SendWindow(frames, 2)
    assert w.sendable() == frames[:2] and w.in_flight == 2
    assert w.sendable() == [] and w.stalls == 1      # blocked: no credit
    w.note_ack(1)
    assert w.sendable() == [frames[2]]
    w.note_ack(1)                                     # stale ack: no rewind
    assert w.ack == 1 and w.unacked() == frames[1:3]
    w.note_ack(3)
    assert w.sendable() == frames[3:5]
    assert w.done and w.sendable() == []              # done: no stall
    assert w.stalls == 1


def test_windowed_client_loop_lossy_bit_parity():
    """End-to-end windowed rounds under loss: credit-paced clients against
    the streaming server converge via ack/credit + RESEND + timeout
    recovery, exercise window stalls, and publish a mean bit-identical to
    the sealed drain over the same accepted clients."""
    spec = _spec(d=2048, bucket=256, mtu=300, window=2)
    base, xs, fleets = _fleet(spec, 6)
    rng = np.random.RandomState(5)
    server = AggServer(spec, base)
    clients = [AggClient(spec, cid, xs[cid]) for cid in range(6)]
    outbox = [(c, f) for c in clients for f in c.send_frames()]
    for step in range(300):
        nxt = []
        for c, f in outbox:
            if rng.rand() < 0.25:
                continue                     # lost on the wire
            rb = server.receive(f)
            nxt.extend((c, g) for g in c.handle_response(rb))
        outbox = nxt
        if all(c.acked for c in clients):
            break
        if not outbox:                       # quiet: timeout recovery
            for c in clients:
                rr = server.resend_request(c.client_id)
                if rr is not None:
                    outbox.extend((c, g) for g in c.handle_response(rr))
                else:
                    outbox.extend((c, f) for f in c.retransmit_frames())
    assert all(c.acked for c in clients), \
        [c.client_id for c in clients if not c.acked]
    assert sum(c.window_stalls for c in clients) > 0
    server.drain()
    mean, stats = server.finalize()
    acc = server.accepted_clients
    assert acc == frozenset(range(6))
    sealed = AggServer(spec, base, streaming=False)
    for cid in sorted(acc):
        for f in fleets[cid]:
            sealed.receive(f)
    mean_ref, _ = sealed.finalize()
    assert np.array_equal(mean.view(np.uint32), mean_ref.view(np.uint32))
    # the DRAINED state carries no body-sized backlog in streaming mode
    assert stats.peak_pending_store_bytes < spec.body_bytes() * 6
