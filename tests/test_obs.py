"""repro.obs (ISSUE 8): metrics registry semantics, the np.percentile-exact
quantile, span-tree causal completeness over the real engine/tree paths,
exporter round-trips (Chrome trace JSON, Prometheus text), the flight
recorder's bounded ring on an injected saturation REJECT, and the
registry-backed DISPATCH_COUNTS / RoundStats views.

Everything here must also hold with observability DISABLED (the default):
the last test class asserts the off-path stays dark — no spans, no global
instruments — while stats accounting is unchanged.
"""
import json

import numpy as np
import pytest

import repro.obs as obs
from repro.agg.server import AggServer
from repro.agg.sim import (OpenLoopConfig, fleet_frames, fleet_payloads,
                           run_open_loop)
from repro.agg.transport import frame as wire
from repro.agg.tree import AggTree
from repro.dist.collectives import QSyncConfig
from repro.kernels import ops as K
from repro.obs import (Counter, FlightRecorder, Histogram, Registry, Tracer,
                       check_round, quantile)


@pytest.fixture(autouse=True)
def _obs_clean():
    """Every test starts and ends with observability off and empty."""
    obs.disable()
    obs.reset()
    yield
    obs.disable()
    obs.reset()


def _spec(round_id=1, d=256, bucket=64, q=16, seed=0, max_attempts=4,
          mtu=0, window=0):
    return wire.RoundSpec(round_id=round_id, d=d,
                          cfg=QSyncConfig(q=q, bucket=bucket), y0=0.5,
                          seed=seed, max_attempts=max_attempts, mtu=mtu,
                          window=window)


def _fleet(spec, n, seed=0):
    rng = np.random.RandomState(seed)
    base = rng.randn(spec.d).astype(np.float32)
    xs = base[None] + 0.02 * rng.randn(n, spec.d).astype(np.float32)
    return base, xs


# ---------------------------------------------------------------- registry

class TestRegistry:
    def test_get_or_create_identity(self):
        reg = Registry()
        c1 = reg.counter("hits", path="a")
        c2 = reg.counter("hits", path="a")
        assert c1 is c2
        assert reg.counter("hits", path="b") is not c1
        c1.inc(); c1.inc(3)
        assert reg.value("hits", path="a") == 4
        assert reg.value("hits", path="b") == 0

    def test_kind_clash_raises(self):
        reg = Registry()
        reg.counter("x")
        with pytest.raises(TypeError):
            reg.gauge("x")

    def test_reset_preserves_identity(self):
        reg = Registry()
        c = reg.counter("n")
        c.inc(5)
        reg.reset()
        assert c.value == 0
        assert reg.counter("n") is c     # same object, zeroed in place

    def test_gauge_set_max(self):
        reg = Registry()
        g = reg.gauge("peak")
        g.set_max(3.0); g.set_max(1.0); g.set_max(7.0)
        assert g.value == 7.0

    def test_histogram_merge(self):
        a = Histogram.from_values([1.0, 2.0, 3.0])
        b = Histogram.from_values([4.0, 5.0])
        a.merge(b)
        assert a.count == 5
        assert a.total == 15.0
        assert a.vmin == 1.0 and a.vmax == 5.0
        assert a.quantile(50) == 3.0

    def test_disabled_returns_noop(self):
        assert not obs.enabled()
        c = obs.counter("dark")
        assert c is obs.NOOP
        c.inc(100)                       # swallowed, never registered
        assert obs.registry().value("dark") is None

    def test_enabled_returns_live(self):
        obs.enable(trace=False, record=False)
        obs.counter("lit").inc(2)
        assert obs.registry().value("lit") == 2


# ---------------------------------------------------------------- quantile

class TestQuantile:
    def test_matches_np_percentile_exactly(self):
        rng = np.random.RandomState(7)
        for n in (1, 2, 3, 7, 100, 999):
            vals = rng.randn(n).tolist()
            for p in (0, 10, 50, 90, 99, 100):
                assert quantile(vals, p) == float(np.percentile(vals, p)), \
                    (n, p)

    def test_matches_np_median(self):
        rng = np.random.RandomState(1)
        for n in (1, 4, 5, 1000):
            vals = rng.randn(n).tolist()
            assert quantile(vals, 50) == pytest.approx(
                float(np.median(vals)), abs=1e-12)

    def test_histogram_exact_below_reservoir_cap(self):
        rng = np.random.RandomState(3)
        vals = rng.randn(500).tolist()
        h = Histogram.from_values(vals)
        assert h.exact
        for p in (50, 99):
            assert h.quantile(p) == float(np.percentile(vals, p))

    def test_histogram_interpolates_beyond_cap(self):
        rng = np.random.RandomState(4)
        vals = np.abs(rng.randn(10_000)).tolist()
        h = Histogram.from_values(vals)
        assert not h.exact
        # bucket interpolation: right order of magnitude, monotone in p
        p50, p99 = h.quantile(50), h.quantile(99)
        assert 0 < p50 < p99 <= h.vmax
        assert abs(p50 - float(np.percentile(vals, 50))) < 0.25


# -------------------------------------------------------------- span trees

class TestSpanTrees:
    def test_flat_round_complete(self):
        obs.enable()
        spec = _spec()
        base, xs = _fleet(spec, 6)
        server = AggServer(spec, base)
        for p in fleet_payloads(spec, xs):
            server.receive(p)
        server.drain()
        server.finalize()
        problems = check_round(obs.tracer(), spec.round_id,
                               accepted=sorted(server.accepted_clients))
        assert problems == []

    def test_check_round_flags_missing_client(self):
        obs.enable()
        spec = _spec()
        base, xs = _fleet(spec, 4)
        server = AggServer(spec, base)
        for p in fleet_payloads(spec, xs):
            server.receive(p)
        server.drain()
        server.finalize()
        ghost = 999
        problems = check_round(obs.tracer(), spec.round_id,
                               accepted=[ghost])
        assert any(f"client {ghost}" in p for p in problems)

    def test_check_round_no_round_span(self):
        assert check_round(Tracer(), 42) == ["round 42: no round span"]

    def test_tree_round_complete_with_fold(self):
        obs.enable()
        spec = _spec(round_id=7, seed=3)
        base, xs = _fleet(spec, 12, seed=3)
        tree = AggTree(spec, base, fanout=4, tiers=1)
        for p in fleet_payloads(spec, xs):
            tree.ingest_frame(p)
        tree.tick()
        tree.seal()
        for _ in range(8):
            tree.tick()
            if tree.published():
                break
        pt = tree.published()[0]
        assert len(pt.accepted) == 12
        problems = check_round(obs.tracer(), spec.round_id,
                               accepted=pt.accepted, require_fold=True)
        assert problems == []

    @pytest.mark.slow
    def test_open_loop_every_round_complete(self):
        # reduced offered load, IDENTICAL shapes (d/bucket/mtu) to the
        # bench config so the jit caches are shared across the suite
        cfg = OpenLoopConfig(rate=60.0, duration=0.25, flash_at=(),
                             adversarial=0, churn_frac=0.0,
                             straggle_frac=0.1, loss=0.02)
        obs.enable()
        rep = run_open_loop(cfg, check_parity=False)
        assert rep.rounds >= 2
        tr = obs.tracer()
        for pr in rep.published:
            assert check_round(tr, pr.round_id, accepted=pr.accepted) == []
        # span times are the sim's virtual event times, not wall time
        root = tr.get(("round", rep.published[0].round_id))
        assert root.end is not None and root.end <= 10.0

    def test_virtual_clock_monotonic(self):
        tr = Tracer()
        tr.feed_time(5.0)
        tr.feed_time(2.0)                # stale feed: ignored
        assert tr.now() == 5.0

    def test_end_idempotent(self):
        tr = Tracer()
        sp = tr.begin("r", key=("round", 1))
        tr.feed_time(1.0)
        tr.end(("round", 1))
        tr.feed_time(2.0)
        tr.end(("round", 1))             # second end is a no-op
        assert sp.end == 1.0


# ------------------------------------------------------ obs.span entry point

class _FakeTraceMe:
    """Stands in for jax.profiler.TraceAnnotation: records each region
    built while 'collecting' is set."""
    collecting = False
    built: list = []

    def __init__(self, name):
        _FakeTraceMe.built.append(name)

    @classmethod
    def is_enabled(cls):
        return cls.collecting

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


@pytest.fixture
def fake_profiler(monkeypatch):
    monkeypatch.setattr(_FakeTraceMe, "built", [])
    monkeypatch.setattr(_FakeTraceMe, "collecting", False)
    monkeypatch.setattr(obs, "_TraceMe", _FakeTraceMe)
    return _FakeTraceMe


class TestSpanEntryPoint:
    def test_off_returns_the_shared_noop(self, fake_profiler):
        assert obs.span("agg.parse") is obs.NO_REGION
        assert obs.span("agg.drain", "drain", round=1) is obs.NO_REGION
        with obs.span("agg.drain", "drain") as region:
            region.note(accepted=3)
        assert fake_profiler.built == [] and obs.tracer().spans == []

    def test_tracer_span_under_its_own_name(self, fake_profiler):
        obs.enable()
        with obs.span("agg.drain", "drain", parent=("round", 4),
                      round=4) as region:
            region.note(accepted=2)
        # a region with no tracer name stays off the tracer
        assert obs.span("agg.parse") is obs.NO_REGION
        (root, sp) = obs.tracer().spans
        assert root.name == "round" and sp.name == "drain"
        assert sp.parent_id == root.span_id and sp.end is not None
        assert sp.attrs == {"round": 4, "accepted": 2}
        assert fake_profiler.built == []

    def test_profiler_annotation_beside_the_tracer_span(self, fake_profiler):
        fake_profiler.collecting = True
        with obs.span("agg.parse"):
            pass
        assert obs.tracer().spans == []
        obs.enable()
        with obs.span("agg.drain", "drain", key=("drain", 1)):
            pass
        assert fake_profiler.built == ["agg.parse", "agg.drain"]
        assert obs.tracer().get(("drain", 1)).end is not None

    @pytest.mark.parametrize("streaming", [True, False],
                             ids=["streaming", "sealed"])
    def test_chunked_round_complete_with_profiler_off(self, fake_profiler,
                                                      streaming):
        obs.enable()
        spec = _spec(round_id=11, d=2048, bucket=256, mtu=300, window=2)
        base, xs = _fleet(spec, 3)
        server = AggServer(spec, base, streaming=streaming)
        for frames in fleet_frames(spec, xs):
            for f in frames:
                server.ingest_frame(f)
        server.seal()
        server.tick()
        pub = server.published()
        assert pub and len(pub[0].accepted) == 3
        assert check_round(obs.tracer(), spec.round_id,
                           accepted=pub[0].accepted) == []
        assert fake_profiler.built == []


# --------------------------------------------------------------- exporters

class TestExporters:
    def _traced_round(self):
        obs.enable()
        spec = _spec()
        base, xs = _fleet(spec, 4)
        server = AggServer(spec, base)
        for p in fleet_payloads(spec, xs):
            server.receive(p)
        server.drain()
        server.finalize()
        return spec, server

    def test_chrome_trace_schema(self):
        self._traced_round()
        events = json.loads(obs.export.chrome_trace(obs.tracer()))
        assert isinstance(events, list) and events
        phases = {e["ph"] for e in events}
        assert "X" in phases and "i" in phases
        for e in events:
            assert isinstance(e["name"], str)
            if e["ph"] == "X":
                assert e["dur"] >= 0
                assert "ts" in e
            if e["ph"] == "i":
                assert e["s"] == "t"

    def test_chrome_trace_no_orphans(self):
        self._traced_round()
        events = json.loads(obs.export.chrome_trace(obs.tracer()))
        ids = {e["args"]["span_id"] for e in events
               if e["ph"] in ("X", "i")}
        for e in events:
            if e["ph"] not in ("X", "i"):
                continue
            parent = e["args"].get("parent_id")
            assert parent is None or parent in ids, e

    def test_chrome_trace_nesting_balanced(self):
        # every complete event must fit inside its parent's time range
        self._traced_round()
        tr = obs.tracer()
        by_id = {s.span_id: s for s in tr.spans}
        for s in tr.spans:
            assert s.end is not None, s          # all closed after finalize
            if s.parent_id is not None:
                p = by_id[s.parent_id]
                assert p.start <= s.start and s.end <= p.end, (s, p)

    def test_prometheus_round_trip(self):
        obs.enable(trace=False, record=False)
        obs.counter("rx_total", path="frame").inc(7)
        obs.gauge("peak_bytes").set(123.5)
        h = obs.histogram("lat_s")
        for v in (0.01, 0.02, 0.5):
            h.observe(v)
        text = obs.export.prometheus_text(obs.registry())
        assert "# TYPE rx_total counter" in text
        parsed = obs.export.parse_prometheus_text(text)
        assert parsed[("rx_total", (("path", "frame"),))] == 7.0
        assert parsed[("peak_bytes", ())] == 123.5
        assert parsed[("lat_s_count", ())] == 3.0
        assert parsed[("lat_s_sum", ())] == pytest.approx(0.53)
        # cumulative buckets: the +Inf bucket equals the count
        assert parsed[("lat_s_bucket", (("le", "+Inf"),))] == 3.0

    def test_prometheus_label_values_quoted(self):
        obs.enable(trace=False, record=False)
        obs.counter("x", round=1).inc(4)
        parsed = obs.export.parse_prometheus_text(
            obs.export.prometheus_text(obs.registry()))
        assert parsed[("x", (("round", "1"),))] == 4.0


# ---------------------------------------------------------- flight recorder

class TestFlightRecorder:
    def test_ring_keeps_exactly_last_n(self):
        rec = FlightRecorder(capacity=4)
        for i in range(7):
            rec.record({"i": i})
        dump = rec.trigger("unit_test", at=1.0)
        assert [e["i"] for e in dump.events] == [3, 4, 5, 6]
        assert dump.reason == "unit_test"
        assert rec.last_dump() is dump

    def test_saturation_reject_dumps_last_n(self):
        # individually-decodable payloads (max|k| ~ 5 < q_max/2 = 8) whose
        # tier fold exceeds the escalation cap's coordinate range: the
        # second child at each tier draws a saturation REJECT, which must
        # trigger a flight-recorder dump holding exactly the last N spans
        cap = 4
        obs.enable(recorder_capacity=cap)
        spec = _spec(round_id=9, d=64, max_attempts=1)
        base = np.zeros(64, dtype=np.float32)
        xs = np.full((4, 64), 0.3, dtype=np.float32)
        tree = AggTree(spec, base, fanout=2, tiers=1)
        for p in fleet_payloads(spec, xs):
            tree.ingest_frame(p)
        tree.tick()
        tree.seal()
        for _ in range(8):
            tree.tick()
        dump = obs.recorder().last_dump()
        assert dump is not None
        assert dump.reason == "saturation_reject"
        assert dump.attrs["round"] == spec.round_id
        assert len(dump.events) == cap
        # the tier kept folding after the dump: saturated stat recorded
        pubs = tree.published()
        assert pubs and len(pubs[0].accepted) == 2

    def test_trigger_noop_when_disabled(self):
        assert obs.trigger("anything", at=0.0) is None
        assert obs.recorder().last_dump() is None


# -------------------------------------------------- registry-backed views

class TestDispatchCounts:
    def test_dict_view(self):
        K.reset_dispatch_counts()
        assert dict(K.DISPATCH_COUNTS.items()) == {
            "lattice_decode": 0, "lattice_decode_batched": 0}
        assert K.DISPATCH_COUNTS == {"lattice_decode": 0,
                                     "lattice_decode_batched": 0}
        assert "lattice_decode" in K.DISPATCH_COUNTS
        assert K.DISPATCH_COUNTS.get("nope", -1) == -1
        assert len(K.DISPATCH_COUNTS) == 2
        assert set(K.DISPATCH_COUNTS) == set(K.DISPATCH_COUNTS.keys())

    def test_counts_survive_registry_reset(self):
        # ops.py caches the Counter objects at import; the registry hands
        # back the SAME instrument for the same (name, labels), and
        # Registry.reset() zeroes it in place instead of orphaning it
        K.reset_dispatch_counts()
        c = obs.registry().counter("kernel_dispatch",
                                   kernel="lattice_decode_batched")
        c.inc(3)
        assert K.DISPATCH_COUNTS["lattice_decode_batched"] == 3
        obs.registry().reset()
        assert K.DISPATCH_COUNTS["lattice_decode_batched"] == 0
        c.inc()
        assert K.DISPATCH_COUNTS["lattice_decode_batched"] == 1
        K.reset_dispatch_counts()


class TestStatsFromRegistry:
    def test_round_stats_match_registry(self):
        obs.enable(trace=False, record=False)
        spec = _spec(round_id=5)
        base, xs = _fleet(spec, 6)
        server = AggServer(spec, base)
        for p in fleet_payloads(spec, xs):
            server.receive(p)
        server.drain()
        server.finalize()
        st = server.stats
        assert st.received == 6
        assert st.accepted == 6
        # the same numbers are readable straight off the global registry
        vals = {i.name: i.value for i in obs.registry().instruments()
                if i.name.startswith("agg_round_")
                and i.labels.get("round") == spec.round_id}
        assert vals.get("agg_round_received") == 6
        assert vals.get("agg_round_accepted") == 6
        assert vals.get("agg_round_bytes_in", 0) > 0

    def test_stats_identical_when_disabled(self):
        # scopes fall back to a detached registry: accounting unchanged
        spec = _spec(round_id=6)
        base, xs = _fleet(spec, 5)
        server = AggServer(spec, base)
        for p in fleet_payloads(spec, xs):
            server.receive(p)
        server.drain()
        server.finalize()
        assert server.stats.received == 5
        assert server.stats.accepted == 5
        # the global registry never saw this round's scope
        assert not any(i.name.startswith("agg_round_")
                       and i.labels.get("round") == spec.round_id
                       for i in obs.registry().instruments())


# ------------------------------------------------------- disabled-by-default

class TestDisabledByDefault:
    def test_off_path_stays_dark(self):
        assert not obs.enabled()
        spec = _spec(round_id=8)
        base, xs = _fleet(spec, 4)
        server = AggServer(spec, base)
        for p in fleet_payloads(spec, xs):
            server.receive(p)
        server.drain()
        server.finalize()
        assert obs.tracer().spans == []
        assert obs.recorder().snapshot() == []
        assert not any(i.labels.get("round") == spec.round_id
                       for i in obs.registry().instruments())
