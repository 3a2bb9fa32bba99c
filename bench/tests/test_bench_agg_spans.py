"""The aggregation server's phase spans: their reduction on a synthetic
nested trace, and a small chunked round under the JAX profiler on the CPU,
whose trace must hold every phase, each nested under its parent."""
import numpy as np
import pytest

from bench import agg_spans as A
from bench import xplane as X


def nested():
    """One frame's receive with a fold inside reassembly, then a drain."""
    d0 = X.DeviceOps(0, [("fusion", 30, 40), ("fusion", 150, 160)])
    spans = [("bench.window", 0, 200), ("bench.receive", 0, 100),
             ("bench.drain", 120, 200)]
    agg = [("agg.parse", 2, 10), ("agg.reassemble", 10, 90),
           ("agg.fold", 20, 80), ("agg.fold.residuals", 25, 45),
           ("agg.fold", 82, 88), ("agg.fold.residuals", 83, 85),
           ("agg.commit", 90, 98),
           ("agg.drain", 125, 195), ("agg.stage", 126, 140),
           ("agg.decode", 140, 190)]
    return X.Trace([d0], spans, (0, 200)), agg


def test_self_ns_and_span_count():
    base, agg = nested()
    tr = X.Trace(base.devices, base.spans + agg, base.window)
    # agg.fold 20-80 holds residuals 25-45, agg.fold 82-88 holds 83-85
    assert A.self_ns(tr, "agg.fold") == (60 - 20) + (6 - 2)
    # reassembly's nested folds cover 20-80 and 82-88 of its 10-90
    assert A.self_ns(tr, "agg.reassemble") == 80 - 60 - 6
    assert A.self_ns(tr, "agg.fold.residuals") == 22
    assert A.span_count(tr, "agg.fold.residuals") == 2
    assert A.span_count(tr, "agg.commit") == 1
    assert A.span_count(tr, "agg.missing") == 0
    # clipped to the window like span_ns
    short = X.Trace(tr.devices, tr.spans, (0, 50))
    assert A.self_ns(short, "agg.fold") == 30 - 20
    assert A.span_count(short, "agg.fold") == 1


def test_server_spans_leave_the_benchmark_readings_alone():
    base, agg = nested()
    tr = X.Trace(base.devices, base.spans + agg, base.window)
    for name in ("bench.receive", "bench.drain", "bench.window"):
        assert tr.span_ns(name) == base.span_ns(name)
    assert tr.idle_share() == base.idle_share()
    assert tr.busy_ns() == base.busy_ns()
    # the gaps 0-30, 40-150 and 160-200 are charged at their midpoints
    # to the innermost open span: now a server phase
    assert dict(base.idle_gaps()) == {"bench.receive": 140e-9,
                                      "bench.drain": 40e-9}
    assert dict(tr.idle_gaps()) == {"agg.commit": 110e-9,
                                    "agg.decode": 40e-9,
                                    "agg.reassemble": 30e-9}


def test_readings_and_coverage():
    base, agg = nested()
    tr = X.Trace(base.devices, base.spans + agg, base.window)
    got = A.readings(tr, {"updates": 2, "rounds": 1})
    assert got["parse_ms_per_update"] == pytest.approx(8 / 1e6 / 2)
    assert got["fold_host_ms_per_update"] == pytest.approx(44 / 1e6 / 2)
    assert got["fold_dispatches_per_update"] == 1
    assert got["decode_ms_per_round"] == pytest.approx(50 / 1e6)
    cov = A.coverage(tr)
    assert cov["bench.receive"]["share"] == pytest.approx(96 / 100)
    assert cov["bench.drain"]["share"] == pytest.approx(64 / 80)
    assert A.readings(base, {"updates": 2, "rounds": 1}) == {}


# Each server phase and the innermost server phases it may run in.
PARENTS = {
    True: {"agg.parse": {None}, "agg.reassemble": {None},
           "agg.fold": {"agg.reassemble"}, "agg.fold.residuals": {"agg.fold"},
           "agg.commit": {None}, "agg.respond": {None}},
    False: {"agg.parse": {None}, "agg.reassemble": {None},
            "agg.drain": {None}, "agg.stage": {"agg.drain"},
            "agg.decode": {"agg.drain"}, "agg.respond": {None, "agg.drain"}},
}


def chunked_round(streaming: bool):
    """A small chunked round, every frame delivered in order; returns the
    number of frames."""
    from repro.agg.server import AggServer
    from repro.agg.sim import fleet_frames
    from repro.agg.transport import frame as wire
    from repro.dist.collectives import QSyncConfig
    spec = wire.RoundSpec(round_id=3, d=2048, cfg=QSyncConfig(q=16,
                          bucket=256), y0=0.5, seed=5, mtu=300, window=2)
    rng = np.random.RandomState(0)
    base = rng.randn(spec.d).astype(np.float32)
    xs = base[None] + 0.02 * rng.randn(3, spec.d).astype(np.float32)
    server = AggServer(spec, base, streaming=streaming)
    frames = [f for fr in fleet_frames(spec, xs) for f in fr]
    for f in frames:
        server.ingest_frame(f)
    server.seal()
    server.tick()
    assert server.published()
    assert server.accepted_clients == frozenset(range(3))
    return len(frames)


def innermost_parents(spans):
    """(name, name of the innermost enclosing server span or None) of each
    server span; the spans come from one thread, so they nest."""
    out, stack = [], []
    for n, a, b in sorted(spans, key=lambda s: (s[1], -s[2])):
        while stack and stack[-1][2] <= a:
            stack.pop()
        out.append((n, stack[-1][0] if stack else None))
        stack.append((n, a, b))
    return out


@pytest.mark.parametrize("streaming", [True, False],
                         ids=["streaming", "sealed"])
def test_round_under_the_profiler(streaming):
    import shutil

    from jax.profiler import TraceAnnotation

    from bench import harness
    chunked_round(streaming)      # compile outside the trace
    with harness.profiled(True) as prof:
        with TraceAnnotation("bench.window"):
            n_frames = chunked_round(streaming)
    tr = A.load(prof.path)
    shutil.rmtree(prof.dir, ignore_errors=True)
    agg = [s for s in tr.spans if s[0].startswith(A.PREFIX)]
    assert {n for n, _, _ in agg} == set(PARENTS[streaming])
    for name, parent in innermost_parents(agg):
        assert parent in PARENTS[streaming][name], (name, parent)
    assert n_frames > 3 and A.span_count(tr, "agg.parse") == n_frames
    if streaming:
        # one fold per validated range: at most one per chunk
        assert 3 <= A.span_count(tr, "agg.fold.residuals") <= n_frames
        assert A.span_count(tr, "agg.commit") == 3
    else:
        assert A.span_count(tr, "agg.decode") == 1
