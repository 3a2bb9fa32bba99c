"""The reduction from a profiler trace to device numbers, on synthetic
intervals and on a trace recorded on a TPU v5e (a Pallas lattice encode of
2^22 coordinates, a bf16 4096^2 matmul and an elementwise pass, three times,
between host spans ``bench.data`` and ``bench.dispatch``)."""
from pathlib import Path

import pytest

from bench import xplane as X

DATA = Path(__file__).resolve().parent / "data" / "v5e_probe.xplane.pb"


def test_union_subtract_total():
    u = X.union([(5, 7), (0, 2), (1, 3), (6, 9), (9, 9)])
    assert u == [(0, 3), (5, 9)]
    assert X.total(u) == 7
    assert X.subtract([(0, 10)], [(1, 2), (4, 6)]) == [(0, 1), (2, 4),
                                                        (6, 10)]
    assert X.subtract([(0, 3), (5, 9)], [(2, 6)]) == [(0, 2), (6, 9)]


def test_op_name():
    assert X.op_name("%lattice_encode_pallas.1 = u32[2048,256]{1,0} "
                     "custom-call(f32[2048,2048] %x)") == \
        "lattice_encode_pallas"
    assert X.op_name("%fusion.12 = bf16[8]{0} fusion(...)") == "fusion"
    assert X.op_name("%all-reduce-start.3 = f32[] all-reduce-start(...)") \
        == "all-reduce-start"


def synthetic():
    d0 = X.DeviceOps(0, [("fusion", 0, 40), ("lattice_encode_pallas", 40, 60),
                         ("all-reduce-start", 60, 61),
                         ("fusion", 61, 70),
                         ("all-reduce-done", 70, 80),
                         ("lattice_decode_pallas", 90, 100)])
    d1 = X.DeviceOps(1, [("fusion", 0, 50), ("all-reduce", 50, 90),
                         ("fusion", 60, 70)])
    spans = [("bench.window", 0, 100), ("bench.step", 0, 85),
             ("bench.data", 80, 95)]
    return X.Trace([d0, d1], spans, (0, 100))


def test_busy_idle_kernels_collectives():
    t = synthetic()
    # device 0 busy 0-80 and 90-100 (90 ns), device 1 busy 0-90 (90 ns)
    assert t.busy_ns() == 90
    assert t.idle_share() == pytest.approx(0.10)
    assert t.kernel_ns(r"^lattice_") == pytest.approx((30 + 0) / 2)
    # device 0: collectives 60-61 and 70-80, nothing else then: 11 ns;
    # device 1: all-reduce 50-90 overlapped by a fusion 60-70: 30 ns
    assert t.exposed_collective_ns_worst() == 30
    assert t.span_ns("bench.data") == 15 and t.span_ns("bench.step") == 85


def test_idle_gaps_attributed_to_innermost_span():
    t = synthetic()
    # device 0 idles 80-90, midpoint 85: bench.step closes at 85, so the
    # innermost span open there is bench.data (80-95)
    assert t.idle_gaps() == [["bench.data", 10 / 1e9]]
    top = t.top_ops(2)
    assert top[0][0] == "fusion"


def test_recorded_v5e_trace():
    tr = X.load(str(DATA))
    assert [d.device for d in tr.devices] == [0]
    names = {n for n, _, _ in tr.devices[0].ops}
    assert "lattice_encode_pallas" in names
    assert tr.kernel_ns(r"^lattice_encode") > 0
    assert {s[0] for s in tr.spans} == {"bench.data", "bench.dispatch"}
    assert 0.0 < tr.idle_share() < 1.0
    # three encodes of 2^22 coordinates at ~180 us each
    assert 3 * 100e3 < tr.kernel_ns(r"^lattice_encode") < 3 * 400e3
    assert tr.idle_gaps()[0][0] in {"bench.data", "bench.dispatch",
                                    "host.other"}
