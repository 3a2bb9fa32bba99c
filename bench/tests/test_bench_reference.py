"""The plain references against the program at a small size on the CPU,
on seeded weights and frames, through the cells' own traffic generators."""
from bench import harness
from bench.tests import small


def reports_its_metrics(cell: str, out: dict) -> bool:
    """Every end-to-end metric the cell lists, split names included."""
    want = harness.cell_metrics(harness.load_spec(), cell, "end_to_end")
    return set(out["metrics"]) == {m["name"] for m in want}


def test_agg_reference_matches_the_streaming_and_sealed_servers(monkeypatch):
    for cell in ("fl-xdevice.stream", "fl-xdevice.sealed"):
        out = small.run(cell, monkeypatch)
        assert out["correct"], out
        assert reports_its_metrics(cell, out), out["metrics"]
        c = out["checks"]
        assert c["mean_bits_differ"]["value"] == 0
        assert c["clients_not_accepted"]["value"] == 0
        assert 0 < c["err_over_half_side"]["value"] < 1


def test_granite_reference_matches_the_trainer(monkeypatch):
    out = small.run("granite.dp1", monkeypatch)
    assert out["correct"], out
    assert reports_its_metrics("granite.dp1", out), out["metrics"]
    c = out["checks"]
    assert c["delta_gap"]["value"] < c["delta_gap"]["limit"]
    assert c["decode_fails"]["value"] == 0
    assert out["attempted"] >= 1 and out["failed"] == 0
