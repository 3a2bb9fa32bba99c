"""The Moonlight cell at a small size on the CPU: the trainer against the
plain reference through the harness, the controls and a fault that must
fail, the window's loads and counts, and the roofline reader."""
import jax.numpy as jnp
import numpy as np
import pytest

from bench import control, counts_moonlight, drive_moonlight, drive_train
from bench import harness, xplane
from bench.tests import small

# Limits of the comparison at this size, set like the cell's from CPU
# readings of five seeds (7, 11, 12, 13, 2^31 + 5): sound runs read
# delta_gap 3.6e-3-5.7e-3 and head_grad_err 0.074-0.126; the fp8 control
# (float8_e4m3fn) delta_gap >= 0.013 and head_grad_err >= 0.334, the int8
# control >= 0.039 and >= 0.249, half the batch masked out >= 0.027 and
# >= 0.75.  The router's own: sound runs read load_gap 0.0046-0.0098 and
# bias_differ 0-0.125 (2 of 16 entries); the fp8 control load_gap >= 0.031,
# the int8 control >= 0.0215; the bias left at zero bias_differ >= 0.875.
# The cell's own limits come from the chip at its size (PERF.md).
SMALL_LIMITS = {"delta_gap": 1e-2, "head_grad_err": 0.18,
                "bias_differ": 0.5, "load_gap": 0.016, "decode_fails": 0}


def moonlight_cfg() -> dict:
    """The program's Moonlight smoke widths (configs/moonlight_16b_a3b.py),
    8 experts scored, this chip holding experts 4-7."""
    cfg = small.load("configs", "moonlight-16b-a3b-L5-e8")
    cfg.update(hidden_size=64, num_attention_heads=4, num_key_value_heads=4,
               qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
               kv_lora_rank=32, moe_intermediate_size=32,
               intermediate_size=128, n_routed_experts=4,
               published_n_routed_experts=8, expert_offset=4,
               num_experts_per_tok=2, vocab_size=257, num_hidden_layers=3)
    return cfg


def traffic() -> dict:
    tf = small.load("traffic", "train-ep8-8k")
    tf.update(seq_len=64, batch_per_chip=2, bucket=512)
    return tf


def run(monkeypatch, **kw) -> dict:
    small.smoke_registry(monkeypatch)
    monkeypatch.setattr(drive_moonlight, "LIMITS", SMALL_LIMITS)
    return small.run("moonlight.ep8-8k", monkeypatch, cfg=moonlight_cfg(),
                     traffic=traffic(), **kw)


def test_moonlight_reference_matches_the_trainer(monkeypatch):
    out = run(monkeypatch)
    assert out["correct"], out
    want = harness.cell_metrics(harness.load_spec(), "moonlight.ep8-8k",
                                "end_to_end")
    assert set(out["metrics"]) == {m["name"] for m in want}
    c = out["checks"]
    assert set(c) == set(SMALL_LIMITS)
    assert c["decode_fails"]["value"] == 0
    assert out["attempted"] >= 1 and out["failed"] == 0


def test_moonlight_controls_fail(monkeypatch):
    """Both controls, fp8 and int8, come out not correct."""
    run(monkeypatch, seconds=0.1)                   # registry, cache
    drv = drive_moonlight.Run(moonlight_cfg(), traffic(), 7, 1)
    drv.setup()
    prog = drv.program_readings()
    drv.release()
    ref = drv.reference()
    rate = drv.cfg["bias_update_speed"]
    assert all(v <= lim for _, v, lim in
               drive_moonlight.readings(prog, ref, rate))
    for dt in control.CONTROL_DTYPES:
        low = drv.reference(dtype=jnp.dtype(dt))
        assert any(v > lim for _, v, lim in drive_moonlight.readings(
            dict(low, fails=0.0), ref, rate)), dt


def test_moonlight_half_batch_fails(monkeypatch):
    make = drive_train.make_batches

    def half(*a, **k):
        fn = make(*a, **k)

        def batch_at(key, step):
            b = fn(key, step)
            keep = (np.arange(b["mask"].shape[0]) % 2 == 0)[:, None]
            return dict(b, mask=b["mask"] * jnp.asarray(keep, jnp.float32))
        return batch_at
    monkeypatch.setattr(drive_train, "make_batches", half)
    out = run(monkeypatch)
    assert not out["correct"] and any(
        c["value"] > c["limit"] for c in out["checks"].values())


@pytest.mark.parametrize("update", ["frozen", "wrong_sign"])
def test_moonlight_faulty_bias_update_fails(monkeypatch, update):
    """A trainer that never moves the selection bias, or moves it the wrong
    way, comes out not correct by the bias reading."""
    from repro.train import trainer
    rule = trainer.update_bias
    monkeypatch.setattr(trainer, "update_bias", {
        "frozen": lambda bias, loads, rate: bias,
        "wrong_sign": lambda bias, loads, rate: rule(bias, loads, -rate),
    }[update])
    out = run(monkeypatch)
    assert not out["correct"]
    c = out["checks"]["bias_differ"]
    assert c["value"] > c["limit"], c


def test_window_counts_the_rows_routed(monkeypatch):
    run(monkeypatch, seconds=0.1)
    cfg, tf = moonlight_cfg(), traffic()
    drv = drive_moonlight.Run(cfg, tf, 11, 1)
    drv.setup()
    res = drv.window(0.1)
    moe_layers = cfg["num_hidden_layers"] - cfg["first_k_dense_replace"]
    assert res["rows_routed"] == (res["tokens"] * moe_layers
                                  * cfg["num_experts_per_tok"])
    assert 0 < res["rows_held"] < res["rows_routed"]
    per_tok = res["rows_held"] / res["tokens"]
    assert res["flops_per_token"] == counts_moonlight.flops_per_token(
        cfg, tf["seq_len"], per_tok)
    assert res["gmm_flops"] == pytest.approx(
        18 * 64 * 32 * res["rows_held"] / res["steps"])


def test_flops_per_token_hand_count():
    cfg = small.load("configs", "moonlight-16b-a3b-L5-e8")
    D, H = 2048, 16
    mla = D * H * 192 + D * 576 + 512 * H * 256 + H * 128 * D   # 13,762,560
    assert mla == 13_762_560
    dense = 3 * D * 11264
    moe = D * 64 + 3 * D * 2816                         # router, shared
    routed = 3 * 3 * D * 1408        # 0.75 held rows a token in 4 layers
    matmul = 5 * mla + dense + 4 * moe + routed + 20480 * D
    attention = 5 * 3 * H * 320 * 8193
    got = counts_moonlight.flops_per_token(cfg, 8192, 3.0)
    assert got == 6 * matmul + attention
    assert 1.6e9 < 6 * matmul < 1.7e9 and 0.62e9 < attention < 0.64e9
    assert counts_moonlight.gmm_flops(cfg, 10) == 18 * D * 1408 * 10


def test_expert_gmm_roofline_reads_the_kernels():
    read = harness.metric_reader("expert_gmm_roofline")
    ops = [("expert_gmm", 0.0, 2e6), ("expert_tgmm", 2e6, 3e6),
           ("fusion", 3e6, 9e6)]
    tr = xplane.Trace([xplane.DeviceOps(0, ops)], [], (0.0, 10e6))
    peaks = {"bf16_flops": 197e12}
    run_ = {"kind": "train", "gmm_flops": 197e12 * 1e-3, "steps": 2}
    view = harness.RunView(tr, run_, {}, 1, peaks)
    assert read(view) == pytest.approx(100 * 2e-3 / 3e-3)
    granite = dict(run_)
    del granite["gmm_flops"]
    assert read(harness.RunView(tr, granite, {}, 1, peaks)) is None


def test_a_program_without_the_architecture_refuses_at_once(monkeypatch):
    from repro.configs import registry
    monkeypatch.delitem(registry.ARCHS, "moonlight-16b-a3b")
    drv = drive_moonlight.Run(small.load("configs", "moonlight-16b-a3b-L5-e8"),
                              small.load("traffic", "train-ep8-8k"), 1, 1)
    with pytest.raises(KeyError):
        drv.setup()
