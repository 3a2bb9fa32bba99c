"""Small configurations of the benchmark's cells for the CPU tests: the
published shapes of each configuration cut to a size a test run holds."""
import json
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]


def load(kind: str, name: str) -> dict:
    return json.loads((ROOT / "bench" / kind / f"{name}.json").read_text())


def granite_cfg() -> dict:
    """The program's granite smoke widths (configs/granite_moe_1b.py)."""
    cfg = load("configs", "granite-moe-1b-a400m-L8")
    cfg.update(hidden_size=64, num_attention_heads=4, num_key_value_heads=2,
               head_dim=16, intermediate_size=64, vocab_size=257,
               num_local_experts=8, num_experts_per_tok=2,
               num_hidden_layers=2)
    return cfg


def train_traffic(name: str = "train-dp1") -> dict:
    tf = load("traffic", name)
    tf.update(seq_len=64, batch_per_chip=2, bucket=512)
    return tf


def fl_cfg() -> dict:
    cfg = load("configs", "fl-xdevice-c256-d1.4M")
    cfg.update(cohort=8, d=15_000, mtu=4096)     # padded, as the cell's d is
    return cfg


# Limits of the training comparison at this size, set like the cell's from
# CPU readings of five seeds (7, 11, 12, 13, 2^31 + 5): sound runs read
# delta_gap <= 7.5e-3 and head_grad_err 0.031-0.121; the int8 control
# delta_gap >= 3.6e-2 and head_grad_err >= 0.253, the fp8 control
# (float8_e4m3fn) head_grad_err >= 0.267, and half the batch masked out
# delta_gap >= 2.0e-2.  The cell's own limits come from the chip at its
# size (PERF.md).
SMALL_LIMITS = {"delta_gap": 1.5e-2, "head_grad_err": 0.2, "decode_fails": 0}


def smoke_registry(monkeypatch):
    """Point the program's registry at its smoke configurations."""
    from repro.configs import registry
    monkeypatch.setattr(registry, "config",
                        lambda name: registry.smoke_config(name))


def run(workload: str, monkeypatch, seconds: float = 0.5, **kw) -> dict:
    """One CPU run of a cell through the harness, at the small size."""
    import jax
    from bench import harness
    monkeypatch.setattr("repro.launch.compile_cache.enable_compile_cache",
                        lambda: "off")
    saved = {k: getattr(jax.config, k) for k in (
        "jax_enable_compilation_cache",
        "jax_persistent_cache_min_compile_time_secs")}
    jax.config.update("jax_enable_compilation_cache", False)
    if workload.startswith("granite"):
        from bench import drive_train
        smoke_registry(monkeypatch)
        monkeypatch.setattr(drive_train, "LIMITS", SMALL_LIMITS)
        kw.setdefault("cfg", granite_cfg())
        kw.setdefault("traffic", train_traffic())
    else:
        kw.setdefault("cfg", fl_cfg())
    try:
        return harness.run_cell(workload, 2**31 + 5, seconds, trace=False,
                                t_start=time.perf_counter(),
                                require_chip=False, log=lambda s: None, **kw)
    finally:
        for k, v in saved.items():
            jax.config.update(k, v)
