"""``correct`` comes out false when the timed path is broken underneath,
once for each fault a cell can have, and for each cell's control (the
plain reference one precision below the configuration's, in the program's
place).  Small sizes on the CPU; ``bench/control.py`` reads the same at
the cells' own sizes on the chip."""
import jax.numpy as jnp
import numpy as np
import pytest

from bench import control, drive_agg, drive_train
from bench.tests import small


def fails(out) -> bool:
    return not out["correct"] and any(
        c["value"] > c["limit"] for c in out["checks"].values())


@pytest.mark.parametrize("fault", ["half_batch", "answer_altered"])
def test_agg_faults(monkeypatch, fault):
    from repro.agg.server import AggServer
    if fault == "half_batch":
        monkeypatch.setattr(AggServer, "ingest_frame",
                            control.drop_half(AggServer.ingest_frame))
    else:
        monkeypatch.setattr(AggServer, "finalize",
                            control.alter_answer(AggServer.finalize))
    assert fails(small.run("fl-xdevice.stream", monkeypatch))


def test_agg_control(monkeypatch):
    small.run("fl-xdevice.sealed", monkeypatch)     # jax cache settings
    drv = drive_agg.Run(small.fl_cfg(),
                           small.load("traffic", "agg-sealed"), 7, 1)
    out = control.agg_readings(drv, 0.1, controls=True)
    limits = drive_agg.LIMITS
    assert all(out["sound"][n] <= lim for n, lim in limits.items())
    for kind in ("control", "half_batch", "answer_altered"):
        assert any(out[kind][n] > lim for n, lim in limits.items()), kind


def test_train_state_unchanged(monkeypatch):
    from repro.train import optim
    monkeypatch.setattr(optim, "apply_update",
                        lambda params, grads, opt_state, *a, **k:
                        (params, opt_state))
    assert fails(small.run("granite.dp1", monkeypatch))


def test_train_half_batch(monkeypatch):
    make = drive_train.make_batches

    def half(*a, **k):
        fn = make(*a, **k)

        def batch_at(key, step):
            b = fn(key, step)
            keep = (np.arange(b["mask"].shape[0]) % 2 == 0)[:, None]
            return dict(b, mask=b["mask"] * jnp.asarray(keep, jnp.float32))
        return batch_at
    monkeypatch.setattr(drive_train, "make_batches", half)
    assert fails(small.run("granite.dp1", monkeypatch))


def test_train_control(monkeypatch):
    """Both controls, fp8 and int8, come out not correct."""
    small.run("granite.dp1", monkeypatch, seconds=0.1)   # registry, cache
    drv = drive_train.Run(small.granite_cfg(), small.train_traffic(), 7, 1)
    drv.setup()
    prog = drv.program_readings()
    drv.release()
    ref = drv.reference()
    assert all(v <= lim for _, v, lim in drive_train.readings(prog, ref))
    assert control.CONTROL_DTYPES == ("float8_e4m3fn", "int8")
    for dt in control.CONTROL_DTYPES:
        low = drv.reference(dtype=jnp.dtype(dt))
        assert any(v > lim for _, v, lim in
                   drive_train.readings(dict(low, fails=0.0), ref)), dt
