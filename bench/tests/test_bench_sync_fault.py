"""The fault granite.dp4-fp32 exists to catch, at a small size on four CPU
devices: the gradient's reduce-scatter over ``data`` left out
(``bench/control_sync.py``) comes out not correct, where the sound run on
the same seed does not.  Four devices need ``XLA_FLAGS`` before JAX starts,
so the run is a subprocess; this process keeps its one device."""
import json
import os
import subprocess
import sys
import textwrap

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

CODE = """
import json
import jax
jax.config.update("jax_enable_compilation_cache", False)
from bench import control_sync, drive_train
from bench.tests import small
from repro.configs import registry
registry.config = lambda name: registry.smoke_config(name)
cfg, tf = small.granite_cfg(), small.train_traffic("train-dp4-fp32")
res = control_sync.fault_readings(lambda: drive_train.Run(cfg, tf, 7, 4))
print(json.dumps(res))
"""


def test_dp4_exchange_left_out_fails():
    from bench.tests import small
    env = dict(os.environ,
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               JAX_PLATFORMS="cpu",
               PYTHONPATH=os.pathsep.join([ROOT, os.path.join(ROOT, "src")]))
    r = subprocess.run([sys.executable, "-c", textwrap.dedent(CODE)],
                       capture_output=True, text=True, timeout=600, env=env,
                       cwd=ROOT)
    assert r.returncode == 0, r.stderr[-4000:]
    res = json.loads(r.stdout.strip().splitlines()[-1])
    lim = small.SMALL_LIMITS
    assert all(res["sound"][n] <= v for n, v in lim.items()), res
    assert all(res["exchange_left_out"][n] > v for n, v in lim.items()
               if v > 0), res
