"""Traffic of the training face: closed-loop training steps.

Set-up builds the program's trainer (``Trainer`` and its compiled step) on
a ``dp x 1`` mesh, makes the state on the device from the seed in one
jitted call (the benchmark's own weights, placed in the program's ZeRO-3
storage layout), and drives that state through the first ``setup_steps``
steps with the window's own call and feed, which compiles and warms every
shape.  The window then runs step after step, each on fresh rows of a
seeded Zipf-Markov token stream made on the device, until ``seconds`` have
passed; tokens per second are taken over all steps and all of the window.

The first steps are what ``correct`` compares with the plain reference
(``bench.reference.granite``): each step's loss, the first gradient as the
optimizer got it (its first moment after one step, over 1 - b1), leaf by
leaf and, for the LM head, element by element, and the parameters' change
after the set-up steps, leaf by leaf.
"""
from __future__ import annotations

import contextlib
import dataclasses
import gc
import math
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation
from jax.sharding import NamedSharding, PartitionSpec as P

from bench import counts
from bench.drive_agg import seed_key
from bench.reference import granite as ref

WIDTHS = {"d_model": "hidden_size", "n_heads": "num_attention_heads",
          "n_kv": "num_key_value_heads", "head_dim": "head_dim",
          "d_ff": "intermediate_size", "vocab": "vocab_size",
          "n_experts": "num_local_experts", "top_k": "num_experts_per_tok",
          "n_layers": "num_hidden_layers", "norm_eps": "rms_norm_eps",
          "rope_theta": "rope_theta", "capacity_factor": "capacity_factor",
          "tie_embeddings": "tie_word_embeddings"}


def zipf_cdf(vocab: int) -> np.ndarray:
    r = np.arange(1, vocab + 1, dtype=np.float64)
    p = 1.0 / r
    return np.cumsum(p / p.sum()).astype(np.float32)


def make_batches(vocab: int, seq: int, batch: int, sharding=None):
    """batch_at(key, step) -> {"tokens", "targets", "mask"} (batch, seq) on
    the device: an order-1 Markov chain (next = 31 * first + a drift of
    0..6 a token, mod V) with Zipf-distributed resets one token in ten."""
    cdf = jnp.asarray(zipf_cdf(vocab))

    def batch_at(key, step):
        k1, k2, k3 = jax.random.split(jax.random.fold_in(key, step), 3)
        shape = (batch, seq + 1)
        base = jnp.minimum(jnp.searchsorted(
            cdf, jax.random.uniform(k1, shape), side="right"), vocab - 1)
        drift = jnp.cumsum(jax.random.randint(k2, shape, 0, 7), axis=1)
        reset = jax.random.bernoulli(k3, 0.1, shape)
        toks = jnp.where(reset, base, (base[:, :1] * 31 + drift) % vocab
                         ).astype(jnp.int32)
        return {"tokens": toks[:, :-1], "targets": toks[:, 1:],
                "mask": jnp.ones((batch, seq), jnp.float32)}

    if sharding is None:
        return jax.jit(batch_at)
    return jax.jit(batch_at, out_shardings={k: sharding for k in
                                            ("tokens", "targets", "mask")})


class Run:
    def __init__(self, cfg: dict, traffic: dict, seed: int, chips: int):
        self.cfg, self.traffic, self.seed = cfg, traffic, seed
        self.dp = int(traffic["dp"])
        if self.dp != chips:
            raise ValueError(f"traffic dp={self.dp} on a {chips}-chip cell")
        key = seed_key(seed)
        self.wkey = jax.random.fold_in(key, 1)     # weights
        self.dkey = jax.random.fold_in(key, 2)     # data
        self.skey = jax.random.fold_in(key, 3)     # the step's own key
        self.seq = int(traffic["seq_len"])
        self.batch = int(traffic["batch_per_chip"]) * self.dp
        self.mask_fault = None     # set by the fault checks only

    def _program_config(self):
        from repro.configs import registry
        cfg = self.cfg
        mcfg = dataclasses.replace(registry.config(cfg["arch"]),
                                   n_layers=cfg["num_hidden_layers"])
        for field, key in WIDTHS.items():
            if getattr(mcfg, field) != cfg[key]:
                raise ValueError(f"the program's {field}="
                                 f"{getattr(mcfg, field)} differs from the "
                                 f"configuration's {key}={cfg[key]}")
        if (mcfg.family, mcfg.act, mcfg.emb_scale) != ("moe", "swiglu", 1.0):
            raise ValueError(f"{cfg['arch']} is not the moe/swiglu model "
                             f"the configuration describes")
        return mcfg

    def setup(self) -> None:
        from repro.dist.collectives import QSyncConfig
        from repro.launch.mesh import make_mesh
        from repro.models import transformer as T
        from repro.models.sharding import ShardCtx, logical_to_storage
        from repro.train import data as D
        from repro.train import optim as O
        from repro.train.trainer import TrainConfig, Trainer

        tf = self.traffic
        mcfg = self._program_config()
        self.mesh = make_mesh((self.dp, 1), ("data", "model"))
        ctx = ShardCtx(tp=1, dp=self.dp,
                       qcfg=QSyncConfig(q=tf["q"], bucket=tf["bucket"]),
                       grad_sync=tf["grad_sync"])
        opt = {k: v for k, v in tf["optimizer"].items()
               if k in {f.name for f in dataclasses.fields(O.OptConfig)}}
        self.opt_cfg = O.OptConfig(**opt)
        tc = TrainConfig(steps=1 << 30, max_restarts=0, y0=tf["y0"])
        with contextlib.redirect_stdout(sys.stderr):
            tr = Trainer(mcfg, ctx, self.mesh, self.opt_cfg, tc,
                         D.DataConfig(vocab=mcfg.vocab, seq_len=self.seq,
                                      global_batch=self.batch))
        self.step_fn = tr.step_fn
        metas = T.all_metas(mcfg, ctx)
        shapes = ref.leaf_shapes(self.cfg)
        for grp in ("layers", "top"):
            for name, meta in metas[grp].items():
                want = shapes[grp][name][1:] if grp == "layers" \
                    else shapes[grp][name]
                if tuple(meta.local_shape) != tuple(want):
                    raise ValueError(f"{grp}/{name}: program shape "
                                     f"{meta.local_shape} != {want}")
        self.metas = metas
        sharding = jax.tree.map(lambda s: NamedSharding(self.mesh, s),
                                tr.state_spec,
                                is_leaf=lambda x: isinstance(x, P))
        cfg, opt_cfg = ref._Hashable(self.cfg), self.opt_cfg

        def storage(logical):
            out = {"layers": {}, "top": {}}
            for n, x in logical["layers"].items():
                out["layers"][n] = jax.vmap(
                    lambda v, n=n: logical_to_storage(
                        v, metas["layers"][n], ctx))(x)
            for n, x in logical["top"].items():
                out["top"][n] = logical_to_storage(x, metas["top"][n], ctx)
            return out

        self._storage = storage

        def make_state(wkey, skey):
            params = storage(ref.init_params(cfg, wkey))
            return {"params": params, "opt": O.init_opt_state(params, opt_cfg),
                    "y": T.y_init(mcfg, ctx, tc.y0),
                    "step": jnp.zeros((), jnp.int32), "key": skey}

        self.state = jax.jit(make_state, out_shardings=sharding)(
            self.wkey, self.skey)
        self.batch_at = make_batches(mcfg.vocab, self.seq, self.batch,
                                     NamedSharding(self.mesh, P("data")))
        self.step = 0
        self.setup_loss, self.setup_fails = [], []
        b1 = self.opt_cfg.b1
        for i in range(int(self.traffic["setup_steps"])):
            met = self._step()
            self.setup_loss.append(float(met["loss"]))
            self.setup_fails.append(float(met["fails"]))
            if i == 0:
                self.grad_norms = {k: v / (1 - b1) for k, v in
                                   self._norms(self.state["opt"]["m"]).items()}
                self.head_grad = self._head(self.state["opt"]["m"], b1)
        self.delta_norms = self._delta_norms()
        self.window_metrics = []

    def _batch(self):
        b = self.batch_at(self.dkey, self.step)
        if self.mask_fault is not None:
            b = dict(b, mask=b["mask"] * self.mask_fault)
        return b

    def _step(self):
        with TraceAnnotation("bench.data"):
            batch = self._batch()
        with TraceAnnotation("bench.step"):
            self.state, met = self.step_fn(self.state, batch)
        self.step += 1
        return met

    # ------------------------------------------------------------- readings
    def _leaf_flat(self, grp, name, a):
        n = self.metas[grp][name].numel()
        if grp == "layers":
            return a.reshape(a.shape[0], -1)[:, :n]
        return a.reshape(1, -1)[:, :n]

    def _norms(self, tree) -> dict:
        if not hasattr(self, "_norms_fn"):
            def fn(tree):
                return {g: {n: jnp.sqrt(jnp.sum(jnp.square(
                    self._leaf_flat(g, n, a)), axis=1))
                    for n, a in tree[g].items()} for g in ("layers", "top")}
            self._norms_fn = jax.jit(fn)
        return ref.flat_norms(self._norms_fn(tree))

    def _head(self, m, b1) -> np.ndarray:
        """The LM head's first moment over 1 - b1, in the logical layout,
        on the host."""
        meta = self.metas["top"]["lm_head"]
        head = jax.jit(lambda a: self._leaf_flat("top", "lm_head", a)[0]
                       .reshape(meta.local_shape) / (1 - b1))
        return np.asarray(head(m["top"]["lm_head"]))

    def _delta_norms(self) -> dict:
        cfg = ref._Hashable(self.cfg)
        storage = self._storage

        @jax.jit
        def delta(params, wkey):
            p0 = storage(ref.init_params(cfg, wkey))
            return jax.tree.map(jnp.subtract, params, p0)
        return self._norms(delta(self.state["params"], self.wkey))

    # --------------------------------------------------------------- window
    def window(self, seconds: float) -> dict:
        t0 = time.perf_counter()
        steps, prev = 0, None
        while True:
            met = self._step()
            self.window_metrics.append(met)
            steps += 1
            if prev is not None:
                with TraceAnnotation("bench.wait"):
                    prev["loss"].block_until_ready()
            prev = met
            if time.perf_counter() - t0 >= seconds:
                break
        with TraceAnnotation("bench.wait"):
            jax.block_until_ready((self.state, met))
        window_s = time.perf_counter() - t0
        tokens = steps * self.batch * self.seq
        bad = sum(1 for m in self.window_metrics
                  if not math.isfinite(float(m["loss"]))
                  or float(m["fails"]) > 0)
        return {"kind": "train", "window_s": window_s, "steps": steps,
                "tokens": tokens, "attempted": steps, "failed": bad,
                "flops_per_token": counts.granite_flops_per_token(
                    self.cfg, self.seq),
                "end_to_end": {"tokens_per_s": tokens / window_s}}

    def release(self) -> None:
        self.state = None
        self.window_metrics = None
        gc.collect()

    # ---------------------------------------------------------------- check
    def ref_batches(self):
        gen = make_batches(self.cfg["vocab_size"], self.seq, self.batch)
        bpc = int(self.traffic["batch_per_chip"])

        def batches(step):
            b = gen(self.dkey, step)
            for r in range(self.dp):
                sl = slice(r * bpc, (r + 1) * bpc)
                yield b["tokens"][sl], b["targets"][sl]
        return batches

    def reference(self, dtype=jnp.float32) -> dict:
        return ref.train(self.cfg, self.traffic["optimizer"], self.wkey,
                         self.ref_batches(),
                         steps=int(self.traffic["setup_steps"]), dtype=dtype)

    def program_readings(self) -> dict:
        return {"loss": self.setup_loss, "grad": self.grad_norms,
                "head_grad": self.head_grad, "delta": self.delta_norms,
                "fails": sum(self.setup_fails)}

    def check(self) -> list:
        return readings(self.program_readings(), self.reference())


def worst_leaf_gap(prog: dict, ref_n: dict, skip=()) -> float:
    """max over leaves of |prog norm - ref norm| / max(ref norm, median
    ref norm) (leaves in ``skip`` left out)."""
    med = float(np.median(list(ref_n.values())))
    return max(abs(prog[k] - v) / max(v, med)
               for k, v in ref_n.items() if k not in skip)


def head_grad_err(prog: dict, refr: dict) -> float:
    """The norm of the first gradient's difference from the reference's on
    the LM head, element by element, over the larger of the head's
    reference norm and the median leaf's."""
    med = float(np.median(list(refr["grad"].values())))
    diff = prog["head_grad"].astype(np.float64) - refr["head_grad"]
    return float(np.linalg.norm(diff)) / max(refr["grad"]["top/lm_head"], med)


def gaps(prog: dict, refr: dict) -> dict:
    """The program's first steps against the reference's: the largest
    relative gap of a step's loss, the worst leaf's gap of the first
    gradient's and of the parameters' change's norms, and the LM head's
    error of the first gradient element by element (which a lower precision
    moves even where whole-leaf norms average its rounding out).
    Leaves whose reference gradient is under a thousandth of the median
    leaf's move by round-off alone under Adam, so the change is compared on
    the others."""
    med = float(np.median(list(refr["grad"].values())))
    still = {k for k, v in refr["grad"].items() if v < 1e-3 * med}
    return {"loss_gap": max(abs(a - b) / abs(b)
                            for a, b in zip(prog["loss"], refr["loss"])),
            "grad_gap": worst_leaf_gap(prog["grad"], refr["grad"]),
            "head_grad_err": head_grad_err(prog, refr),
            "delta_gap": worst_leaf_gap(prog["delta"], refr["delta"], still),
            "decode_fails": prog["fails"]}


def readings(prog: dict, refr: dict) -> list:
    """[(name, value, limit)] of the numbers ``correct`` compares."""
    g = gaps(prog, refr)
    return [(n, g[n], lim) for n, lim in LIMITS.items()]


# Limits, each set on the chip at the cell's size between the largest
# reading of sound runs over a dozen seeds or more and the smallest of the
# fp8 and int8 controls and the faults (PERF.md, "Limits of correct").
LIMITS = {"loss_gap": 4e-4, "grad_gap": 0.2, "delta_gap": 4e-3,
          "head_grad_err": 0.07, "decode_fails": 0}
