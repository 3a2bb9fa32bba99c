"""Fault-tolerant trainer: jitted shard_map train step + restartable loop.

The train step (one compiled program, runs on every device):
  1. local loss -> grads; the FSDP gather's custom vjp reduce-scatters
     gradients over DP with the paper's lattice quantization;
  2. telemetry (decode failures / measured distances, now *per bucket*)
     arrives as the cotangent of the dummy ``tele`` input;
  3. global grad-norm clip (one scalar all-reduce), ZeRO-local optimizer;
  4. the per-bucket ``y`` distance-bound state is updated from telemetry
     via :func:`repro.core.qstate.update_y`: buckets implicated in a
     detected decode failure *escalate* (the SPMD version of
     RobustAgreement's r <- r^2, DESIGN §2), clean buckets relax toward
     their measured distances.

Anchored gradients (``ShardCtx.anchor_grads``): each leaf's y-state carries
``{"y": (nb,), "anchor": (m,)}``; the FSDP backward encodes ``g - anchor``
through the butterfly (dist/fsdp.py) and returns the decoded full mean in
the tele cotangent, which becomes the next step's anchor — cross-step
variance reduction: consecutive gradients are correlated, so
``|g_t - mean_{t-1}|`` (what y must cover) shrinks well below ``|g_t|``.

Fault tolerance: checkpoint every ``ckpt_every`` steps (atomic, logical
layout => restores onto a different mesh); the loop catches device/runtime
failures, restores the last checkpoint and replays — data is stateless-
seeded so the replay is deterministic.  Stragglers cannot desync state:
every step is a single SPMD program (implicit barrier).
"""
from __future__ import annotations

import dataclasses
import time
from functools import partial
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.core import qstate as QS
from repro.dist import fsdp as F
from repro.models.config import ModelConfig
from repro.models.sharding import (ShardCtx, anchor_spec, shard_len,
                                   storage_spec)
from repro.models import transformer as T
from repro.train import optim as O
from repro.train import data as D
from repro.train import checkpoint as C

Array = jax.Array


@dataclasses.dataclass(frozen=True)
class TrainConfig:
    steps: int = 100
    microbatch: int = 0            # 0 = no accumulation
    ckpt_every: int = 50
    ckpt_dir: str = "/tmp/repro_ckpt"
    log_every: int = 10
    keep: int = 3
    max_restarts: int = 3
    y0: float = 1.0                # per-coordinate distance guess; with
                                   # qcfg.rotate each leaf seeds from the §6
                                   # rotated-space bound (sharding.leaf_y0)
    y_decay: float = 0.99          # relax y toward measured distance
    y_escalate: float = 2.0        # on detected decode failure
    bias_rate: float = 1e-3        # a sigmoid_bias router's selection-bias
                                   # step (DeepSeek-V3's gamma)
    donate: bool = False           # the step consumes its input state (one
                                   # copy of the state resident, not two)


def _y_update(y, tele: Array, tc: TrainConfig):
    """Per-leaf distance-bound state transition from the tele cotangent.

    y: legacy scalar state ((), (L,)), per-bucket state ((nb,), (L, nb)),
    or an anchored dict leaf {"y": (..., nb), "anchor": (..., m)}.
    tele: (..., width) — [max_dist, fails, y_next | dist_b | fails_b
    | anchor_next] per dist/fsdp.py's layout.
    """
    if isinstance(y, dict):
        nb = y["y"].shape[-1]
        m = y["anchor"].shape[-1]
        lo = F.TELE_WIDTH + 2 * nb
        # the tele slice is the rank's anchor row; reshape restores the
        # stored layout (sharded anchors live as (L?, 1, 1, shard) local
        # views of the ZeRO-3 storage array — legacy (L?, m) is a no-op)
        return {"y": _y_update(y["y"], tele, tc),
                "anchor": tele[..., lo:lo + m].reshape(y["anchor"].shape)}
    if y.ndim == tele.ndim and \
            tele.shape[-1] >= F.TELE_WIDTH + 2 * y.shape[-1]:
        nb = y.shape[-1]
        dist_b = tele[..., F.TELE_WIDTH:F.TELE_WIDTH + nb]
        fails_b = tele[..., F.TELE_WIDTH + nb:F.TELE_WIDTH + 2 * nb]
        return QS.update_y(y, fails_b, dist_b, decay=tc.y_decay,
                           escalate=tc.y_escalate)
    # legacy scalar leaf: one bound per leaf from the scalar telemetry
    max_dist, fails, y_next = tele[..., 0], tele[..., 1], tele[..., 2]
    candidate = jnp.where(y_next > 1e-11,
                          jnp.clip(y_next, 0.25 * y, 4.0 * y),
                          y)
    relaxed = tc.y_decay * y + (1 - tc.y_decay) * candidate
    return jnp.where(fails > 0, y * tc.y_escalate, relaxed)


def update_bias(bias: Array, loads: Array, rate: float) -> Array:
    """Aux-loss-free balancing (DeepSeek-V3, arXiv:2412.19437 §2.1.2): each
    layer's selection bias rises by ``rate`` for the experts under the
    layer's mean load and falls by it for those over.  bias (L, E) f32,
    loads (L, E) rows routed to each expert in the step."""
    lf = loads.astype(jnp.float32)
    return bias + rate * jnp.sign(jnp.mean(lf, -1, keepdims=True) - lf)


def record_moe_loads(loads, cfg: ModelConfig) -> tuple[int, int]:
    """Count a step's (or several steps' summed) loads (L, E) into the
    ``moe.rows_held`` and ``moe.rows_routed`` counters of ``repro.obs``:
    the rows routed to this chip's held experts and to all experts.
    Reads ``loads`` back; returns (held, routed)."""
    import repro.obs as obs
    a = np.asarray(loads).astype(np.int64)
    lo = cfg.expert_offset
    held, routed = int(a[:, lo:lo + cfg.held].sum()), int(a.sum())
    obs.counter("moe.rows_held").inc(held)
    obs.counter("moe.rows_routed").inc(routed)
    return held, routed


def make_train_step(cfg: ModelConfig, ctx: ShardCtx, mesh, opt_cfg: O.OptConfig,
                    tc: TrainConfig):
    """Returns jitted step(state, batch) -> (state, metrics).

    A ``sigmoid_bias`` router adds the state leaf ``moe_bias`` (L, E) f32,
    outside the gradient and the sync, updated after each step from the
    step's loads (summed over ``data``), and the metric ``loads`` (L, E)
    int32."""
    metas = T.all_metas(cfg, ctx)
    loss_fn = T.make_loss_fn(cfg, ctx)
    L = T.n_scan_steps(cfg)
    if ctx.anchor_grads and tc.microbatch > 1:
        # the anchor rides the tele cotangent, which accumulation combines
        # with jnp.maximum — meaningless for a mean vector
        raise ValueError("anchor_grads is incompatible with microbatch > 1")

    pspec = {"layers": {k: storage_spec(m, ctx) for k, m in metas["layers"].items()},
             "top": {k: storage_spec(m, ctx) for k, m in metas["top"].items()}}
    dpa = ctx.dp_axes if len(ctx.dp_axes) > 1 else ctx.dp_axes[0]
    bspec_leaf = P(dpa)
    opt_spec = ({"m": pspec, "v": pspec} if opt_cfg.name == "adamw"
                else {"m": pspec})

    # anchored leaves are {"y", "anchor"} dicts whose anchor may live in
    # ZeRO-3 storage layout (sharded over tp x dp like the weights); the y
    # spec is then a per-leaf tree instead of one replicated P()
    def _y_leaf_spec(meta):
        if not ctx.anchor_grads:
            return P()
        return {"y": P(), "anchor": anchor_spec(meta, ctx, meta.scanned)}

    y_spec = {"layers": {k: _y_leaf_spec(m) for k, m in metas["layers"].items()},
              "top": {k: _y_leaf_spec(m) for k, m in metas["top"].items()}}
    state_spec = {"params": pspec, "opt": opt_spec, "y": y_spec, "step": P(),
                  "key": P()}
    biased = cfg.router == "sigmoid_bias"
    if biased:
        if tc.microbatch > 1:
            raise ValueError("a sigmoid_bias router's loads are not "
                             "accumulated over microbatches")
        state_spec["moe_bias"] = P()

    def batch_spec(batch):
        return {k: bspec_leaf for k in batch}

    def per_device(state, batch):
        params, opt, y, step, key = (state["params"], state["opt"], state["y"],
                                     state["step"], state["key"])
        kstep = jax.random.fold_in(key, step)
        tele0 = T.tele_zeros(cfg, ctx)

        bias = state.get("moe_bias")

        def lg(batch_mb):
            (l, metrics), (gp, gt) = jax.value_and_grad(
                loss_fn, argnums=(0, 1), has_aux=True)(
                    params, tele0, batch_mb, kstep, y, bias)
            return metrics, gp, gt

        if tc.microbatch > 1:
            mb = tc.microbatch
            def split(v):
                b = v.shape[0]
                return v.reshape(mb, b // mb, *v.shape[1:])
            batch_mb = {k: split(v) for k, v in batch.items()}

            def acc(carry, xs):
                metrics, gp, gt = lg(xs)
                cg, ct, cm = carry
                cg = jax.tree.map(lambda a, b: a + b, cg, gp)
                ct = jax.tree.map(lambda a, b: jnp.maximum(a, b), ct, gt)
                cm = jax.tree.map(lambda a, b: a + b, cm, metrics)
                return (cg, ct, cm), None

            zg = jax.tree.map(jnp.zeros_like, params)
            zt = T.tele_zeros(cfg, ctx)
            zm = {"loss": jnp.zeros(()), "aux": jnp.zeros(())}
            (gp, gt, metrics), _ = jax.lax.scan(acc, (zg, zt, zm), batch_mb)
            gp = jax.tree.map(lambda a: a / mb, gp)
            metrics = jax.tree.map(lambda a: a / mb, metrics)
        else:
            metrics, gp, gt = lg(batch)

        # ---- global grad norm (count each logical element once) ----
        sq = jnp.zeros((), jnp.float32)
        for grp in ("layers", "top"):
            for name, g in gp[grp].items():
                s = jnp.sum(g.astype(jnp.float32) ** 2)
                for ax in ctx.dp_axes:
                    s = jax.lax.psum(s, ax)
                if not metas[grp][name].tp_replicated and ctx.tp > 1:
                    s = jax.lax.psum(s, ctx.tp_axis)
                sq = sq + s
        gnorm = jnp.sqrt(sq)

        params2, opt2 = O.apply_update(params, gp, opt, step, opt_cfg, gnorm)

        # ---- y state from telemetry ----
        y2 = {"layers": {k: _y_update(y["layers"][k], gt["layers"][k], tc)
                         for k in y["layers"]},
              "top": {k: _y_update(y["top"][k], gt["top"][k], tc)
                      for k in y["top"]}}
        fails = sum(jnp.sum(t[..., 1]) for t in jax.tree.leaves(gt))

        loss_rep = metrics["loss"]
        for ax in ctx.dp_axes:
            loss_rep = jax.lax.psum(loss_rep, ax)
        loss_rep = loss_rep / ctx.dp

        new_state = {"params": params2, "opt": opt2, "y": y2,
                     "step": step + 1, "key": key}
        out_metrics = {"loss": loss_rep, "gnorm": gnorm, "fails": fails}
        if biased:
            loads = metrics["loads"]
            for ax in ctx.dp_axes:
                loads = jax.lax.psum(loads, ax)
            new_state["moe_bias"] = update_bias(bias, loads, tc.bias_rate)
            out_metrics["loads"] = loads
        return new_state, out_metrics

    def step_fn(state, batch):
        f = jax.shard_map(per_device, mesh=mesh,
                          in_specs=(state_spec, batch_spec(batch)),
                          out_specs=(state_spec, P()),
                          check_vma=False)
        return f(state, batch)

    return (jax.jit(step_fn, donate_argnums=(0,) if tc.donate else ()),
            state_spec, pspec)


def init_state(cfg: ModelConfig, ctx: ShardCtx, opt_cfg: O.OptConfig,
               tc: TrainConfig, key: Array) -> dict:
    params = T.init_params(cfg, ctx, key)
    state = {
        "params": params,
        "opt": O.init_opt_state(params, opt_cfg),
        "y": T.y_init(cfg, ctx, tc.y0),
        "step": jnp.zeros((), jnp.int32),
        "key": key,
    }
    if cfg.router == "sigmoid_bias":
        state["moe_bias"] = moe_bias_init(cfg)
    return state


def moe_bias_init(cfg: ModelConfig) -> Array:
    return jnp.zeros((T.n_scan_steps(cfg), cfg.n_experts), jnp.float32)


class Trainer:
    """Host-side loop with checkpoint/restart fault tolerance."""

    def __init__(self, cfg: ModelConfig, ctx: ShardCtx, mesh,
                 opt_cfg: O.OptConfig, tc: TrainConfig, data_cfg: D.DataConfig,
                 extra_batch: Optional[Callable[[int], dict]] = None,
                 failure_hook: Optional[Callable[[int], None]] = None):
        self.cfg, self.ctx, self.mesh = cfg, ctx, mesh
        self.opt_cfg, self.tc, self.data_cfg = opt_cfg, tc, data_cfg
        self.extra_batch = extra_batch
        self.failure_hook = failure_hook
        self.step_fn, self.state_spec, self.pspec = make_train_step(
            cfg, ctx, mesh, opt_cfg, tc)
        self.metas = T.all_metas(cfg, ctx)
        self.history: list[dict] = []
        self.wire_bytes_step = self._wire_bytes_step()
        print(f"[train] grad sync wire: "
              f"{self.wire_bytes_step / 2**20:.2f} MiB/step per rank "
              f"({self.ctx.fsdp_config().sync}, "
              f"packed={self.ctx.qcfg.packed})", flush=True)
        # anchor-state + prefetch banner, from the one wire_accounting
        # definition (core/wire_accounting via fsdp.anchor_bytes_step):
        # sharded anchors materialize zero bytes beyond the rank's shard
        cur_a = self._anchor_bytes_step(self.ctx.anchor_sharded)
        repl_a = self._anchor_bytes_step(False)
        print(f"[train] anchor state: {cur_a / 2**20:.2f} MiB/step per rank "
              f"(anchored={self.ctx.anchor_grads}, "
              f"sharded={self.ctx.anchor_sharded}; replicated equivalent "
              f"{repl_a / 2**20:.2f} MiB) "
              f"prefetch={'on' if self.ctx.prefetch else 'off'}", flush=True)

    def _wire_bytes_step(self) -> int:
        """Static per-rank wire bytes of one step's DP gradient sync
        (packed lattice payload accounting; fsdp.wire_bytes_bwd)."""
        fcfg = self.ctx.fsdp_config()
        sizes = [int(self.mesh.shape[ax]) for ax in self.ctx.dp_axes]
        per_group = {
            grp: sum(F.wire_bytes_bwd(shard_len(m, self.ctx) * self.ctx.dp,
                                      sizes, fcfg)
                     for m in self.metas[grp].values())
            for grp in ("layers", "top")}
        n_mb = max(self.tc.microbatch, 1)
        layers = T.n_scan_steps(self.cfg) * per_group["layers"]
        return n_mb * (layers + per_group["top"])

    def _anchor_bytes_step(self, sharded: bool) -> int:
        """Static per-rank anchor-state bytes one step materializes beyond
        each rank's own shard (0 unanchored; 0 sharded; the legacy
        replicated layout re-materializes full (m,) anchors —
        fsdp.anchor_bytes_step / core.wire_accounting.anchor_state_bytes)."""
        if not self.ctx.anchor_grads:
            return 0
        fcfg = dataclasses.replace(self.ctx.fsdp_config(),
                                   anchor_sharded=sharded)
        sizes = [int(self.mesh.shape[ax]) for ax in self.ctx.dp_axes]
        per_group = {
            grp: sum(F.anchor_bytes_step(shard_len(m, self.ctx) * self.ctx.dp,
                                         sizes, fcfg)
                     for m in self.metas[grp].values())
            for grp in ("layers", "top")}
        return (T.n_scan_steps(self.cfg) * per_group["layers"]
                + per_group["top"])

    def _batch(self, step: int) -> dict:
        b = D.batch_at(self.data_cfg, step)
        if self.extra_batch is not None:
            b.update(self.extra_batch(step))
        dpa = (self.ctx.dp_axes if len(self.ctx.dp_axes) > 1
               else self.ctx.dp_axes[0])
        return {k: jax.device_put(v, NamedSharding(self.mesh, P(dpa)))
                for k, v in b.items()}

    def save(self, state):
        step = int(state["step"])
        logical = C.params_to_logical(state["params"], self.metas, self.ctx)
        opt_logical = {k: C.params_to_logical(v, self.metas, self.ctx)
                       for k, v in state["opt"].items()}
        y_np = jax.tree.map(np.asarray, state["y"])
        tree = {"params": logical, "opt": opt_logical, "y": y_np}
        if "moe_bias" in state:
            tree["moe_bias"] = np.asarray(state["moe_bias"])
        C.save(self.tc.ckpt_dir, step, tree,
               {"arch": self.cfg.arch}, keep=self.tc.keep)

    def restore(self) -> Optional[dict]:
        step = C.latest_step(self.tc.ckpt_dir)
        if step is None:
            return None
        tree, meta = C.load(self.tc.ckpt_dir)
        params = C.logical_to_params(tree["params"], self.metas, self.ctx)
        state = init_state(self.cfg, self.ctx, self.opt_cfg, self.tc,
                           jax.random.PRNGKey(0))
        state["params"] = params
        if "opt" in tree:
            state["opt"] = {k: C.logical_to_params(v, self.metas, self.ctx)
                            for k, v in tree["opt"].items()}
        # y/anchor shapes depend on the mesh (per-bucket nb, gathered m);
        # an elastic restore onto a different mesh keeps the fresh init —
        # telemetry state re-converges within a few steps.  A checkpoint
        # *missing* the y entry is corrupt and still raises loudly.
        # Checkpoints from before sharded anchors hold replicated (L?, m)
        # anchor leaves: reshard them into the current storage layout first.
        restored_y = jax.tree.map(jnp.asarray, tree["y"])
        restored_y = C.reshard_y(restored_y, state["y"])
        if (jax.tree.structure(restored_y) == jax.tree.structure(state["y"])
                and all(a.shape == b.shape for a, b in
                        zip(jax.tree.leaves(restored_y),
                            jax.tree.leaves(state["y"])))):
            state["y"] = restored_y
        if "moe_bias" in tree and "moe_bias" in state:
            state["moe_bias"] = jnp.asarray(tree["moe_bias"])
        state["step"] = jnp.asarray(step, jnp.int32)
        return state

    def train(self, state: Optional[dict] = None) -> dict:
        if state is None:
            state = self.restore() or init_state(
                self.cfg, self.ctx, self.opt_cfg, self.tc,
                jax.random.PRNGKey(0))
        restarts = 0
        loads_acc = None       # summed on the device, read at log steps
        while int(state["step"]) < self.tc.steps:
            step = int(state["step"])
            try:
                if self.failure_hook is not None:
                    self.failure_hook(step)
                batch = self._batch(step)
                t0 = time.perf_counter()
                state, metrics = self.step_fn(state, batch)
                if "loads" in metrics:
                    loads = metrics.pop("loads")
                    loads_acc = loads if loads_acc is None else \
                        loads_acc + loads
                if step % self.tc.log_every == 0:
                    if loads_acc is not None:
                        record_moe_loads(loads_acc, self.cfg)
                        loads_acc = None
                    m = {k: float(v) for k, v in metrics.items()}
                    m["step"] = step
                    m["dt"] = time.perf_counter() - t0
                    m["wire_mb"] = self.wire_bytes_step / 2**20
                    self.history.append(m)
                    print(f"[train] step={step} loss={m['loss']:.4f} "
                          f"gnorm={m['gnorm']:.3f} fails={m['fails']:.0f} "
                          f"dt={m['dt']:.2f}s", flush=True)
                if (step + 1) % self.tc.ckpt_every == 0:
                    self.save(state)
            except (RuntimeError, jax.errors.JaxRuntimeError) as e:  # device loss
                restarts += 1
                print(f"[train] step {step} failed ({type(e).__name__}: {e}); "
                      f"restart {restarts}/{self.tc.max_restarts}", flush=True)
                if restarts > self.tc.max_restarts:
                    raise
                restored = self.restore()
                if restored is None:
                    state = init_state(self.cfg, self.ctx, self.opt_cfg,
                                       self.tc, jax.random.PRNGKey(0))
                else:
                    state = restored
        if loads_acc is not None:
            record_moe_loads(loads_acc, self.cfg)
        self.save(state)
        return state
