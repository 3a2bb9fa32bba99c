"""Deterministic synthetic data pipeline.

Stateless-seeded: ``batch_at(step)`` is a pure function of (seed, step,
shape), so a restarted job resumes mid-epoch bit-identically (fault
tolerance) and any DP shard can be regenerated on any host (elasticity,
straggler re-assignment).  The "dataset" is a Zipf-ish token stream with
Markov structure so the LM loss actually decreases (unlike uniform noise).
"""
from __future__ import annotations

import dataclasses
from typing import Iterator, Optional

import jax
import jax.numpy as jnp
import numpy as np

Array = jax.Array


@dataclasses.dataclass(frozen=True)
class DataConfig:
    vocab: int
    seq_len: int
    global_batch: int
    seed: int = 0
    kind: str = "markov"      # markov | uniform


def _zipf_cdf(vocab: int) -> np.ndarray:
    r = np.arange(1, vocab + 1, dtype=np.float64)
    p = 1.0 / r
    return np.cumsum(p / p.sum()).astype(np.float32)


def batch_at(cfg: DataConfig, step: int) -> dict[str, np.ndarray]:
    """Global batch for one step: {"tokens","targets","mask"} (B, S)."""
    key = jax.random.fold_in(jax.random.PRNGKey(cfg.seed), step)
    B, S, V = cfg.global_batch, cfg.seq_len, cfg.vocab
    if cfg.kind == "uniform":
        toks = jax.random.randint(key, (B, S + 1), 0, V, jnp.int32)
    else:
        # order-1 Markov chain: next = (a*cur + noise) % V with Zipf resets
        k1, k2, k3 = jax.random.split(key, 3)
        # inverse-CDF Zipf draw: O(B*S) memory, where a categorical over
        # the vocab draws B*S*V Gumbels (5 GB at B=14, S=2048, V=49155)
        base = jnp.minimum(jnp.searchsorted(
            jnp.asarray(_zipf_cdf(V)), jax.random.uniform(k1, (B, S + 1)),
            side="right"), V - 1)
        drift = jnp.cumsum(jax.random.randint(k2, (B, S + 1), 0, 7), axis=1)
        reset = jax.random.bernoulli(k3, 0.1, (B, S + 1))
        toks = jnp.where(reset, base, (base[:, :1] * 31 + drift) % V).astype(jnp.int32)
    return {
        "tokens": np.asarray(toks[:, :-1]),
        "targets": np.asarray(toks[:, 1:]),
        "mask": np.ones((B, S), np.float32),
    }


def local_batch_at(cfg: DataConfig, step: int, dp_rank: int, dp_size: int
                   ) -> dict[str, np.ndarray]:
    """The dp_rank-th slice of the global batch (per-host loading)."""
    g = batch_at(cfg, step)
    b_loc = cfg.global_batch // dp_size
    sl = slice(dp_rank * b_loc, (dp_rank + 1) * b_loc)
    return {k: v[sl] for k, v in g.items()}


def frames_at(cfg: DataConfig, step: int, n_frames: int, d_model: int
              ) -> np.ndarray:
    """Stub modality frontend (whisper frames / vlm patches): deterministic
    pseudo-embeddings (B, n_frames, d_model)."""
    key = jax.random.fold_in(jax.random.PRNGKey(cfg.seed + 7_777), step)
    return np.asarray(jax.random.normal(key, (cfg.global_batch, n_frames,
                                               d_model), jnp.float32))
