"""Reproduction of "New Bounds For Distributed Mean Estimation and Variance
Reduction" (ICLR 2021) grown into a jax_pallas training/serving system.

Written against jax 0.9 (``jax.shard_map``, ``jax.sharding.AxisType``,
``jax.make_mesh(axis_types=...)``); importing ``repro`` has no side effects
on jax.
"""
