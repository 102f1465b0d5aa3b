"""Power-law (R-MAT / Kronecker) edge-stream generator — paper §III workload.

The paper benchmarks "a power-law graph of 100,000,000 entries divided up
into 1,000 sets of 100,000 entries" per instance.  R-MAT with Graph500
parameters (a=.57, b=.19, c=.19, d=.05) is the standard generator for that
family and is what Kepner's prior D4M benchmarks use.  Fully vectorized in
JAX: one categorical draw per (edge, scale-bit).
"""
from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp

from repro import stages

GRAPH500 = (0.57, 0.19, 0.19, 0.05)


def rmat_edges(key: jax.Array, n_edges: int, scale: int,
               params: Tuple[float, float, float, float] = GRAPH500
               ) -> Tuple[jax.Array, jax.Array]:
    """Sample n_edges (row, col) pairs on a 2^scale x 2^scale vertex grid."""
    n_edges, scale = int(n_edges), int(scale)
    params = tuple(float(p) for p in params)
    sig = stages.signature_of(extra=(("n_edges", n_edges), ("scale", scale),
                                     ("params", params)))
    return stages.dispatch(
        "data.rmat_edges", sig,
        lambda: lambda key: _rmat_edges_body(key, n_edges, scale, params),
        key)


def _rmat_edges_body(key, n_edges, scale, params):
    probs = jnp.asarray(params)
    quad = jax.random.categorical(
        key, jnp.log(probs), shape=(n_edges, scale))      # [E, S] in {0..3}
    row_bits = (quad >> 1).astype(jnp.int32)              # quadrant row bit
    col_bits = (quad & 1).astype(jnp.int32)
    weights = (1 << jnp.arange(scale, dtype=jnp.int32))
    rows = jnp.sum(row_bits * weights, axis=1).astype(jnp.int32)
    cols = jnp.sum(col_bits * weights, axis=1).astype(jnp.int32)
    return rows, cols


def rmat_stream(key: jax.Array, n_blocks: int, block_size: int, scale: int,
                params: Tuple[float, float, float, float] = GRAPH500):
    """The paper's per-instance stream: [T, B] update blocks with unit values.

    (T=1000, B=100000, total 1e8 for the full-size experiment.)
    """
    n_blocks, block_size, scale = int(n_blocks), int(block_size), int(scale)
    params = tuple(float(p) for p in params)
    sig = stages.signature_of(
        block_size=block_size,
        extra=(("n_blocks", n_blocks), ("scale", scale), ("params", params)))

    def body(key):
        rows, cols = _rmat_edges_body(key, n_blocks * block_size, scale,
                                      params)
        vals = jnp.ones((n_blocks, block_size), jnp.float32)
        return (rows.reshape(n_blocks, block_size),
                cols.reshape(n_blocks, block_size), vals)

    return stages.dispatch("data.rmat_stream", sig, lambda: body, key)


def instance_streams(key: jax.Array, n_instances: int, n_blocks: int,
                     block_size: int, scale: int,
                     params=GRAPH500, sharding=None):
    """Independent streams for many instances: [I, T, B] arrays.

    Each instance gets a distinct fold of the key — the paper's "thousands of
    processors each creating many different graphs".  One compiled program
    generates the streams where ``sharding`` places them (default: the
    default device); split on the instance axis, the fleet's stream never
    lands whole on one device.  The per-bit categorical draw is fused
    instead of materialized.
    """
    def body(key):
        keys = jax.random.split(key, n_instances)
        return jax.vmap(
            lambda k: rmat_stream(k, n_blocks, block_size, scale, params))(
                keys)

    sig = stages.signature_of(
        block_size=int(block_size),
        extra=(("n_instances", int(n_instances)), ("n_blocks", int(n_blocks)),
               ("scale", int(scale)),
               ("params", tuple(float(p) for p in params))))
    return stages.wrap(body, "data.instance_streams", sig,
                       out_shardings=sharding)(key)


def degree_tail_exponent(degrees) -> float:
    """Crude MLE power-law exponent over the degree tail (sanity checks)."""
    import numpy as np
    d = np.asarray(degrees)
    d = d[d >= 1].astype(np.float64)
    if d.size < 10:
        return float("nan")
    xmin = max(1.0, np.percentile(d, 50))
    tail = d[d >= xmin]
    return 1.0 + tail.size / np.sum(np.log(tail / xmin))
