"""Traffic generator: one cycle's update stream.

Reads a traffic mix (``bench/traffic/<mix>.json``) and a configuration
(``bench/configs/<name>.json``) and makes, from the seed alone:

* the cycle's update stream, on the device, as per-round ``[I, T, B]``
  arrays (rows, cols int32; the unit values share one array).  Instance
  ``i``'s blocks in round ``r`` come from the key ``fold(fold(seed, r), i)``,
  so they do not depend on how the generator is chunked.  No generator
  call makes more than ``MAX_EDGES`` edges, the size proven on the chip;
* which instances the reference checks.

The R-MAT body is a copy of ``repro.data.powerlaw._rmat_edges_body`` (the
Graph500 quadrant recursion), kept here so that no change to the program
can change the benchmark's traffic.
"""
from __future__ import annotations

import functools
from typing import List, Tuple

import jax
import jax.numpy as jnp
import numpy as np

MAX_EDGES = 8_388_608
SAMPLES = 4          # instances the reference checks
STREAM_TAG, SAMPLE_TAG = 1, 3


def seed_key(seed: int, tag: int) -> jax.Array:
    """A threefry key from any non-negative seed (wider than 32 bits too)."""
    state = np.random.SeedSequence([int(seed), tag]).generate_state(
        2, np.uint32)
    return jax.random.wrap_key_data(jnp.asarray(state), impl="threefry2x32")


def host_rng(seed: int, tag: int) -> np.random.Generator:
    return np.random.default_rng([int(seed), tag])


def sample_ids(seed: int, n: int) -> List[int]:
    """The instances the reference checks, drawn from the seed."""
    rng = host_rng(seed, SAMPLE_TAG)
    return sorted(int(i) for i in rng.choice(n, min(SAMPLES, n),
                                             replace=False))


def rmat_edges(key, n_edges: int, scale: int, params) -> Tuple:
    """``n_edges`` (row, col) pairs on a 2^scale x 2^scale grid: one
    quadrant draw per (edge, bit)."""
    quad = jax.random.categorical(key, jnp.log(jnp.asarray(params)),
                                  shape=(n_edges, scale))
    weights = 1 << jnp.arange(scale, dtype=jnp.int32)
    rows = jnp.sum((quad >> 1).astype(jnp.int32) * weights, axis=1)
    cols = jnp.sum((quad & 1).astype(jnp.int32) * weights, axis=1)
    return rows.astype(jnp.int32), cols.astype(jnp.int32)


@functools.partial(jax.jit, static_argnums=(3, 4, 5, 6, 7))
def _instances(key, rnd, first, n, blocks, block, scale, params):
    key = jax.random.fold_in(key, rnd)

    def one(i):
        r, c = rmat_edges(jax.random.fold_in(key, i), blocks * block, scale,
                          params)
        return r.reshape(blocks, block), c.reshape(blocks, block)
    return jax.vmap(one)(first + jnp.arange(n, dtype=jnp.uint32))


def chunk_instances(n_local: int, edges_per_instance: int) -> int:
    """Largest divisor of ``n_local`` whose generator call stays within
    ``MAX_EDGES`` (one compiled shape for every chunk)."""
    fits = [c for c in range(1, n_local + 1)
            if n_local % c == 0 and c * edges_per_instance <= MAX_EDGES]
    if not fits:
        raise ValueError(f"one instance's round ({edges_per_instance} "
                         f"edges) exceeds {MAX_EDGES}")
    return max(fits)


def cycle_stream(seed: int, cfg: dict, traffic: dict, device):
    """(rounds, vals) on ``device``: ``rounds[r] = (rows, cols)``, each
    ``[I, T, B]`` int32; ``vals`` is the shared ``[I, T, B]`` array of unit
    values."""
    blocks, n_rounds = traffic["blocks_per_cycle"], traffic["rounds_per_cycle"]
    if blocks % n_rounds:
        raise ValueError(f"{blocks} blocks do not split into {n_rounds} "
                         "rounds")
    n, T, B = cfg["instances_per_chip"], blocks // n_rounds, cfg["block_size"]
    chunk = chunk_instances(n, T * B)
    scale = cfg["rmat_scale"]
    params = tuple(float(p) for p in cfg["rmat_params"])
    key = jax.device_put(seed_key(seed, STREAM_TAG), device)
    rounds = []
    for r in range(n_rounds):
        parts = [_instances(key, np.uint32(r), np.uint32(j * chunk), chunk,
                            T, B, scale, params)
                 for j in range(n // chunk)]
        rounds.append(tuple(parts[0][k] if len(parts) == 1 else
                            jnp.concatenate([p[k] for p in parts])
                            for k in (0, 1)))
    return rounds, jnp.ones((n, T, B), jnp.float32, device=device)
