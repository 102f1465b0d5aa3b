"""Fleet-scale repairs of the one-chip bring-up (CPU, small sizes).

* the service top-k analytics and the global degree histogram map over
  instances in bounded ``lax.map`` batches; their results must equal the
  plain ``vmap`` form they replaced, with and without a remainder batch;
* ``create_instances``/``instance_streams`` place their output where
  asked and equal a per-instance reference;
* ``stages.set_cache_dir`` hands an externally set
  ``JAX_COMPILATION_CACHE_DIR`` back untouched, and the default cache
  directory follows that variable;
* ``chip_smoke.py`` refuses to run without a TPU.
"""
import importlib.util
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from repro import stages
from repro.core import assoc, distributed, hier, semiring, stream
from repro.data import powerlaw
from repro.query import analytics, service

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CUTS, BLOCK, NKEYS = (8, 32, 128), 4, 40


def _fleet(n_inst, steps=12, seed=0, sr=semiring.PLUS_TIMES):
    rng = np.random.default_rng(seed)
    shape = (n_inst, steps, BLOCK)
    rows = jnp.asarray(rng.integers(0, NKEYS, shape), jnp.int32)
    cols = jnp.asarray(rng.integers(0, NKEYS, shape), jnp.int32)
    vals = jnp.asarray(rng.normal(size=shape), jnp.float32)
    states = distributed.create_instances(n_inst, CUTS, BLOCK, sr=sr)
    final, _ = stream.ingest_instances(
        states, rows, cols, vals, sr=sr,
        lazy_l0=sr.name == semiring.PLUS_TIMES.name)
    return final


@pytest.mark.parametrize("budget_rows", [0, 2, 64])
@pytest.mark.parametrize("sr_name", ["plus.times", "max.plus", "min.plus"])
def test_lax_map_analytics_equals_vmap(monkeypatch, budget_rows, sr_name):
    """budget_rows = instances per map step (0: the production budget,
    which takes all 5 in one batch); 2 leaves a remainder batch."""
    if budget_rows:
        monkeypatch.setattr(analytics, "_DENSE_BUDGET_BYTES",
                            budget_rows * analytics._DENSE_VECTORS * 4 * NKEYS)
    assert analytics.instance_batch(NKEYS) == (budget_rows or
                                               analytics.instance_batch(NKEYS))
    sr = semiring.get(sr_name)
    states = _fleet(5, sr=sr)
    stages.clear_memory_cache()     # the budget is read at trace time
    got = service.make_analytics_fn(NKEYS, 4, sr)(states)
    want = jax.vmap(lambda h: analytics.top_k_rows(h, NKEYS, 4, sr=sr))(
        states)
    for g, w in zip(got, want):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))
    stages.clear_memory_cache()


@pytest.mark.parametrize("budget_rows", [0, 3])
def test_lax_map_degree_histogram_equals_vmap(monkeypatch, budget_rows):
    if budget_rows:
        monkeypatch.setattr(analytics, "_DENSE_BUDGET_BYTES",
                            budget_rows * analytics._DENSE_VECTORS * 4 * NKEYS)
    states = _fleet(7, seed=1)
    mesh = jax.make_mesh((1,), ("data",))
    stages.clear_memory_cache()
    got = distributed.global_degree_histogram_fn(
        mesh, ("data",), NKEYS, 8)(states)

    def one_instance(h):
        deg = assoc.reduce_rows(hier.query_all(h), NKEYS)
        bins = jnp.clip(jnp.floor(jnp.log2(jnp.maximum(deg, 1)))
                        .astype(jnp.int32), 0, 7)
        return jnp.zeros((8,), jnp.int32).at[bins].add(
            (deg > 0).astype(jnp.int32))

    want = jax.vmap(one_instance)(states).sum(axis=0)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    assert int(np.asarray(got).sum()) > 0
    stages.clear_memory_cache()


def test_degree_histogram_bins_powers_of_two_exactly():
    """Row r holds one entry of value 2^r, so its out-degree lands in bin r
    (floor(log2) from frexp's exponent; the v5e's log2 put 2^15 in 14)."""
    n = 20
    states = distributed.create_instances(1, (32,), 32)
    rows = jnp.arange(n, dtype=jnp.int32).reshape(1, 1, n)
    rows = jnp.pad(rows, ((0, 0), (0, 0), (0, 32 - n)))
    vals = jnp.pad(2.0 ** jnp.arange(n, dtype=jnp.float32),
                   (0, 32 - n)).reshape(1, 1, 32)
    states, _ = stream.ingest_instances(states, rows, jnp.zeros_like(rows),
                                        vals)
    mesh = jax.make_mesh((1,), ("data",))
    got = distributed.global_degree_histogram_fn(
        mesh, ("data",), 32, 24)(states)
    np.testing.assert_array_equal(np.asarray(got), [1] * n + [0] * 4)


def test_sharded_construction_matches_unsharded():
    """One construction program serves both placements: the placed leaves
    carry the requested sharding, and the values equal a per-instance
    reference (an empty ``hier.create`` and one ``rmat_stream`` per split
    key)."""
    mesh = jax.make_mesh((1,), ("data",))
    sharding = NamedSharding(mesh, P("data"))
    key = jax.random.PRNGKey(3)
    keys = jax.random.split(key, 3)
    want = [powerlaw.rmat_stream(k, 2, 16, 8) for k in keys]
    for placement in (None, sharding):
        streams = powerlaw.instance_streams(key, 3, 2, 16, scale=8,
                                            sharding=placement)
        for field, got in enumerate(streams):
            if placement is not None:
                assert got.sharding == placement
            for i in range(3):
                np.testing.assert_array_equal(np.asarray(got[i]),
                                              np.asarray(want[i][field]))
        states = distributed.create_instances(3, CUTS, BLOCK,
                                              sharding=placement)
        one = hier.create(CUTS, BLOCK)
        for got, leaf in zip(jax.tree.leaves(states), jax.tree.leaves(one)):
            if placement is not None:
                assert got.sharding == placement
            assert got.shape == (3,) + leaf.shape
            for i in range(3):
                np.testing.assert_array_equal(np.asarray(got[i]),
                                              np.asarray(leaf))


_CACHE_PROBE = """
import os, sys
import jax, jax.numpy as jnp
from repro import stages
outside = os.environ.get("JAX_COMPILATION_CACHE_DIR")
assert stages.default_cache_dir() == (outside or stages._CHECKOUT_CACHE)
assert jax.config.jax_compilation_cache_dir == outside
stages.set_cache_dir(sys.argv[1])
assert jax.config.jax_compilation_cache_dir == sys.argv[1]
stages.set_cache_dir(None)
assert jax.config.jax_compilation_cache_dir == outside, \\
    jax.config.jax_compilation_cache_dir
stages.set_cache_dir(stages.default_cache_dir())
w = stages.wrap(lambda x: x * 3 + 1, "test.cache_probe")
print(float(w(jnp.ones(())).block_until_ready()))
"""


def _probe(tmp_path, outside):
    env = dict(os.environ, PYTHONPATH=os.path.join(ROOT, "src"))
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    if outside:
        env["JAX_COMPILATION_CACHE_DIR"] = outside
    out = subprocess.run([sys.executable, "-c", _CACHE_PROBE,
                          str(tmp_path / "explicit")],
                         env=env, capture_output=True, text=True,
                         timeout=300)
    assert out.returncode == 0, out.stderr[-3000:]
    assert out.stdout.strip().endswith("4.0")


def test_set_cache_dir_keeps_outside_cache(tmp_path):
    outside = tmp_path / "outside"
    _probe(tmp_path, str(outside))
    # the compile landed in the outside directory once the explicit one
    # was detached by None
    assert any(outside.iterdir())


def test_default_cache_dir_follows_the_variable(monkeypatch, tmp_path):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    assert stages.default_cache_dir() == os.path.join(ROOT, ".jax-cache")
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert stages.default_cache_dir() == str(tmp_path)


def test_chip_smoke_refuses_cpu():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", os.path.join(ROOT, "chip_smoke.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    assert jax.devices()[0].platform != "tpu"
    with pytest.raises(SystemExit) as exc:
        mod.require_tpu(1)
    assert exc.value.code not in (0, None)
