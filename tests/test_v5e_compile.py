"""Compile the main path for a described TPU v5e chip (no chip attached).

The TPU compiler ships with jaxlib and compiles for a v5e:2x2 topology that
is described, not attached.  It refuses what the chip would refuse: a
program over the device's memory, a Pallas kernel Mosaic cannot lower.
Nothing runs, so these tests say nothing about results or time.

The topology is described inside a module fixture, never at import: only
one process at a time may load the TPU library, and every test worker
imports this file.  The persistent compilation cache is off around the
compiles, because an executable for a described chip cannot be read back
without one.

Widths are the d4m-stream deployment's (configs/d4m_stream.py) at a few
instances; ``chip_smoke.py`` runs the same programs at 1,024.
"""
import contextlib
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro import stages
from repro.configs import get_config
from repro.core import distributed
from repro.core import semiring as sr_mod
from repro.kernels.hier_merge.hier_merge import (merge_multi_pallas,
                                                 merge_pallas)
from repro.query import service

CFG = get_config("d4m-stream")
SR = sr_mod.PLUS_TIMES
HBM_BYTES = 16 * 10**9          # one v5e chip
INSTANCES, BLOCKS = 8, 8


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@contextlib.contextmanager
def _no_persistent_cache():
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
        compilation_cache.reset_cache()


def _compile(fn, *args, **jit_kwargs):
    with _no_persistent_cache():
        return jax.jit(fn, **jit_kwargs).lower(*args).compile()


def _placed(tree, sharding):
    return jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sharding),
        tree)


def _states(sharding):
    return _placed(jax.eval_shape(lambda: distributed.create_instances(
        INSTANCES, CFG.cuts, CFG.block_size)), sharding)


def _peak(compiled) -> int:
    m = compiled.memory_analysis()
    return (m.argument_size_in_bytes + m.output_size_in_bytes
            + m.temp_size_in_bytes - m.alias_size_in_bytes)


@pytest.fixture(scope="module")
def ingest_step(one_chip):
    """(compiled ingest step, its abstract fleet state) for the chip."""
    states = _states(one_chip)
    stream = tuple(jax.ShapeDtypeStruct((INSTANCES, BLOCKS, CFG.block_size),
                                        d, sharding=one_chip)
                   for d in (jnp.int32, jnp.int32, jnp.float32))
    w = service.make_ingest_fn(
        SR, use_kernel=CFG.use_kernel, lazy_l0=CFG.lazy_l0, fused=CFG.fused,
        chunk=CFG.chunk, batch_mode=CFG.batch_mode)
    return _compile(w.fn, states, *stream, **dict(w.jit_kwargs)), states


def test_ingest_step_compiles(ingest_step):
    c, states = ingest_step
    m = c.memory_analysis()
    state_bytes = sum(x.size * x.dtype.itemsize
                      for x in jax.tree.leaves(states))
    # the donated fleet state is updated in place on the chip (the alias
    # also covers the tile padding of the per-instance scalars)
    assert m.alias_size_in_bytes >= state_bytes
    assert _peak(c) < HBM_BYTES


def _opcodes(text):
    """``{instruction name: opcode}`` of the optimized HLO text; a fusion's
    opcode carries its kind (``fusion:kCustom``)."""
    out = {}
    for line in text.splitlines():
        m = re.match(r"\s+(?:ROOT )?%?([\w.\-]+) = .*? ([a-z][\w\-]*)\(", line)
        if m:
            kind = re.search(r"kind=(k\w+)", line)
            out[m[1]] = m[2] + (f":{kind[1]}" if m[2] == "fusion" and kind
                                else "")
    return out


def test_ingest_step_names_its_layers(ingest_step):
    """The chip's optimized HLO keeps the program's scopes in each op's
    ``op_name`` (``stages.parse_op_scopes``), no op falls under two cohort
    depths, and the canonicalization runs no scatter: ``canon.value_sum``
    is a segmented scan and ``canon.key_scatter`` a compaction sort (a
    scatter is a ``scatter`` op or, as XLA's TPU backend emits one, a
    ``kCustom`` fusion)."""
    c, _ = ingest_step
    text = c.as_text()
    (table,) = stages.parse_op_scopes(text).values()
    parts = [op.split("/") for op in table.values()]
    for scope in ("cohort.d0", "cohort.d1", "cohort.d2", "cohort.take",
                  "cohort.put", "canon.sort", "canon.value_sum",
                  "canon.key_scatter"):
        assert any(scope in p for p in parts), scope
    assert all(sum(x.startswith("cohort.d") for x in p) <= 1 for p in parts)
    opcodes = _opcodes(text)
    canon = {n: opcodes[n] for n, op in table.items()
             if n in opcodes and any(x.startswith("canon.")
                                     for x in op.split("/"))}
    scatters = {n: (k, table[n]) for n, k in canon.items()
                if k in ("scatter", "fusion:kCustom")
                or table[n].split("/")[-1] in ("scatter", "scatter-add")}
    assert not scatters, scatters
    assert any("canon.value_sum" in table[n].split("/") for n in canon)
    assert any(k == "sort" and "canon.key_scatter" in table[n].split("/")
               for n, k in canon.items())


def test_point_query_compiles(one_chip):
    q = jax.ShapeDtypeStruct((CFG.query_batch,), jnp.int32,
                             sharding=one_chip)
    w = service.make_point_query_fn(SR, use_kernel=CFG.use_kernel,
                                    l0_mode=CFG.query_l0_mode)
    c = _compile(w.fn, _states(one_chip), q, q)
    assert _peak(c) < HBM_BYTES


def test_analytics_compiles_within_a_quarter_of_hbm(one_chip):
    w = service.make_analytics_fn(1 << CFG.rmat_scale, 8, SR)
    c = _compile(w.fn, _states(one_chip))
    assert c.memory_analysis().temp_size_in_bytes < HBM_BYTES // 4


def _runs(n, sharding):
    return tuple(jax.ShapeDtypeStruct((n,), d, sharding=sharding)
                 for d in (jnp.int32, jnp.int32, jnp.float32))


_MOSAIC_REFUSES = pytest.mark.xfail(
    strict=True,
    reason="Mosaic refuses the bitonic merge kernel on v5e: "
    "'Unimplemented primitive in Pallas TPU lowering: rev' (jnp.flip) and "
    "'infer-vector-layout: unsupported shape cast' (the 1-D reshape in "
    "_compare_exchange)")


@_MOSAIC_REFUSES
@pytest.mark.parametrize("entries", [2048, 32768])
def test_merge_pallas_compiles(one_chip, entries):
    a, b = _runs(entries // 2, one_chip), _runs(entries // 2, one_chip)
    _compile(lambda a, b: merge_pallas(*a, *b, interpret=False), a, b)


@_MOSAIC_REFUSES
@pytest.mark.parametrize("entries", [2048, 32768])
def test_merge_multi_pallas_compiles(one_chip, entries):
    block, run = _runs(entries // 2, one_chip), _runs(entries // 2, one_chip)
    _compile(lambda b, r: merge_multi_pallas(b, [r], interpret=False),
             block, run)
