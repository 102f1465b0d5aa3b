"""Staged lowering + keyed compile cache (repro/stages.py).

Covers the ISSUE 6 acceptance grid:

  * ONE knob validator: an invalid combination fails with the identical
    ``invalid d4m config signature`` message at every entry point
    (stream.ingest_jit, hier.update, stream.update_instances,
    service.make_ingest_fn);
  * wrap/lower/compile stats: compiles are counted once per signature,
    repeat dispatches are memory hits;
  * persistence round-trip: compile in one process "life", clear the
    in-memory caches (simulated cold start, persistent cache kept), and
    prove the fresh stages instance reports disk hits, ZERO compiles, and
    bit-identical results for ingest and query dispatches across
    batch_mode {grouped, bucketed} x semiring;
  * the launch acceptance: ``precompile_fleet`` + warm cache => a
    subsequent in-process ``launch/ingest`` + ``launch/query`` run
    performs zero compile events (``stages.stats()``).
"""
import argparse

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import stages
from repro.core import distributed, hier, semiring, stream
from repro.data import powerlaw
from repro.query import service


@pytest.fixture
def cache_dir(tmp_path):
    """Point the persistence layer at a fresh directory for one test and
    always detach it afterwards (process-global state)."""
    stages.set_cache_dir(str(tmp_path))
    try:
        yield str(tmp_path)
    finally:
        stages.set_cache_dir(None)


def _stream_batch(I=2, T=4, B=8, nkeys=48, seed=0):
    rng = np.random.default_rng(seed)
    rows = jnp.asarray(rng.integers(0, nkeys, (I, T, B)), jnp.int32)
    cols = jnp.asarray(rng.integers(0, nkeys, (I, T, B)), jnp.int32)
    vals = jnp.asarray(rng.normal(size=(I, T, B)), jnp.float32)
    return rows, cols, vals


# ----------------------------------------------------------- signatures -----


def test_signature_of_validates_knobs():
    with pytest.raises(ValueError, match="strictly increasing"):
        stages.signature_of(cuts=(64, 16))
    with pytest.raises(ValueError, match="block_size"):
        stages.signature_of(cuts=(16, 64), block_size=0)
    with pytest.raises(ValueError, match="semiring"):
        stages.signature_of(sr="no.such.semiring")
    with pytest.raises(ValueError, match="chunk"):
        stages.signature_of(chunk=0)
    with pytest.raises(ValueError, match="batch_mode"):
        stages.signature_of(batch_mode="sideways")
    with pytest.raises(ValueError, match="l0_mode"):
        stages.signature_of(l0_mode="psychic")
    with pytest.raises(ValueError, match="plus.times"):
        stages.signature_of(sr=semiring.MAX_PLUS, lazy_l0=True)


def test_invalid_combo_fails_identically_at_every_entry_point():
    """The satellite: one shared canonicalizer means ONE error message.
    ``lazy_l0`` outside plus.times is the probe combo; every front door
    must raise the same ValueError text."""
    I, B = 2, 8
    cuts = (16, 64)
    h = hier.create(cuts, B)
    states = distributed.create_instances(I, cuts, B)
    r = jnp.zeros((B,), jnp.int32)
    v = jnp.zeros((B,), jnp.float32)
    rb = jnp.zeros((I, B), jnp.int32)
    vb = jnp.zeros((I, B), jnp.float32)

    def msg(fn):
        with pytest.raises(ValueError) as ei:
            fn()
        return str(ei.value)

    messages = {
        "stream.ingest_jit": msg(lambda: stream.ingest_jit(
            cuts, B, sr=semiring.MAX_PLUS, lazy_l0=True)),
        "hier.update": msg(lambda: hier.update(
            h, r, r, v, sr=semiring.MAX_PLUS, lazy_l0=True)),
        "stream.update_instances": msg(lambda: stream.update_instances(
            states, rb, rb, vb, sr=semiring.MAX_PLUS, lazy_l0=True)),
        "service.make_ingest_fn": msg(lambda: service.make_ingest_fn(
            semiring.MAX_PLUS, lazy_l0=True)),
    }
    texts = set(messages.values())
    assert len(texts) == 1, messages
    text = texts.pop()
    assert text.startswith("invalid d4m config signature:")
    assert "plus.times" in text


def test_wrap_is_memoized_and_counts_compiles():
    sig = stages.signature_of(extra=(("test", "wrap_memo"),))

    def f(x):
        return x * 2.0

    w1 = stages.wrap(f, "test.wrap_memo", sig)
    w2 = stages.wrap(lambda x: x * 2.0, "test.wrap_memo", sig)
    assert w1 is w2          # second wrap of the same key reuses the first

    before = stages.stats()
    x = jnp.arange(4, dtype=jnp.float32)
    np.testing.assert_array_equal(np.asarray(w1(x)), np.asarray(x) * 2.0)
    mid = stages.stats()
    assert mid["compiles"] == before["compiles"] + 1
    assert mid["lowerings"] == before["lowerings"] + 1
    w1(x)
    after = stages.stats()
    assert after["compiles"] == mid["compiles"]         # no recompile
    assert after["memory_hits"] == mid["memory_hits"] + 1
    # new avals => new cache entry, one more compile
    w1(jnp.arange(8, dtype=jnp.float32))
    assert stages.stats()["compiles"] == after["compiles"] + 1


def test_wrapped_inlines_under_ambient_trace():
    """Calling a Wrapped with tracers must inline the plain function (so
    wrapped entry points compose under jit/vmap/scan) — and must not touch
    the dispatch counters."""
    sig = stages.signature_of(extra=(("test", "inline"),))
    w = stages.wrap(lambda x: x + 1.0, "test.inline", sig)
    before = stages.stats()

    @jax.jit
    def outer(x):
        return w(x) * 3.0

    out = outer(jnp.float32(1.0))
    assert float(out) == 6.0
    # the outer jit is a plain jax.jit, invisible to stages
    assert stages.stats()["dispatches"] == before["dispatches"]


# ----------------------------------------------------------- persistence ----


ROUND_TRIP_GRID = [
    ("grouped", "plus.times"),
    ("grouped", "max.plus"),
    ("bucketed", "plus.times"),
    ("bucketed", "max.plus"),
]


def test_persistence_round_trip(cache_dir):
    """Lower+compile in one process life, write the cache dir, then prove a
    fresh stages instance (cleared memory, same disk) reports cache hits
    and bit-identical ingest AND query results across
    batch_mode {grouped, bucketed} x semiring."""
    I, T, B = 2, 4, 8
    cuts = (16, 64, 512)
    rows, cols, vals = _stream_batch(I, T, B)
    qr = jnp.asarray([0, 3, 7, 11, 46, 60], jnp.int32)
    qc = jnp.asarray([1, 3, 9, 11, 2, 61], jnp.int32)

    def run_all():
        out = {}
        for batch_mode, sr_name in ROUND_TRIP_GRID:
            sr = semiring.get(sr_name)
            states = distributed.create_instances(I, cuts, B, sr=sr)
            final, telem = stream.ingest_instances(
                states, rows, cols, vals, sr=sr, batch_mode=batch_mode)
            q = service.make_point_query_fn(sr)(final, qr, qc)
            out[(batch_mode, sr_name)] = (
                jax.tree.map(np.asarray, final), np.asarray(telem["nnz0"]),
                np.asarray(q))
        return out

    warm = run_all()
    s_warm = stages.stats()
    assert s_warm["compiles"] > 0
    assert s_warm["disk_writes"] > 0        # executables actually persisted

    # simulated cold start: in-memory caches dropped, persistent cache kept
    stages.clear_memory_cache()
    stages.reset_stats()
    cold = run_all()
    s_cold = stages.stats()
    assert s_cold["compiles"] == 0, s_cold
    assert s_cold["disk_hits"] > 0, s_cold

    for key in warm:
        w_state, w_nnz0, w_q = warm[key]
        c_state, c_nnz0, c_q = cold[key]
        for wl, cl in zip(jax.tree_util.tree_leaves(w_state),
                          jax.tree_util.tree_leaves(c_state)):
            np.testing.assert_array_equal(wl, cl)
        np.testing.assert_array_equal(w_nnz0, c_nnz0)
        np.testing.assert_array_equal(w_q, c_q)     # bit-identical


# ------------------------------------------- disk-loaded introspection ------


def test_disk_loaded_executable_degrades_to_relowering(cache_dir):
    """An executable loaded from the persistent cache may not implement
    cost_analysis()/as_text(); ``stages.Compiled`` must degrade by
    re-lowering from the cache key's abstract avals instead of raising
    ``AttributeError`` into tracekit or ``stats()`` consumers."""
    sig = stages.signature_of(extra=(("test", "disk_introspect"),))
    make_fn = lambda: lambda x: x * 3.0   # noqa: E731
    x = jnp.arange(4, dtype=jnp.float32)
    stages.wrap(make_fn(), "test.disk_introspect", sig)(x)   # compile+persist

    # simulated cold start: memory caches dropped, persistent cache kept;
    # the entry is re-wrapped by a fresh factory call (as at startup, so
    # jax's own in-process caches cannot serve it) and loaded from disk
    stages.clear_memory_cache()
    stages.reset_stats()
    w = stages.wrap(make_fn(), "test.disk_introspect", sig)
    comp = stages.compiled_for(w, x)
    assert comp.from_disk and stages.stats()["compiles"] == 0

    # worst case: the deserialized executable answers NOTHING — swap in an
    # introspection-free stub and prove every analysis surface degrades
    class _Opaque:
        pass

    comp._executable = _Opaque()
    lowerings_before = stages.stats()["lowerings"]
    cost = comp.cost_analysis()
    assert float(cost.get("flops", 0)) > 0
    assert "4xf32" in comp.as_text()    # the re-lowered StableHLO
    assert comp.memory_analysis() is None   # no memory surface to degrade to
    # the Lowered the load went through serves both calls: no re-lowering
    assert stages.stats()["lowerings"] == lowerings_before

    # cost_of never raises on the same degraded executable either
    out = stages.cost_of(w, x)
    assert out["flops"] is not None and out["bytes_accessed"] is not None

    # ... but if the Wrapped builder is ALSO gone, the failure is an
    # informative AttributeError, not a bare delegation crash
    stages.clear_memory_cache()
    comp._executable = _Opaque()
    with pytest.raises(AttributeError, match="rebuild it"):
        comp.cost_analysis()


# --------------------------------------------------- launch acceptance ------


def test_precompile_fleet_then_launch_zero_compiles(cache_dir):
    """The ISSUE acceptance criterion: ``stages.precompile_fleet`` + warm
    persistent cache => a subsequent ``launch/ingest`` + ``launch/query``
    run performs ZERO compile events."""
    from repro.launch import ingest as launch_ingest
    from repro.launch import query as launch_query

    I, blocks, B, rounds, scale = 2, 8, 64, 4, 12
    cuts = (128, 1024, 8192)
    n_keys = 1 << scale
    queries, top_k = 16, 4
    sig = stages.signature_of(cuts=cuts, block_size=B, fused=True,
                              lazy_l0=True, chunk=1, batch_mode="grouped",
                              l0_mode="auto")
    report = stages.precompile_fleet(
        sig, instances=I, blocks=blocks // rounds, queries=queries,
        analytics_num_rows=n_keys, analytics_k=top_k)
    assert set(report) >= {"stream.ingest_instances", "service.ingest",
                           "service.point_query", "service.analytics",
                           "hier.update", "hier.flush", "hier.query_all",
                           "query.engine.point_lookup"}
    # the launches' one-off set-up programs lie outside the dispatch set:
    # fleet construction and the synthetic stream generator, at the shapes
    # both launches build
    distributed.create_instances(I, cuts, B)
    for t in (blocks // rounds, blocks):
        powerlaw.instance_streams(jax.random.PRNGKey(0), I, t, B, scale)

    stages.reset_stats()
    ingest_args = argparse.Namespace(
        instances=I, blocks=blocks, block_size=B, rounds=rounds,
        cuts=",".join(map(str, cuts)), scale=scale, seed=0, ckpt_dir="",
        ckpt_every=4, resume=False, verbose=False, layered=False,
        lazy_l0="auto", chunk=1, use_kernel=False, batch_mode="grouped",
        precompile=False)
    out_i = launch_ingest.run(ingest_args)
    assert out_i["total_updates"] == I * blocks * B // rounds * rounds

    query_args = argparse.Namespace(
        instances=I, blocks=blocks, block_size=B, rounds=rounds,
        cuts=",".join(map(str, cuts)), scale=scale, seed=0,
        queries=queries, queries_per_round=1, l0_mode="auto", top_k=top_k,
        no_analytics=False, layered=False, no_lazy_l0=False, chunk=1,
        use_kernel=False, batch_mode="grouped",
        precompile=False)
    out_q = launch_query.run(query_args)
    assert out_q["updates_per_s"] > 0

    s = stages.stats()
    assert s["compiles"] == 0, s
    assert s["lowerings"] == 0, s
    assert s["memory_hits"] > 0, s

    # and a simulated fresh process (memory cleared, disk warm): the same
    # precompile pass re-lowers every entry (the persistent cache is keyed
    # by the lowered program) and deserializes it — zero compiles
    stages.clear_memory_cache()
    stages.reset_stats()
    report2 = stages.precompile_fleet(
        sig, instances=I, blocks=blocks // rounds, queries=queries,
        analytics_num_rows=n_keys, analytics_k=top_k)
    assert set(report2.values()) == {"disk"}, report2
    s2 = stages.stats()
    assert s2["compiles"] == 0, s2
    assert s2["disk_hits"] == s2["lowerings"] == len(report2), s2
