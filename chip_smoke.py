"""Run the D4M fleet's main path on TPU and check its answers.

    python chip_smoke.py              # one chip: ingest, then read-while-ingest
    python chip_smoke.py --chips 4    # the sharded fleet on a 4-chip mesh

The deployment is the paper's own (``configs/d4m_stream.py``): cuts
(2048, 16384, 131072), 1,024-entry R-MAT blocks at scale 22, the fused
cascade with the lazy layer-0 buffer and grouped batching.  One chip holds
``INSTANCES_PER_CHIP`` independent hierarchies (2.1 GiB of layer buffers);
each ingests ``BLOCKS`` blocks in ``ROUNDS`` rounds, so every layer spills.

One chip, through the normal entry points (``stream.ingest_instances_jit``
behind ``service.make_ingest_fn``, then ``service.run_service``):

  a. refuse anything but a TPU;
  b. ingest; check the exact update count, zero overflow, and spills into
     every layer of every instance;
  c. serve: interleaved ingest rounds, Q=256 point lookups and top-8 rows
     at num_rows = 2^scale;
  d. check ``hier.query_all``, the point lookups and the top-k totals of a
     few seeded instances against a plain numpy coalesce of their streams.

``--chips 4`` runs only the sharded path (``distributed.sharded_ingest_fn``,
``aggregate_update_counts_fn``, ``sharded_query_fn``,
``global_degree_histogram_fn``) over 4 x ``INSTANCES_PER_CHIP`` instances
built directly sharded on a ("data",) mesh, and checks each against a host
reference.

Everything runs in this one process, which holds the chip.  Any failed
check exits non-zero; the last line of standard output is a JSON object
naming the device.  Rates printed here are a smoke figure, not a benchmark.
"""
import argparse
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax.sharding import (Mesh, NamedSharding, PartitionSpec as P,  # noqa: E402
                          SingleDeviceSharding)

from repro import stages  # noqa: E402
from repro.configs import get_config  # noqa: E402
from repro.core import distributed, hier  # noqa: E402
from repro.core import semiring as sr_mod  # noqa: E402
from repro.data.powerlaw import instance_streams  # noqa: E402
from repro.query import service  # noqa: E402

INSTANCES_PER_CHIP = 1024
BLOCKS = 64             # per instance in the ingest phase
ROUNDS = 8
SERVICE_ROUNDS = 4      # read-while-ingest rounds, BLOCKS // ROUNDS blocks each
TOP_K = 8
SAMPLES = 4             # instances checked against the numpy reference
NUM_BINS = 32           # log2 out-degree histogram bins
SR = sr_mod.PLUS_TIMES


def check(ok, what: str) -> None:
    print(f"check {'ok  ' if ok else 'FAIL'} {what}", flush=True)
    if not ok:
        sys.exit(f"chip_smoke: check failed: {what}")


def require_tpu(count: int):
    """The first ``count`` TPU devices; exits non-zero on any other backend
    (a CPU run measures nothing this script reports)."""
    devices = jax.devices()
    if devices[0].platform != "tpu":
        sys.exit(f"chip_smoke: needs a TPU, JAX found "
                 f"{devices[0].platform!r}")
    if len(devices) < count:
        sys.exit(f"chip_smoke: needs {count} TPU chips, found "
                 f"{len(devices)}")
    print(f"device {devices[0].device_kind} x{len(devices)}", flush=True)
    return devices[:count]


# ------------------------------------------------------------ reference ---


class Reference:
    """Plain numpy model of a few instances: every (row, col, val) they were
    sent, coalesced by key with the plus.times add."""

    def __init__(self, ids):
        self.ids = [int(i) for i in ids]
        self.parts = {i: [] for i in self.ids}
        self._cache = {}

    def add(self, rows, cols, vals) -> None:
        for i in self.ids:
            self.parts[i].append(tuple(np.asarray(x[i]).ravel()
                                       for x in (rows, cols, vals)))
        self._cache.clear()

    def coalesced(self, i):
        """(rows, cols, totals) of instance ``i``, sorted by (row, col)."""
        if i not in self._cache:
            r, c, v = (np.concatenate(p) for p in zip(*self.parts[i]))
            key = (r.astype(np.int64) << 32) | c.astype(np.int64)
            uniq, inv = np.unique(key, return_inverse=True)
            tot = np.bincount(inv, weights=v.astype(np.float64))
            self._cache[i] = ((uniq >> 32).astype(np.int32),
                              (uniq & 0xFFFFFFFF).astype(np.int32), tot)
        return self._cache[i]

    def lookup(self, i, q_rows, q_cols):
        r, c, tot = self.coalesced(i)
        key = (r.astype(np.int64) << 32) | c.astype(np.int64)
        q = (q_rows.astype(np.int64) << 32) | q_cols.astype(np.int64)
        pos = np.minimum(np.searchsorted(key, q), len(key) - 1)
        return np.where(key[pos] == q, tot[pos], 0.0)

    def out_degrees(self, i):
        r, _, tot = self.coalesced(i)
        rows, inv = np.unique(r, return_inverse=True)
        return rows, np.bincount(inv, weights=tot)


def make_queries(ref: Reference, i: int, n_keys: int, q: int, rng):
    """``q`` keys for instance ``i``: half it holds, half it does not."""
    r, c, _ = ref.coalesced(i)
    n_present = q // 2
    pick = rng.choice(len(r), n_present, replace=False)
    held = set(zip(r.tolist(), c.tolist()))
    absent = []
    while len(absent) < q - n_present:
        key = tuple(int(x) for x in rng.integers(0, n_keys, 2))
        if key not in held:
            held.add(key)
            absent.append(key)
    absent = np.array(absent, np.int32).reshape(-1, 2)
    return (np.concatenate([r[pick], absent[:, 0]]).astype(np.int32),
            np.concatenate([c[pick], absent[:, 1]]).astype(np.int32))


def check_hierarchy(states, ref: Reference) -> None:
    for i in ref.ids:
        h = jax.tree.map(lambda x: x[i], states)
        seg = hier.query_all(h, SR)
        n = int(seg.nnz)
        r, c, tot = ref.coalesced(i)
        check(n == len(r), f"instance {i}: query_all holds {n} keys, "
              f"reference {len(r)}")
        hi, lo = np.asarray(seg.hi[:n]), np.asarray(seg.lo[:n])
        val = np.asarray(seg.val[:n], np.float64)
        check(np.array_equal(hi, r) and np.array_equal(lo, c),
              f"instance {i}: query_all keys equal the reference")
        check(np.allclose(val, tot, rtol=1e-5, atol=0),
              f"instance {i}: query_all values within rtol 1e-5")


def check_lookups(query, ref: Reference, n_keys: int, q: int, rng) -> None:
    """One ``query`` dispatch per sampled instance ``i``, with keys drawn
    for ``i``; ``query(q_rows, q_cols)`` returns [I, Q] answers."""
    for i in ref.ids:
        q_rows, q_cols = make_queries(ref, i, n_keys, q, rng)
        got = np.asarray(query(jnp.asarray(q_rows), jnp.asarray(q_cols)))
        want = ref.lookup(i, q_rows, q_cols)
        present = int(np.count_nonzero(want))
        check(present == q // 2, f"instance {i}: {present} of {q} query "
              f"keys present")
        check(np.allclose(got[i], want, rtol=1e-5, atol=0),
              f"instance {i}: {q} point lookups match")


def check_top_k(totals, ids, ref: Reference) -> None:
    for i in ref.ids:
        rows, deg = ref.out_degrees(i)
        want = np.sort(deg)[::-1][:TOP_K]
        check(np.allclose(totals[i], want, rtol=1e-5, atol=0),
              f"instance {i}: top-{TOP_K} row totals match")
        by_row = dict(zip(rows.tolist(), deg.tolist()))
        check(np.allclose([by_row.get(int(r), -1.0) for r in ids[i]],
                          totals[i], rtol=1e-5, atol=0),
              f"instance {i}: top-{TOP_K} ids carry their totals")


def check_ingest(states, n_inst: int, blocks: int, block: int) -> None:
    count = hier.exact_update_count(states)
    check(count == n_inst * blocks * block,
          f"exact update count {count:,} == {n_inst} x {blocks} x {block}")
    overflow = int(np.asarray(states.overflow).sum())
    check(overflow == 0, f"overflow {overflow} == 0")
    spills = np.asarray(states.spills)
    L = spills.shape[-1]
    print(f"spills into layers 1..{L - 1} per instance (min/mean): "
          + ", ".join(f"{spills[:, d].min()}/{spills[:, d].mean():.2f}"
                      for d in range(L - 1))
          + f"; last-layer pressure events {int(spills[:, -1].sum())}")
    check(all(spills[:, d].min() > 0 for d in range(L - 1)),
          "every instance spilled into every layer")


def state_bytes(states) -> int:
    return sum(int(x.nbytes) for x in jax.tree.leaves(states))


def report(n_inst, states, compile_s, device) -> None:
    sb = state_bytes(states)
    print(f"instances {n_inst}, state {sb:,} bytes "
          f"({sb / 2**30:.3f} GiB)")
    print(f"compile seconds {compile_s:.3f}")
    st = stages.stats()
    print("stages.stats " + json.dumps(
        {k: v for k, v in st.items() if k != "per_entry"}))
    mem = device.memory_stats() or {}
    print(f"peak_bytes_in_use {mem.get('peak_bytes_in_use')}")


def _compile(wrapped, *args) -> float:
    t0 = time.perf_counter()
    wrapped.lower(*args).compile()
    return time.perf_counter() - t0


# ------------------------------------------------------------- one chip ---


def run_one_chip(cfg, n_inst: int, key, seed: int, device) -> dict:
    B, scale = cfg.block_size, cfg.rmat_scale
    num_rows = 1 << scale
    per = BLOCKS // ROUNDS
    sharding = SingleDeviceSharding(device)
    knobs = dict(use_kernel=cfg.use_kernel, lazy_l0=cfg.lazy_l0,
                 fused=cfg.fused, chunk=cfg.chunk, batch_mode=cfg.batch_mode)

    def gen(rnd):
        return instance_streams(jax.random.fold_in(key, rnd), n_inst, per,
                                B, scale, sharding=sharding)

    states = distributed.create_instances(n_inst, cfg.cuts, B,
                                          sharding=sharding)
    ingest = service.make_ingest_fn(SR, **knobs)
    query = service.make_point_query_fn(SR, use_kernel=cfg.use_kernel,
                                        l0_mode=cfg.query_l0_mode)
    analytic = service.make_analytics_fn(num_rows, TOP_K, SR)
    q_abs = jax.ShapeDtypeStruct((cfg.query_batch,), jnp.int32)
    t0 = time.perf_counter()
    first = jax.block_until_ready(gen(0))
    compile_s = time.perf_counter() - t0
    compile_s += _compile(ingest, states, *first)
    compile_s += _compile(query, states, q_abs, q_abs)
    compile_s += _compile(analytic, states)

    rng = np.random.default_rng(seed)
    ref = Reference(sorted(rng.choice(n_inst, SAMPLES, replace=False)))
    print(f"reference instances {ref.ids}")

    # b. ingest
    wall = 0.0
    for rnd in range(ROUNDS):
        stream = first if rnd == 0 else gen(rnd)
        ref.add(*stream)
        t0 = time.perf_counter()
        states = jax.block_until_ready(ingest(states, *stream))
        wall += time.perf_counter() - t0
    check_ingest(states, n_inst, BLOCKS, B)
    ingest_rate = n_inst * BLOCKS * B / wall

    # c. serve while ingesting
    q_rows, q_cols = make_queries(ref, ref.ids[0], num_rows,
                                  cfg.query_batch, rng)
    chunks = [gen(ROUNDS + r) for r in range(SERVICE_ROUNDS)]
    for c in chunks:
        ref.add(*c)
    srv_rows, srv_cols, srv_vals = (jnp.concatenate(x, axis=1)
                                    for x in zip(*chunks))
    del chunks
    states, stats = service.run_service(
        states, srv_rows, srv_cols, srv_vals, jnp.asarray(q_rows),
        jnp.asarray(q_cols), rounds=SERVICE_ROUNDS, sr=SR,
        l0_mode=cfg.query_l0_mode,
        queries_per_round=cfg.queries_per_round,
        analytics_num_rows=num_rows, analytics_k=TOP_K, **knobs)
    del srv_rows, srv_cols, srv_vals
    total_blocks = BLOCKS + SERVICE_ROUNDS * per
    count = hier.exact_update_count(states)
    check(count == n_inst * total_blocks * B,
          f"after serving: exact update count {count:,}")
    check(int(np.asarray(states.overflow).sum()) == 0,
          "after serving: overflow 0")

    # d. answers against the numpy reference
    check_hierarchy(states, ref)
    check_lookups(lambda qr, qc: query(states, qr, qc), ref, num_rows,
                  cfg.query_batch, rng)
    totals, ids = (np.asarray(x) for x in analytic(states))
    check_top_k(totals, ids, ref)

    report(n_inst, states, compile_s, device)
    print(f"smoke figure, not a benchmark: ingest {ingest_rate:,.0f} upd/s; "
          f"serving {stats['updates_per_s']:,.0f} upd/s with "
          f"{stats['queries_per_s']:,.0f} lookups/s, query batch p50 "
          f"{stats['latency_p50_s'] * 1e3:.3f} ms")
    return dict(ingest_rate=ingest_rate, service=stats)


# ----------------------------------------------------------- four chips ---


def host_degree_histogram(rows_all: np.ndarray, num_bins: int) -> np.ndarray:
    """Histogram of floor(log2(out-degree)) over every (instance, row):
    with unit values an out-degree is the number of updates naming the
    row, i.e. the run length of the row in the instance's sorted stream."""
    s = np.sort(rows_all, axis=1)
    starts = np.ones(s.shape, bool)
    starts[:, 1:] = s[:, 1:] != s[:, :-1]
    pos = np.flatnonzero(starts.ravel())
    deg = np.diff(np.append(pos, starts.size))
    bins = np.minimum(np.frexp(deg)[1] - 1, num_bins - 1)
    return np.bincount(bins, minlength=num_bins)


def run_sharded(cfg, n_per_chip: int, key, seed: int, devices) -> None:
    B, scale = cfg.block_size, cfg.rmat_scale
    num_rows = 1 << scale
    per = BLOCKS // ROUNDS
    n_inst = n_per_chip * len(devices)
    mesh = Mesh(np.array(devices), ("data",))
    axes = ("data",)
    sharding = NamedSharding(mesh, P("data"))

    def gen(rnd):
        return instance_streams(jax.random.fold_in(key, rnd), n_inst, per,
                                B, scale, sharding=sharding)

    states = distributed.create_instances(n_inst, cfg.cuts, B,
                                          sharding=sharding)
    print(f"mesh {dict(mesh.shape)}, {n_per_chip} instances per chip, "
          f"state sharding {states.layers[-1].hi.sharding.spec}")
    ingest = distributed.sharded_ingest_fn(
        mesh, axes, SR, lazy_l0=cfg.lazy_l0, use_kernel=cfg.use_kernel,
        fused=cfg.fused, chunk=cfg.chunk, batch_mode=cfg.batch_mode)
    count_fn = distributed.aggregate_update_counts_fn(mesh, axes)
    fleet_query = distributed.sharded_query_fn(
        mesh, axes, SR, use_kernel=cfg.use_kernel, l0_mode=cfg.query_l0_mode)
    per_instance = distributed.sharded_query_fn(
        mesh, axes, SR, use_kernel=cfg.use_kernel, l0_mode=cfg.query_l0_mode,
        per_instance=True)
    histogram = distributed.global_degree_histogram_fn(
        mesh, axes, num_rows, NUM_BINS)
    q_abs = jax.ShapeDtypeStruct((cfg.query_batch,), jnp.int32)
    t0 = time.perf_counter()
    first = jax.block_until_ready(gen(0))
    compile_s = time.perf_counter() - t0
    compile_s += _compile(ingest, states, *first)
    compile_s += _compile(fleet_query, states, q_abs, q_abs)
    compile_s += _compile(per_instance, states, q_abs, q_abs)
    compile_s += _compile(histogram, states)

    rng = np.random.default_rng(seed)
    ref = Reference(sorted(rng.choice(n_inst, SAMPLES, replace=False)))
    print(f"reference instances {ref.ids}")
    rows_all = np.empty((n_inst, BLOCKS * B), np.int32)
    wall = 0.0
    for rnd in range(ROUNDS):
        stream = first if rnd == 0 else gen(rnd)
        ref.add(*stream)
        rows_all[:, rnd * per * B:(rnd + 1) * per * B] = \
            np.asarray(stream[0]).reshape(n_inst, per * B)
        t0 = time.perf_counter()
        states, _ = ingest(states, *stream)
        states = jax.block_until_ready(states)
        wall += time.perf_counter() - t0
    check_ingest(states, n_inst, BLOCKS, B)

    fleet_count = int(count_fn(states))
    check(fleet_count == n_inst * BLOCKS * B,
          f"aggregate_update_counts_fn {fleet_count:,} == exact count")

    def lookups(qr, qc):
        """Per-instance answers, checked against the fleet-wide query."""
        fleet = np.asarray(fleet_query(states, qr, qc), np.float64)
        local = np.asarray(per_instance(states, qr, qc))
        check(local.shape == (n_inst, len(qr)),
              f"per-instance answers {local.shape}")
        check(np.allclose(fleet, local.astype(np.float64).sum(axis=0),
                          rtol=1e-5, atol=0),
              "fleet-wide lookups == per-instance answers summed on the host")
        return local

    check_lookups(lookups, ref, num_rows, cfg.query_batch, rng)
    check_hierarchy(states, ref)

    got = np.asarray(histogram(states))
    want = host_degree_histogram(rows_all, NUM_BINS)
    print(f"degree histogram (log2 bins) {got[:20].tolist()}")
    check(np.array_equal(got, want),
          "global_degree_histogram_fn == numpy histogram of out-degrees")

    report(n_inst, states, compile_s, devices[0])
    print(f"smoke figure, not a benchmark: sharded ingest "
          f"{n_inst * BLOCKS * B / wall:,.0f} upd/s over {len(devices)} chips")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4 runs only the sharded fleet path on a 4-chip "
                    "mesh")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    devices = require_tpu(args.chips)
    stages.set_cache_dir(stages.default_cache_dir())
    print(f"compile cache {stages.cache_dir()}")
    cfg = get_config("d4m-stream")
    key = jax.random.PRNGKey(args.seed)
    if args.chips == 1:
        run_one_chip(cfg, INSTANCES_PER_CHIP, key, args.seed, devices[0])
    else:
        run_sharded(cfg, INSTANCES_PER_CHIP, key, args.seed, devices)
    d = jax.devices()
    print(json.dumps({"ok": True, "device": {
        "platform": d[0].platform, "kind": d[0].device_kind,
        "count": len(d)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
