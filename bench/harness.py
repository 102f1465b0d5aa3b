"""One run of a cell: set-up, a measured window of whole cycles, the check.

A cycle drops the last fleet, builds a fresh one
(``distributed.create_instances``) and feeds it the cycle's rounds: each
round is one ingest dispatch (``service.make_ingest_fn`` →
``stream.ingest_instances``).  The loop is closed: every call is waited for
before the next is sent.  Every cycle does the same work on the same
stream, so a window of whole cycles is stationary however fast the
program gets, and no fleet outgrows its last layer.
"""
from __future__ import annotations

import contextlib
import time
from typing import Callable, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import SingleDeviceSharding

from bench import check, gen
from bench.reference import Reference
from repro.core import distributed
from repro.core import semiring as sr_mod
from repro.query import service

SR = sr_mod.PLUS_TIMES


class Fleet:
    """The system under test on one chip, through the program's entries."""

    def __init__(self, cfg: dict, traffic: dict, device):
        self.cfg, self.traffic = cfg, traffic
        self.n = cfg["instances_per_chip"]
        self.sharding = SingleDeviceSharding(device)
        knobs = {k: cfg[k] for k in ("use_kernel", "lazy_l0", "fused",
                                     "chunk", "batch_mode")}
        self.ingest = service.make_ingest_fn(SR, **knobs)

    def create(self, dtype=None):
        return distributed.create_instances(
            self.n, tuple(self.cfg["cuts"]), self.cfg["block_size"],
            dtype=jnp.dtype(dtype or self.cfg["dtype"]),
            sharding=self.sharding)


def delete(states) -> None:
    """Free a fleet's device buffers now, so two fleets never coexist."""
    for leaf in jax.tree.leaves(states):
        leaf.delete()


class Cycle:
    """One cycle's inputs and the calls that drive it."""

    def __init__(self, fleet: Fleet, stream, vals, ids):
        self.fleet, self.stream, self.vals = fleet, stream, vals
        self.updates = (fleet.n * fleet.traffic["blocks_per_cycle"]
                        * fleet.cfg["block_size"])
        self.ids = list(ids)
        self.phases: Dict[str, float] = {}

    def run(self, span: Optional[Callable] = None,
            rounds: Optional[int] = None):
        """Build a fresh fleet and drive ``rounds`` rounds (all by
        default); returns the fleet."""
        f = self.fleet
        span = span or (lambda _: contextlib.nullcontext())
        with span("bench.create"):
            states = jax.block_until_ready(f.create())
        for r, (rows, cols) in enumerate(self.stream[:rounds]):
            with span("bench.ingest"):
                states = jax.block_until_ready(
                    f.ingest(states, rows, cols, self.vals))
        return states


def setup(cfg: dict, traffic: dict, seed: int, device) -> Cycle:
    """Build the cell's programs and inputs, and run each program once, so
    that nothing compiles in the window."""
    t0 = time.perf_counter()
    fleet = Fleet(cfg, traffic, device)
    t1 = time.perf_counter()
    stream, vals = gen.cycle_stream(seed, cfg, traffic, device)
    cyc = Cycle(fleet, stream, vals, gen.sample_ids(seed, fleet.n))
    jax.block_until_ready((stream, vals))
    t2 = time.perf_counter()
    delete(cyc.run(rounds=1))         # create and ingest once
    cyc.phases = dict(programs=t1 - t0, inputs=t2 - t1,
                      warm=time.perf_counter() - t2)
    return cyc


def window(cyc: Cycle, seconds: float, span=None):
    """Whole cycles until ``seconds`` have passed; returns (fleet left by the
    last cycle, cycles, wall seconds).  With ``span`` (a traced run) the
    window is one cycle."""
    states, cycles = None, 0
    t0 = time.perf_counter()
    while True:
        if states is not None:
            delete(states)
            states = None
        states = cyc.run(span)
        cycles += 1
        wall = time.perf_counter() - t0
        if span or wall >= seconds:
            return states, cycles, wall


def read_fleet(states, ids) -> dict:
    """What the check needs of the fleet, on the host: the exact update
    count and overflow of every instance, spills per depth, and every layer
    of the sampled instances, in the dtypes the device holds."""
    idx = np.asarray(ids)
    lo = np.asarray(states.n_updates, np.int64)
    hi = np.asarray(states.n_updates_hi, np.int64)
    layers = [tuple(np.asarray(x[idx]) for x in (l.hi, l.lo, l.val, l.nnz))
              for l in states.layers]
    return dict(count=int(lo.sum() + (hi.sum() << np.int64(32))),
                overflow=int(np.asarray(states.overflow).sum()),
                spills=np.asarray(states.spills),
                layers={i: [tuple(x[n] for x in layer) for layer in layers]
                        for n, i in enumerate(ids)})


def reference(cyc: Cycle, ids) -> Reference:
    """The reference fed the sampled instances' part of the cycle's stream
    (the benchmark's own input, read back from the device)."""
    ref = Reference(ids)
    idx = np.asarray(ids)
    vals = np.asarray(cyc.vals[idx])
    for rows, cols in cyc.stream:
        ref.add(np.asarray(rows[idx]), np.asarray(cols[idx]), vals)
    return ref


def compare(cyc: Cycle, fleet_host: dict, ref: Reference,
            compiles: int) -> list:
    numbers = check.window(compiles)
    numbers += check.fleet(fleet_host["count"], cyc.updates,
                           fleet_host["overflow"], fleet_host["layers"], ref,
                           cyc.fleet.cfg["dtype"])
    return numbers
