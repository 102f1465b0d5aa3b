"""The controls of the comparison that decides ``correct``.

    python3 bench/control.py --workload paper-ingest --seeds 11 12 13

A control is the reference put in the program's place, so that each
number ``check`` compares reads what the control would give; a control
has to fail at least one number of the cell.  Two controls:

* ``bf16``: the reference accumulated and held in bfloat16, one step
  below the float32 the configurations state (a store that kept bfloat16
  values);
* ``lost-round``: the reference without the cycle's last round, a store
  that acknowledged updates it cannot read back.

The cycle's stream is made on the device at the cell's own size and
seed, as a run makes it; nothing of the program runs.  Prints one JSON
line per (seed, control) with every number beside its limit.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

import numpy as np

if __name__ == "__main__":
    _ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    sys.path[:0] = [_ROOT]

from bench import check, gen  # noqa: E402
from bench.reference import BF16, Reference  # noqa: E402


def stand_in(ref: Reference, dtype) -> dict:
    """The control's answers in the shape ``check`` reads from a run: one
    instance's hierarchy as a single layer of its coalesced entries, its
    values held in ``dtype``."""
    layers = {}
    for i in ref.ids:
        r, c, v = ref.coalesced(i)
        layers[i] = [(r, c, v.astype(dtype), len(r))]
    return layers


def readings(cfg: dict, traffic: dict, stream, ids, n: int,
             control: str) -> list:
    """Every number the cell compares, with ``control`` in the program's
    place; ``stream`` holds the sampled instances' host rounds."""
    truth = Reference(ids)
    ctl = Reference(ids, BF16 if control == "bf16" else np.float64)
    for rows, cols, vals in stream:
        truth.add(rows, cols, vals)
        ctl.add(rows, cols, vals)
    if control == "lost-round":
        ctl.drop_last()
    updates = n * traffic["blocks_per_cycle"] * cfg["block_size"]
    count = updates - (n * traffic["blocks_per_cycle"] * cfg["block_size"]
                       // traffic["rounds_per_cycle"]
                       if control == "lost-round" else 0)
    store = BF16 if control == "bf16" else np.dtype(cfg["dtype"])
    return check.fleet(count, updates, 0, stand_in(ctl, store), truth,
                       cfg["dtype"])


def host_stream(seed: int, cfg: dict, traffic: dict, device, ids) -> list:
    """The sampled instances' part of the cycle's stream, made on
    ``device`` as a run makes it, on the host."""
    rounds, vals = gen.cycle_stream(seed, cfg, traffic, device)
    idx = np.asarray(ids)
    v = np.asarray(vals[idx])
    return [(np.asarray(r[idx]), np.asarray(c[idx]), v) for r, c in rounds]


def main(argv=None) -> int:
    import jax
    from bench import spec

    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    bench = spec.load()
    cell = spec.cell(bench, args.workload)
    cfg = spec.config(bench, cell["config"])
    traffic = spec.traffic(cell["traffic"])
    device = jax.devices()[0]
    n = cfg["instances_per_chip"]
    for seed in args.seeds:
        ids = gen.sample_ids(seed, n)
        stream = host_stream(seed, cfg, traffic, device, ids)
        for control in ("bf16", "lost-round"):
            numbers = readings(cfg, traffic, stream, ids, n, control)
            print(json.dumps({"workload": args.workload, "seed": seed,
                              "control": control, "samples": gen.SAMPLES,
                              "correct": check.passed(numbers),
                              "checks": check.as_dict(numbers)}),
                  flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
