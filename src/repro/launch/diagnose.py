import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

import argparse      # noqa: E402
import re            # noqa: E402
from collections import Counter, defaultdict  # noqa: E402

"""HLO diagnosis for the perf loop: biggest buffers + collective census.

    PYTHONPATH=src python -m repro.launch.diagnose --arch deepseek-v2-236b \
        --shape train_4k --mesh single [--variant k=v,...] [--probe]

--probe compiles the L=2 unrolled grad probe (fast, exact per-layer costs);
without it the full scanned program is compiled.  Prints the top-N largest
tensors with their producing op and the per-type collective bytes — the
"profile" the hypothesis->change->measure loop reads (no real TPU here).
"""

_SHAPE = re.compile(r"(\w+)\[([\d,]+)\]")
_BYTES = {"pred": 1, "s8": 1, "u8": 1, "bf16": 2, "f16": 2, "s16": 2,
          "u16": 2, "f32": 4, "s32": 4, "u32": 4, "f64": 8, "s64": 8,
          "u64": 8}


def analyze(hlo: str, top: int = 20):
    tensors = []
    coll = defaultdict(lambda: [0, 0])
    opcount = Counter()
    for line in hlo.splitlines():
        line = line.strip()
        m = re.match(r"%?[\w\.\-]+ = (\(?)([a-z0-9]+)\[([\d,]*)\]", line)
        if not m:
            continue
        op_m = re.search(r"\]\{?[\d,]*\}?\s+([a-z][\w\-]*)\(", line)
        op = op_m.group(1) if op_m else "?"
        opcount[op] += 1
        dtype, dims = m.group(2), m.group(3)
        if dtype not in _BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        b = n * _BYTES[dtype]
        tensors.append((b, f"{dtype}[{dims}]", op,
                        line.split("=")[0].strip()[:40]))
        for c in ("all-gather", "all-reduce", "reduce-scatter",
                  "all-to-all", "collective-permute"):
            if f" {c}(" in line:
                coll[c][0] += b
                coll[c][1] += 1
    tensors.sort(reverse=True)
    print(f"== top {top} tensors (per-device) ==")
    seen = set()
    shown = 0
    for b, shape, op, name in tensors:
        key = (shape, op)
        if key in seen:
            continue
        seen.add(key)
        print(f"  {b/2**30:8.3f} GiB  {shape:<28s} {op:<18s} {name}")
        shown += 1
        if shown >= top:
            break
    print("== collectives (per-device result bytes) ==")
    for c, (b, n) in sorted(coll.items()):
        print(f"  {c:<20s} {b/2**30:8.3f} GiB over {n} ops")
    print("== op census ==", dict(opcount.most_common(12)))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch")
    ap.add_argument("--shape")
    ap.add_argument("--mesh", default="single", choices=["single", "multi"])
    ap.add_argument("--variant", default="baseline")
    ap.add_argument("--probe", action="store_true")
    ap.add_argument("--top", type=int, default=20)
    ap.add_argument("--audit", action="store_true",
                    help="tracekit fleet audit instead of a single-program "
                    "diagnosis: J001-J006 + cost budgets over the whole "
                    "stages dispatch set (ISSUE 8); no --arch needed")
    ap.add_argument("--audit-config", default="smoke",
                    choices=("smoke", "production"),
                    help="fleet config for --audit (entry set is identical, "
                    "only shapes differ)")
    args = ap.parse_args()

    if args.audit:
        from repro.analysis import tracekit
        raise SystemExit(tracekit.main(["--check",
                                        "--config", args.audit_config]))
    if not args.arch or not args.shape:
        ap.error("--arch and --shape are required unless --audit")

    from repro.launch.mesh import make_production_mesh
    mesh = make_production_mesh(multi_pod=(args.mesh == "multi"))

    if args.probe:
        import dataclasses
        import jax
        import jax.numpy as jnp
        from functools import partial
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.configs import LM_SHAPES, get_config
        from repro.launch.cells import apply_variant, sds
        from repro.launch.probes import _lm_shardings
        from repro.distribution.sharding import use_policy
        from repro.models import transformer as tf

        cfg = apply_variant(get_config(args.arch), args.variant)
        info = LM_SHAPES[args.shape]
        B, S = info["batch"], info["seq"]
        pcfg = dataclasses.replace(cfg, n_layers=2, scan_layers=False,
                                   num_microbatches=1,
                                   prefill_microbatch=0)
        params_abs = jax.eval_shape(lambda k: tf.init(k, pcfg),
                                    jax.random.PRNGKey(0))
        policy, param_sh = _lm_shardings(pcfg, mesh, params_abs)
        bax = policy.batch_axes
        bax_size = 1
        for a in bax:
            bax_size *= mesh.shape[a]
        mb = min(B, max(B // max(cfg.num_microbatches, 1), bax_size))
        batch_abs = dict(tokens=sds((mb, S), jnp.int32),
                         labels=sds((mb, S), jnp.int32))
        bsh = dict(tokens=NamedSharding(mesh, P(bax)),
                   labels=NamedSharding(mesh, P(bax)))
        grad_fn = jax.value_and_grad(partial(tf.loss_fn, cfg=pcfg),
                                     has_aux=True)
        from repro import stages
        sig = stages.signature_of(
            mesh=mesh, extra=(("arch", args.arch), ("lp", 2),
                              ("shape", args.shape),
                              ("variant", args.variant)))
        with use_policy(policy), mesh:
            co = stages.wrap(
                grad_fn, "diagnose.lm_grad", sig,
                in_shardings=(param_sh, bsh),
                out_shardings=(None, param_sh)
            ).lower(params_abs, batch_abs).compile()
        cost = co.cost_analysis()
        print(f"probe L=2 mb={mb} compiled; cost:",
              {k: f"{v:.3e}" for k, v in cost.items()
               if k in ("flops", "bytes accessed")})
        analyze(co.as_text(), args.top)
    else:
        from repro.launch.cells import lower_cell
        with mesh:
            lowered, meta = lower_cell(args.arch, args.shape, mesh,
                                       args.variant)
            co = lowered.compile()
        m = co.memory_analysis()
        print("temp GiB:", getattr(m, "temp_size_in_bytes", 0) / 2**30)
        analyze(co.as_text(), args.top)


if __name__ == "__main__":
    main()
