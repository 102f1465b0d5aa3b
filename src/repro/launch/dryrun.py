import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

# ^ MUST precede any jax-importing import: jax locks the device count on
# first backend init.  512 host devices back both production meshes
# (single-pod 16x16 uses the first 256).  Do NOT set this anywhere global —
# smoke tests and benches run on 1 device.

import argparse          # noqa: E402
import json              # noqa: E402
import sys               # noqa: E402
import time              # noqa: E402
import traceback         # noqa: E402

"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

For each cell:
    lowered  = jax.jit(step, in_shardings=..., out_shardings=...).lower(
                   **input ShapeDtypeStructs)          # launch/cells.py
    compiled = lowered.compile()
    print(compiled.memory_analysis())                  # proves it fits
    print(compiled.cost_analysis())                    # flops/bytes
    parse(compiled.as_text())                          # collective bytes

and write results/dryrun/<mesh>/<arch>__<shape>[__<variant>].json with the
roofline inputs.  Failures (sharding mismatch, OOM at compile, unsupported
collective) are bugs in the system — the sweep reports them per cell.

Usage:
    python -m repro.launch.dryrun --arch smollm-360m --shape train_4k \
        --mesh single
    python -m repro.launch.dryrun --all --mesh both --resume
"""


def _cost_dict(compiled):
    try:
        c = compiled.cost_analysis()
    except Exception as e:                       # pragma: no cover
        return {"error": str(e)}
    return {k: float(v) for k, v in c.items()
            if isinstance(v, (int, float))}


def _memory_dict(compiled):
    out = {}
    try:
        m = compiled.memory_analysis()
    except Exception as e:                       # pragma: no cover
        return {"error": str(e)}
    for attr in ("argument_size_in_bytes", "output_size_in_bytes",
                 "temp_size_in_bytes", "alias_size_in_bytes",
                 "generated_code_size_in_bytes"):
        v = getattr(m, attr, None)
        if v is not None:
            out[attr] = int(v)
    if not out and m is not None:
        out["repr"] = str(m)
    return out


def run_cell(arch: str, shape: str, mesh_kind: str, variant: str,
             outdir: str, save_hlo: bool = False, verbose: bool = True):
    import jax
    from repro.launch.cells import SkipCell, lower_cell
    from repro.launch.mesh import make_production_mesh
    from repro.roofline.hlo import collective_bytes_by_type, count_op
    from repro.roofline.terms import (HW_V5E, model_flops_lm,
                                      roofline_terms, useful_fraction)

    mesh = make_production_mesh(multi_pod=(mesh_kind == "multi"))
    n_dev = mesh.devices.size
    tag = f"{arch}__{shape}" + ("" if variant == "baseline"
                                else f"__{variant}")
    os.makedirs(os.path.join(outdir, mesh_kind), exist_ok=True)
    path = os.path.join(outdir, mesh_kind, tag + ".json")

    rec = dict(arch=arch, shape=shape, mesh=mesh_kind, variant=variant,
               n_devices=int(n_dev), status="ok")
    t0 = time.time()
    try:
        with mesh:
            lowered, meta = lower_cell(arch, shape, mesh, variant)
            rec["lower_s"] = round(time.time() - t0, 2)
            t1 = time.time()
            compiled = lowered.compile()
            rec["compile_s"] = round(time.time() - t1, 2)

        rec["meta"] = {k: v for k, v in meta.items()
                       if isinstance(v, (int, float, str))}
        mem = _memory_dict(compiled)
        cost = _cost_dict(compiled)
        rec["memory_analysis"] = mem
        rec["cost_analysis"] = cost

        hlo = compiled.as_text()
        coll_total, coll_by_type = collective_bytes_by_type(hlo)
        rec["collective_bytes_per_device"] = int(coll_total)
        rec["collectives"] = coll_by_type
        rec["hlo_ops"] = dict(fusion=count_op(hlo, "fusion"),
                              transpose=count_op(hlo, "transpose"),
                              copy=count_op(hlo, "copy"))
        if save_hlo:
            import gzip
            with gzip.open(path.replace(".json", ".hlo.gz"), "wt") as f:
                f.write(hlo)

        flops_dev = cost.get("flops", 0.0)
        bytes_dev = cost.get("bytes accessed", 0.0)
        rec["raw"] = dict(flops=flops_dev, bytes=bytes_dev,
                          coll=float(coll_total))

        # scan-corrected metrics (XLA counts scan bodies once — probes
        # extrapolate the real trip counts; see launch/probes.py)
        from repro.launch.probes import corrected_metrics
        t2 = time.time()
        corr = corrected_metrics(arch, shape, mesh, variant)
        rec["probe_s"] = round(time.time() - t2, 2)
        if corr["corrected"] is not None:
            rec["corrected"] = corr["corrected"]
            rec["probes"] = corr["probes"]
            flops_dev = corr["corrected"]["flops"]
            bytes_dev = corr["corrected"]["bytes"]
            coll_total = corr["corrected"]["coll"]

        terms = roofline_terms(flops_dev, bytes_dev, coll_total)
        rec["roofline"] = terms.as_dict()
        model_flops = meta.get("model_flops", 0.0)
        rec["model_flops"] = float(model_flops)
        rec["useful_fraction"] = useful_fraction(
            model_flops, flops_dev * n_dev)
        # per-device HBM residency proof
        arg_b = mem.get("argument_size_in_bytes", 0)
        tmp_b = mem.get("temp_size_in_bytes", 0)
        out_b = mem.get("output_size_in_bytes", 0)
        rec["fits_hbm"] = bool(arg_b + tmp_b <= HW_V5E["hbm_bytes"]) \
            if arg_b else None
        if verbose:
            print(f"[{mesh_kind}] {tag}: lower {rec['lower_s']}s "
                  f"compile {rec['compile_s']}s "
                  f"probes {rec.get('probe_s', 0)}s")
            print(f"  memory: args={arg_b/2**30:.2f}GiB "
                  f"temp={tmp_b/2**30:.2f}GiB out={out_b/2**30:.2f}GiB "
                  f"fits_16GiB={rec['fits_hbm']}")
            print(f"  cost: flops/dev={flops_dev:.3e} "
                  f"bytes/dev={bytes_dev:.3e} coll/dev={coll_total:.3e}")
            print(f"  roofline: compute={terms.compute_s:.4f}s "
                  f"memory={terms.memory_s:.4f}s "
                  f"collective={terms.collective_s:.4f}s "
                  f"-> {terms.dominant}-bound "
                  f"useful={rec['useful_fraction']:.3f}")
    except SkipCell as e:
        rec["status"] = "skip"
        rec["reason"] = str(e)
        if verbose:
            print(f"[{mesh_kind}] {tag}: SKIP — {e}")
    except Exception as e:
        rec["status"] = "error"
        rec["error"] = f"{type(e).__name__}: {e}"
        rec["traceback"] = traceback.format_exc()[-4000:]
        if verbose:
            print(f"[{mesh_kind}] {tag}: ERROR — {type(e).__name__}: {e}")
    rec["total_s"] = round(time.time() - t0, 2)
    with open(path, "w") as f:
        json.dump(rec, f, indent=1)
    return rec


def main():
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch")
    ap.add_argument("--shape")
    ap.add_argument("--mesh", default="single",
                    choices=["single", "multi", "both"])
    ap.add_argument("--variant", default="baseline",
                    help='config overrides, e.g. "num_microbatches=8"')
    ap.add_argument("--all", action="store_true",
                    help="run every assigned cell")
    ap.add_argument("--resume", action="store_true",
                    help="skip cells whose result JSON already exists")
    ap.add_argument("--save-hlo", action="store_true")
    ap.add_argument("--out", default="results/dryrun")
    args = ap.parse_args()

    from repro.launch.cells import all_cells

    meshes = ["single", "multi"] if args.mesh == "both" else [args.mesh]
    cells = all_cells() if args.all else [(args.arch, args.shape)]
    if not args.all and (args.arch is None or args.shape is None):
        ap.error("--arch and --shape required unless --all")

    failures = 0
    for mesh_kind in meshes:
        for arch, shape in cells:
            tag = f"{arch}__{shape}" + ("" if args.variant == "baseline"
                                        else f"__{args.variant}")
            path = os.path.join(args.out, mesh_kind, tag + ".json")
            if args.resume and os.path.exists(path):
                with open(path) as f:
                    prev = json.load(f)
                if prev.get("status") in ("ok", "skip"):
                    print(f"[{mesh_kind}] {tag}: cached "
                          f"({prev['status']})")
                    continue
            rec = run_cell(arch, shape, mesh_kind, args.variant, args.out,
                           save_hlo=args.save_hlo)
            failures += rec["status"] == "error"
    sys.exit(1 if failures else 0)


if __name__ == "__main__":
    main()
