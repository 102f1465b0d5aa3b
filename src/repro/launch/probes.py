"""Scan-corrected cost extraction for the roofline.

XLA's cost analysis counts a ``lax.scan``/while body ONCE regardless of
trip count (verified on this backend: smollm L=2 vs L=4 report identical
flops).  The deployed programs scan over layers (and microbatches, and
ingest blocks), so raw ``cost_analysis()`` under-reports flops/bytes/
collective-bytes by the trip counts.

Correction: compile small UNROLLED probes and extrapolate linearly —

  LM train    probe(L') = one microbatch fwd+bwd, layers+attn unrolled,
              L' in {2,3};  grad(L) = p3 + (L-3)(p3-p2)
              total = num_microbatches * grad(L) + adamw(full params)
  LM decode   total = p3 + (L-3)(p3-p2)          (probes = unrolled decode)
  LM prefill  chunk(L) as decode; total = n_chunks * chunk(L)
  D4M ingest  probe(T') = T' unrolled block-updates, T' in {1,2};
              total = p1 + (T-1)(p2-p1)

GNN / recsys models are python-unrolled already — their full compile is
exact and needs no probes.  Memory analysis is always taken from the FULL
scanned compile (that is the real program's residency).
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Dict, Tuple

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro import stages
from repro.configs import D4M_SHAPES, LM_SHAPES, get_config
from repro.distribution.sharding import (lm_param_specs, make_policy,
                                         to_shardings, use_policy)
from repro.launch.cells import apply_variant, scaled_cuts, sds
from repro.optim.adamw import AdamWConfig, adamw_init, adamw_update

I32 = jnp.int32
F32 = jnp.float32
METRICS = ("flops", "bytes", "coll")


def extract(compiled) -> Dict[str, float]:
    from repro.roofline.hlo import collective_bytes_by_type
    c = compiled.cost_analysis()
    coll, _ = collective_bytes_by_type(compiled.as_text())
    return dict(flops=float(c.get("flops", 0.0)),
                bytes=float(c.get("bytes accessed", 0.0)),
                coll=float(coll))


def _combine(base: Dict[str, float], delta: Dict[str, float], n: float,
             scale: float = 1.0, extra: Dict[str, float] | None = None):
    out = {}
    for m in METRICS:
        d = max(delta[m], 0.0)
        out[m] = scale * (base[m] + n * d) + (extra[m] if extra else 0.0)
    return out


def _lm_shardings(cfg, mesh, params_abs):
    policy = make_policy(mesh, cfg.layout)
    param_sh = to_shardings(lm_param_specs(params_abs, cfg, policy), mesh)
    return policy, param_sh


def _batch_axes(mesh):
    return ("pod", "data") if "pod" in mesh.axis_names else ("data",)


def _probe_sig(arch, shape, mesh, variant, **extra) -> stages.Signature:
    """Signature for one roofline probe: the probe layer count ``lp`` (and
    any other closure knob) rides in ``extra`` so differently-unrolled
    probes never alias one stage-cache entry."""
    base = dict(arch=arch, shape=shape, variant=variant)
    base.update(extra)
    return stages.signature_of(mesh=mesh, extra=tuple(sorted(base.items())))


def lm_corrected(arch: str, shape: str, mesh: Mesh,
                 variant: str = "baseline") -> Dict:
    from repro.models import transformer as tf

    cfg = get_config(arch)
    if variant != "baseline":
        cfg = apply_variant(cfg, variant)
    info = LM_SHAPES[shape]
    B, S = info["batch"], info["seq"]
    kind = info["kind"]
    bax = make_policy(mesh, cfg.layout).batch_axes
    probes = {}

    def probe_cfg(lp):
        return dataclasses.replace(cfg, n_layers=lp, scan_layers=False,
                                   num_microbatches=1, prefill_microbatch=0)

    if kind == "train":
        nm = cfg.num_microbatches
        bax_size = 1
        for a in bax:
            bax_size *= mesh.shape[a]
        mb = min(B, max(B // nm, bax_size))   # divisible probe microbatch
        nm = B // mb
        for lp in (2, 3):
            pcfg = probe_cfg(lp)
            params_abs = jax.eval_shape(lambda k: tf.init(k, pcfg),
                                        jax.random.PRNGKey(0))
            policy, param_sh = _lm_shardings(pcfg, mesh, params_abs)
            batch_abs = dict(tokens=sds((mb, S), I32),
                             labels=sds((mb, S), I32))
            bsh = dict(tokens=NamedSharding(mesh, P(bax)),
                       labels=NamedSharding(mesh, P(bax)))
            grad_fn = jax.value_and_grad(
                partial(tf.loss_fn, cfg=pcfg), has_aux=True)
            with use_policy(policy), mesh:
                co = stages.wrap(
                    grad_fn, "probes.lm_grad",
                    _probe_sig(arch, shape, mesh, variant, lp=lp),
                    in_shardings=(param_sh, bsh),
                    out_shardings=(None, param_sh)
                ).lower(params_abs, batch_abs).compile()
            probes[f"grad_L{lp}"] = extract(co)
        # optimizer at FULL parameter shapes (elementwise, no scan)
        params_abs = jax.eval_shape(lambda k: tf.init(k, cfg),
                                    jax.random.PRNGKey(0))
        policy, param_sh = _lm_shardings(cfg, mesh, params_abs)
        opt_abs = jax.eval_shape(adamw_init, params_abs)
        opt_sh = dict(m=param_sh, v=param_sh,
                      count=NamedSharding(mesh, P()))
        with mesh:
            co = stages.wrap(
                lambda g, s, p: adamw_update(g, s, p, AdamWConfig()),
                "probes.lm_opt", _probe_sig(arch, shape, mesh, variant),
                in_shardings=(param_sh, opt_sh, param_sh),
                out_shardings=(param_sh, opt_sh, None)
            ).lower(params_abs, opt_abs, params_abs).compile()
        probes["opt"] = extract(co)
        p2, p3 = probes["grad_L2"], probes["grad_L3"]
        delta = {m: p3[m] - p2[m] for m in METRICS}
        corrected = _combine(p3, delta, cfg.n_layers - 3, scale=nm,
                             extra=probes["opt"])
    elif kind == "decode":
        for lp in (2, 3):
            pcfg = probe_cfg(lp)
            params_abs = jax.eval_shape(lambda k: tf.init(k, pcfg),
                                        jax.random.PRNGKey(0))
            policy, param_sh = _lm_shardings(pcfg, mesh, params_abs)
            from repro.launch.cells import lm_cache_spec
            cache_abs = jax.eval_shape(
                lambda: tf.init_cache(pcfg, B, S))
            cache_sh = lm_cache_spec(pcfg, mesh,
                                     make_policy(mesh, pcfg.layout), S)
            with use_policy(policy), mesh:
                co = stages.wrap(
                    lambda p, t, c, l: tf.decode_step(p, t, c, l, pcfg),
                    "probes.lm_decode",
                    _probe_sig(arch, shape, mesh, variant, lp=lp),
                    in_shardings=(param_sh, NamedSharding(mesh, P(bax)),
                                  cache_sh, NamedSharding(mesh, P())),
                    out_shardings=(NamedSharding(mesh, P(bax)), cache_sh)
                ).lower(params_abs, sds((B, 1), I32), cache_abs,
                        sds((), I32)).compile()
            probes[f"decode_L{lp}"] = extract(co)
        p2, p3 = probes["decode_L2"], probes["decode_L3"]
        delta = {m: p3[m] - p2[m] for m in METRICS}
        corrected = _combine(p3, delta, cfg.n_layers - 3)
    elif kind == "prefill":
        import math as _math
        bax_size = 1
        for a in bax:
            bax_size *= mesh.shape[a]
        mb = cfg.prefill_microbatch or B
        mb = min(B, -(-mb // bax_size) * bax_size)   # divisible probe chunk
        n_chunks = max(B // mb, 1)
        for lp in (2, 3):
            pcfg = probe_cfg(lp)
            params_abs = jax.eval_shape(lambda k: tf.init(k, pcfg),
                                        jax.random.PRNGKey(0))
            policy, param_sh = _lm_shardings(pcfg, mesh, params_abs)
            with use_policy(policy), mesh:
                co = stages.wrap(
                    lambda p, t: tf.prefill(p, t, pcfg),
                    "probes.lm_prefill",
                    _probe_sig(arch, shape, mesh, variant, lp=lp),
                    in_shardings=(param_sh, NamedSharding(mesh, P(bax))),
                    out_shardings=None,
                ).lower(params_abs, sds((mb, S), I32)).compile()
            probes[f"prefill_L{lp}"] = extract(co)
        p2, p3 = probes["prefill_L2"], probes["prefill_L3"]
        delta = {m: p3[m] - p2[m] for m in METRICS}
        corrected = _combine(p3, delta, cfg.n_layers - 3, scale=n_chunks)
    else:
        raise ValueError(kind)
    return dict(corrected=corrected, probes=probes)


# ---------------------------------------------------------------- D4M -------

def d4m_corrected(arch: str, shape: str, mesh: Mesh,
                  variant: str = "baseline") -> Dict:
    import math
    from jax.sharding import PartitionSpec
    from repro.core import distributed, hier
    from repro.core import semiring as sr_mod

    cfg = get_config(arch)
    if variant != "baseline":
        cfg = apply_variant(cfg, variant)
    info = D4M_SHAPES[shape]
    if info["kind"] != "ingest":
        return dict(corrected=None, probes={})
    axes = tuple(mesh.axis_names)
    n_dev = math.prod(mesh.shape.values())
    n_inst = n_dev * cfg.instances_per_device
    block = info["block_size"]
    blocks = info["blocks"]
    cuts = scaled_cuts(cfg.cuts, block)
    spec = PartitionSpec(axes)
    probes = {}

    # ``chunk`` pre-combines that many stream blocks per hierarchy update
    # (stream.ingest semantics): probe updates are chunk*block wide and the
    # scan depth shrinks to blocks/chunk.
    chunk = cfg.effective_chunk(blocks)
    upd_block = block * chunk
    n_updates = blocks // chunk

    for tp in (1, 2):
        def unrolled(states, rows, cols, vals, tp=tp):
            # the probe must price the PRODUCTION instance-batched layout:
            # "grouped"/"bucketed" unroll the batched plan-then-execute
            # step (per-depth-cohort loops / one batch-level branch per
            # update), the other modes unroll the per-instance update under
            # vmap with the configured strategy.
            if cfg.fused and cfg.batch_mode in ("grouped", "bucketed"):
                from repro.core import stream as stream_mod
                for t in range(tp):
                    states = stream_mod.update_instances(
                        states, rows[:, t], cols[:, t], vals[:, t],
                        sr=sr_mod.PLUS_TIMES, use_kernel=cfg.use_kernel,
                        lazy_l0=cfg.lazy_l0, batch_mode=cfg.batch_mode)
                return states

            def one(h, r, c, v):
                for t in range(tp):
                    h = hier.update(h, r[t], c[t], v[t],
                                    sr=sr_mod.PLUS_TIMES,
                                    use_kernel=cfg.use_kernel,
                                    lazy_l0=cfg.lazy_l0,
                                    fused=cfg.fused,
                                    batch_mode=("branchfree"
                                                if cfg.batch_mode
                                                == "branchfree"
                                                else "switch"))
                return h
            return jax.vmap(one)(states, rows, cols, vals)

        # through the keyed stage cache: re-probing the same (config, tp)
        # reuses the lowering, and stages.Lowered/Compiled delegate
        # cost_analysis()/as_text() to the underlying executable
        sig = stages.signature_of(
            cuts=cuts, block_size=block, fused=cfg.fused,
            lazy_l0=cfg.lazy_l0, use_kernel=cfg.use_kernel,
            batch_mode=cfg.batch_mode, mesh=mesh, data_axes=axes,
            extra=(("probe_tp", tp), ("upd_block", upd_block)))
        f = stages.wrap(shard_map(
            unrolled, mesh=mesh, in_specs=(spec,) * 4, out_specs=spec,
            check_vma=False), "probes.d4m_ingest", sig)
        states_abs = jax.eval_shape(
            lambda: distributed.create_instances(n_inst, cuts, block))
        stream = (sds((n_inst, tp, upd_block), I32),
                  sds((n_inst, tp, upd_block), I32),
                  sds((n_inst, tp, upd_block), F32))
        with mesh:
            co = f.lower(states_abs, *stream).compile()
        probes[f"ingest_T{tp}"] = extract(co)
    p1, p2 = probes["ingest_T1"], probes["ingest_T2"]
    delta = {m: p2[m] - p1[m] for m in METRICS}
    corrected = _combine(p1, delta, n_updates - 1)
    return dict(corrected=corrected, probes=probes)


def corrected_metrics(arch: str, shape: str, mesh: Mesh,
                      variant: str = "baseline") -> Dict:
    from repro.configs import family
    fam = family(arch)
    if fam == "lm":
        return lm_corrected(arch, shape, mesh, variant)
    if fam == "d4m":
        return d4m_corrected(arch, shape, mesh, variant)
    return dict(corrected=None, probes={})    # gnn/recsys: full compile exact
