"""palkit — Pallas kernel-level static audit + committed VMEM budgets.

The third analysis layer.  ``repro.analysis.lint`` (PR 7) audits SOURCE;
``repro.analysis.tracekit`` (PR 8) audits what XLA BUILT; neither sees
what Mosaic will be ASKED to build: CI runs every Pallas kernel in
interpret mode (ROADMAP item 4 — no TPU in CI), where a misaligned
BlockSpec, a VMEM blowout, or an out-of-bounds index map compiles and
passes, then fails — or silently crawls, or reads garbage — on the first
real TPU.  palkit audits the ``pallas_call`` CONFIGURATION itself: the
grid, the BlockSpecs, the index maps (abstractly evaluated over the
grid), the scratch shapes, and a jaxpr walk of the kernel body.

The audit universe is ``repro.kernels.registry.jobs()`` — the same job
list the equivalence tests execute and a future TPU warmup will run, so
the audited set cannot drift from the tested set (the ``stages.fleet_jobs``
pattern one layer down).

Run as::

    python -m repro.analysis.palkit --check     # CI / tier-1 gate
    python -m repro.analysis.palkit --update    # regenerate VMEM budgets

Rules (each guards an on-hardware invariant interpret mode cannot see):

K000  The kernel cannot even trace at its registry shapes (a corrupted
      BlockSpec or body) — reported as a violation so the CLI fails
      readably instead of crashing mid-audit.
K001  TPU tiling misalignment: a VMEM block (or scratch buffer) whose
      last dim is not a multiple of the 128-lane register width, or whose
      second-to-last dim neither divides nor is a multiple of the dtype's
      sublane count (8 for 4-byte, 16 for 2-byte, 32 for 1-byte types).
      Mosaic pads each such block to the tile grid — silent VMEM and
      bandwidth waste on every grid step.
K002  Per-grid-step VMEM footprint: pipelined blocks are double-buffered,
      so each step holds 2x every non-trivial-window VMEM block plus all
      VMEM scratch.  Fires when the total exceeds the absolute per-core
      ceiling; the committed ``VMEM_BUDGETS.json`` additionally pins each
      kernel's footprint with tracekit-style ``--check`` (>tolerance over
      or unbudgeted fails CI) and ``--update`` (printed diff).
K003  Out-of-bounds surface: a statically evaluable index map that, at
      some grid point, selects a block index outside the operand (Mosaic
      clamps or faults; interpret mode wraps or reads garbage — either
      way the TPU result diverges from the CI result); or a kernel-body
      slice whose static size exceeds the ref dim it slices.
K004  Output-block revisit hazard: a grid axis with more than one step
      that an output's index map ignores means the SAME output block is
      revisited across those steps — without a ``@pl.when(first-step)``
      guarded initialization the accumulation reads uninitialized VMEM
      on hardware (interpret mode hands the kernel zeroed buffers, so CI
      cannot catch it).  Also: a grid axis ignored by EVERY index map
      (dead grid axis — pure overhead).
K005  Interpret-vs-Mosaic divergence surface, flagged per kernel so the
      divergence is a visible, reasoned allow rather than a surprise:
      (a) an index map that reads prefetched scalars — block choice is
      data-dependent, so OOB *data* (not shape) decides what is fetched;
      (b) dynamic addressing (``pl.ds`` with traced starts) in the body,
      where OOB-load semantics differ between backends.
K006  Async-copy discipline (``segment_agg``-style explicit DMA): every
      ``make_async_copy`` started must be waited somewhere in the body,
      and DMA semaphore slot counts must match the double-buffer depth
      of the VMEM scratch they sequence.

Suppression mirrors tracekit: kernels have no useful source lines, so
allows are PER KERNEL —

    # palkit: allow(K00x) kernel=<glob> <reason>

anywhere in the audited source tree; the kernel field is an ``fnmatch``
glob over registry job names and the reason is mandatory.  Accepted debt
can also live in the committed baseline (``palkit_baseline.txt``, shared
``repro.analysis.baseline`` machinery — it starts and stays empty).
"""
from __future__ import annotations

import argparse
import dataclasses
import fnmatch
import itertools
import json
import math
import os
import re
import sys
import time
from typing import Dict, Iterable, List, Optional, Sequence, Set, Tuple

from repro.analysis import baseline as _baseline

RULES = {
    "K000": "kernel fails to trace at its registry shapes",
    "K001": "VMEM block/scratch misaligned with the TPU tile grid",
    "K002": "per-grid-step VMEM footprint over the per-core ceiling",
    "K003": "index map / body slice out of bounds vs operand shape",
    "K004": "output block revisited without guarded init / dead grid axis",
    "K005": "interpret-vs-Mosaic divergence surface (data-dependent "
            "addressing)",
    "K006": "async-copy/semaphore discipline (unwaited DMA, slot "
            "mismatch)",
}

_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))))
DEFAULT_BASELINE = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "palkit_baseline.txt")
DEFAULT_BUDGETS = os.path.join(_ROOT, "VMEM_BUDGETS.json")
DEFAULT_SRC = os.path.join(_ROOT, "src")
DEFAULT_TOLERANCE = 0.10

_LANES = 128
_SUBLANES = {4: 8, 2: 16, 1: 32}          # itemsize -> sublane count

_ALLOW_RE = re.compile(
    r"#\s*palkit:\s*allow\(([A-Za-z0-9, ]+)\)\s+kernel=(\S+)\s*(.*)$")


@dataclasses.dataclass(frozen=True)
class Violation:
    rule: str
    kernel: str
    detail: str          # stable scope token — the baseline identity
    message: str

    @property
    def key(self) -> str:
        return f"{self.rule} {self.kernel} {self.detail}"

    def render(self) -> str:
        return f"{self.kernel}: {self.rule} {self.message}"


@dataclasses.dataclass
class AuditConfig:
    """Rule thresholds.  ``vmem_limit_bytes``: K002 absolute per-core
    ceiling (16 MiB — one TPU core's VMEM).  ``grid_points``: K003
    evaluates index maps exhaustively up to this many grid points, then
    falls back to per-axis corners+strides."""
    vmem_limit_bytes: int = 16 << 20
    grid_points: int = 4096


# --------------------------------------------------------------- records ----


@dataclasses.dataclass
class BlockInfo:
    """One audited BlockMapping: the block's shape/space plus the full
    operand shape and the (closed) index-map jaxpr."""
    role: str                      # in0../out0.. — stable detail token
    block_shape: Tuple[int, ...]
    array_shape: Tuple[int, ...]
    itemsize: int
    space: str                     # vmem | smem | any | semaphore_mem
    index_map: object              # ClosedJaxpr (grid idx + prefetch refs)
    trivial: bool                  # full-array window, not pipelined
    is_output: bool


@dataclasses.dataclass
class ScratchInfo:
    role: str                      # scratch0..
    shape: Tuple[int, ...]
    itemsize: int
    space: str
    is_semaphore: bool


class KernelRecord:
    """One audited ``pallas_call``: grid + blocks + scratch + body jaxpr,
    extracted from the eqn params (JAX 0.9 pallas internals)."""

    def __init__(self, name: str, family: str, eqn):
        gm = eqn.params["grid_mapping"]
        self.name = name
        self.family = family
        self.grid = tuple(gm.grid)
        self.num_index_operands = int(gm.num_index_operands)
        self.num_inputs = int(gm.num_inputs)
        self.num_outputs = int(gm.num_outputs)
        self.body = eqn.params["jaxpr"]
        self.blocks: List[BlockInfo] = []
        for i, bm in enumerate(gm.block_mappings):
            is_out = i >= self.num_inputs
            role = (f"out{i - self.num_inputs}" if is_out else f"in{i}")
            aval = bm.block_aval
            trivial = bm.has_trivial_window
            if callable(trivial):
                trivial = trivial()
            self.blocks.append(BlockInfo(
                role=role,
                block_shape=tuple(_block_dim(d)
                                  for d in (bm.block_shape or ())),
                array_shape=tuple(bm.array_aval.shape),
                itemsize=int(getattr(getattr(aval, "dtype", None),
                                     "itemsize", 0) or 0),
                space=_space_str(aval),
                index_map=getattr(bm, "index_map_jaxpr", None),
                trivial=bool(trivial),
                is_output=is_out,
            ))
        # scratch operands only exist as trailing kernel-body invars
        body_invars = _jx(self.body).invars
        n_lead = self.num_index_operands + self.num_inputs + self.num_outputs
        self.scratch: List[ScratchInfo] = []
        for i, var in enumerate(body_invars[n_lead:]):
            aval = var.aval
            dt = str(getattr(aval, "dtype", ""))
            self.scratch.append(ScratchInfo(
                role=f"scratch{i}",
                shape=tuple(getattr(aval, "shape", ()) or ()),
                itemsize=int(getattr(getattr(aval, "dtype", None),
                                     "itemsize", 0) or 0),
                space=_space_str(aval),
                is_semaphore="sem" in dt,
            ))

    def ref_role(self, root: Optional[int]) -> str:
        """Stable detail token for a kernel-body ref invar index."""
        if root is None:
            return "?"
        nio, nin = self.num_index_operands, self.num_inputs
        if root < nio:
            return f"prefetch{root}"
        if root < nio + nin:
            return f"in{root - nio}"
        if root < nio + nin + self.num_outputs:
            return f"out{root - nio - nin}"
        return f"scratch{root - nio - nin - self.num_outputs}"

    def vmem_bytes(self) -> Tuple[int, int]:
        """(block_bytes, scratch_bytes) held in VMEM per grid step.
        Pipelined (non-trivial-window) blocks are double-buffered by the
        Pallas pipeline; trivial full-array windows and scratch are
        resident once."""
        pipelined = bool(self.grid)
        blocks = 0
        for b in self.blocks:
            if b.space != "vmem":
                continue
            n = _prod(b.block_shape) * b.itemsize
            blocks += 2 * n if (pipelined and not b.trivial) else n
        scratch = sum(_prod(s.shape) * s.itemsize for s in self.scratch
                      if s.space == "vmem" and not s.is_semaphore)
        return blocks, scratch


def _block_dim(d) -> int:
    """One block dim as an int: ``Blocked``/``Element`` carry ``block_size``;
    squeezed dims count as 1."""
    size = getattr(d, "block_size", d)
    return int(size) if isinstance(size, int) else 1


def _space_str(aval) -> str:
    ms = getattr(aval, "memory_space", None)
    return "vmem" if ms is None else str(ms)


def _prod(shape: Sequence[int]) -> int:
    return int(math.prod(int(d) for d in shape)) if shape else 1


def _jx(j):
    """Unwrap ClosedJaxpr -> Jaxpr (no-op on a raw Jaxpr)."""
    return getattr(j, "jaxpr", j)


def _is_literal(v) -> bool:
    from jax.extend.core import Literal
    return isinstance(v, Literal)


def _is_ref(v) -> bool:
    return hasattr(getattr(v, "aval", None), "memory_space") \
        or "MemRef" in str(getattr(v, "aval", ""))


# ---------------------------------------------------------- jaxpr walking ---


def _subjaxprs_of(val) -> Iterable:
    if hasattr(val, "eqns") or hasattr(val, "jaxpr"):
        yield val
    elif isinstance(val, (tuple, list)):
        for v in val:
            yield from _subjaxprs_of(v)


def _pallas_eqns(jaxpr) -> Iterable:
    """Every pallas_call eqn reachable from ``jaxpr`` (through pjit/scan/
    cond bodies)."""
    for eqn in getattr(_jx(jaxpr), "eqns", ()):
        if eqn.primitive.name == "pallas_call":
            yield eqn
        for val in eqn.params.values():
            for sub in _subjaxprs_of(val):
                yield from _pallas_eqns(sub)


def _walk_body(jaxpr, env: Dict[int, int], guarded: bool,
               events: List[Tuple[str, object, Optional[int], bool]]):
    """Collect (prim, eqn, root_ref_index, guarded) for every get / swap /
    dma_start / dma_wait in the kernel body.  ``env`` maps var id -> root
    kernel invar index, threaded positionally through cond branches,
    while bodies, and scan bodies; ``guarded`` is True inside any cond
    branch (the lowering of ``@pl.when``)."""
    for eqn in getattr(_jx(jaxpr), "eqns", ()):
        nm = eqn.primitive.name
        if nm in ("get", "swap", "dma_start", "dma_wait"):
            root = None
            if eqn.invars and not _is_literal(eqn.invars[0]):
                root = env.get(id(eqn.invars[0]))
            events.append((nm, eqn, root, guarded))
            continue
        if nm == "cond":
            for br in eqn.params.get("branches", ()):
                sub = _thread_env(_jx(br).invars, eqn.invars[1:], env)
                _walk_body(br, sub, True, events)
        elif nm == "while":
            cn = eqn.params.get("cond_nconsts", 0)
            bn = eqn.params.get("body_nconsts", 0)
            body_j = eqn.params.get("body_jaxpr")
            if body_j is not None:
                sub = _thread_env(_jx(body_j).invars, eqn.invars[cn:], env)
                _walk_body(body_j, sub, guarded, events)
            cond_j = eqn.params.get("cond_jaxpr")
            if cond_j is not None:
                ops = list(eqn.invars[:cn]) + list(eqn.invars[cn + bn:])
                sub = _thread_env(_jx(cond_j).invars, ops, env)
                _walk_body(cond_j, sub, guarded, events)
        elif nm == "scan":
            body_j = eqn.params.get("jaxpr")
            if body_j is not None:
                sub = _thread_env(_jx(body_j).invars, eqn.invars, env)
                _walk_body(body_j, sub, guarded, events)
        else:
            # pjit / custom_* etc: positional invar threading still holds
            for key in ("jaxpr", "call_jaxpr"):
                sub_j = eqn.params.get(key)
                if sub_j is not None:
                    sub = _thread_env(_jx(sub_j).invars, eqn.invars, env)
                    _walk_body(sub_j, sub, guarded, events)


def _thread_env(invars, operands, env: Dict[int, int]) -> Dict[int, int]:
    sub: Dict[int, int] = {}
    for bv, ov in zip(invars, operands):
        if not _is_literal(ov) and id(ov) in env:
            sub[id(bv)] = env[id(ov)]
    return sub


def _body_events(rec: KernelRecord):
    env = {id(v): i for i, v in enumerate(_jx(rec.body).invars)}
    events: List[Tuple[str, object, Optional[int], bool]] = []
    _walk_body(rec.body, env, False, events)
    return events


# -------------------------------------------------------- index-map eval ----


def _index_map_reads_prefetch(closed) -> bool:
    """True when the index map's block choice depends on prefetched
    scalars (a ``get`` in the index-map jaxpr) — not statically
    evaluable, and a K005 divergence surface."""
    return closed is not None and any(
        e.primitive.name in ("get", "masked_load", "load")
        for e in _jx(closed).eqns)


def _grid_sample(grid: Tuple[int, ...], limit: int
                 ) -> Iterable[Tuple[int, ...]]:
    """Every grid point for small grids; per-axis corners + mid + stride
    neighbors for large ones (the OOB-prone extremes)."""
    if not grid:
        return [()]
    if _prod(grid) <= limit:
        return itertools.product(*(range(g) for g in grid))
    axes = []
    for g in grid:
        pts = {0, 1, g // 2, g - 2, g - 1}
        axes.append(sorted(p for p in pts if 0 <= p < g))
    return itertools.product(*axes)


def _eval_index_map(closed, point: Tuple[int, ...]) -> Optional[List[int]]:
    """Evaluate one index map at one grid point.  Prefetch-ref invars are
    passed as None — only maps with no ``get`` (checked by the caller)
    reach here, so the refs are dead."""
    import jax
    import jax.numpy as jnp
    jaxpr = _jx(closed)
    n_extra = len(jaxpr.invars) - len(point)
    args = [jnp.int32(p) for p in point] + [None] * n_extra
    try:
        out = jax.core.eval_jaxpr(jaxpr, closed.consts, *args)
    except Exception:
        return None
    return [int(v) for v in out]


# ----------------------------------------------------------------- rules ----


def _k001(rec: KernelRecord, cfg: AuditConfig) -> Iterable[Violation]:
    def misaligned(shape: Tuple[int, ...], itemsize: int) -> Optional[str]:
        if not shape or itemsize <= 0:
            return None
        sub = _SUBLANES.get(itemsize, 8)
        if shape[-1] % _LANES != 0:
            return (f"last dim {shape[-1]} is not a multiple of the "
                    f"{_LANES}-lane register width")
        if len(shape) >= 2 and shape[-2] % sub != 0 and sub % shape[-2]:
            return (f"second-to-last dim {shape[-2]} neither divides nor "
                    f"is a multiple of the sublane count {sub} for "
                    f"{itemsize}-byte elements")
        return None

    for b in rec.blocks:
        if b.space != "vmem":
            continue
        why = misaligned(b.block_shape, b.itemsize)
        if why:
            shp = "x".join(map(str, b.block_shape))
            yield Violation(
                "K001", rec.name, f"{b.role}:{shp}",
                f"block {b.role} shape ({shp}) {why} — Mosaic pads the "
                "block to the tile grid, wasting VMEM and bandwidth on "
                "every grid step")
    for s in rec.scratch:
        if s.space != "vmem" or s.is_semaphore:
            continue
        why = misaligned(s.shape, s.itemsize)
        if why:
            shp = "x".join(map(str, s.shape))
            yield Violation(
                "K001", rec.name, f"{s.role}:{shp}",
                f"scratch {s.role} shape ({shp}) {why} — the buffer is "
                "tile-padded for its whole lifetime")


def _k002(rec: KernelRecord, cfg: AuditConfig) -> Iterable[Violation]:
    blocks, scratch = rec.vmem_bytes()
    total = blocks + scratch
    if total > cfg.vmem_limit_bytes:
        yield Violation(
            "K002", rec.name, "ceiling",
            f"per-grid-step VMEM footprint {total} bytes (blocks "
            f"{blocks} double-buffered + scratch {scratch}) exceeds the "
            f"per-core ceiling {cfg.vmem_limit_bytes} — Mosaic will "
            "fail to allocate or spill to HBM")


def _k003(rec: KernelRecord, cfg: AuditConfig) -> Iterable[Violation]:
    # (a) index maps, abstractly evaluated over the grid
    for b in rec.blocks:
        cj = b.index_map
        if cj is None or _index_map_reads_prefetch(cj):
            continue
        if not b.block_shape or not b.array_shape \
                or len(b.block_shape) != len(b.array_shape):
            continue
        if any(not isinstance(g, int) for g in rec.grid):
            continue                       # dynamic grid bounds — skip
        max_idx = [max(-(-ad // bd) - 1, 0)
                   for ad, bd in zip(b.array_shape, b.block_shape)]
        for point in _grid_sample(rec.grid, cfg.grid_points):
            out = _eval_index_map(cj, point)
            if out is None or len(out) != len(max_idx):
                break
            bad = [d for d, (v, m) in enumerate(zip(out, max_idx))
                   if not 0 <= v <= m]
            if bad:
                d = bad[0]
                yield Violation(
                    "K003", rec.name, f"oob:{b.role}",
                    f"index map for {b.role} selects block index "
                    f"{out[d]} on dim {d} at grid point {point} — valid "
                    f"range [0, {max_idx[d]}] for array dim "
                    f"{b.array_shape[d]} / block dim {b.block_shape[d]}; "
                    "Mosaic clamps or faults where interpret mode reads "
                    "garbage")
                break
    # (b) body slices whose static size exceeds the ref dim
    seen: Set[str] = set()
    for nm, eqn, root, _ in _body_events(rec):
        if nm == "get":
            acc = tuple(getattr(eqn.outvars[0].aval, "shape", ()) or ())
        elif nm == "swap":
            acc = tuple(getattr(eqn.invars[1].aval, "shape", ()) or ())
        else:
            continue
        ref = tuple(getattr(eqn.invars[0].aval, "shape", ()) or ())
        if len(acc) != len(ref):
            continue
        over = [d for d, (a, r) in enumerate(zip(acc, ref)) if a > r]
        if over:
            role = rec.ref_role(root)
            if role in seen:
                continue
            seen.add(role)
            d = over[0]
            yield Violation(
                "K003", rec.name, f"slice:{role}",
                f"{nm} on {role} accesses a window of {acc[d]} elements "
                f"on dim {d} of a {ref[d]}-element ref — out of bounds "
                "for EVERY start index")


def _k004(rec: KernelRecord, cfg: AuditConfig) -> Iterable[Violation]:
    grid = rec.grid
    if not grid or any(not isinstance(g, int) for g in grid):
        return
    live_axes = [a for a, g in enumerate(grid) if g > 1]
    if not live_axes:
        return
    maps = [b.index_map for b in rec.blocks if b.index_map is not None]
    for a in live_axes:
        if maps and all(not _depends_on_axis(cj, a) for cj in maps):
            yield Violation(
                "K004", rec.name, f"dead-axis:{a}",
                f"grid axis {a} (size {grid[a]}) is ignored by every "
                "index map — each step redoes identical work")
    guarded_swaps = {root for nm, _, root, guarded in _body_events(rec)
                     if nm == "swap" and guarded and root is not None}
    out_blocks = [b for b in rec.blocks if b.is_output]
    for oi, b in enumerate(out_blocks):
        if b.index_map is None or b.trivial:
            continue
        ignored = [a for a in live_axes
                   if not _depends_on_axis(b.index_map, a)]
        if not ignored:
            continue
        root = rec.num_index_operands + rec.num_inputs + oi
        if root not in guarded_swaps:
            yield Violation(
                "K004", rec.name, f"revisit:out{oi}",
                f"output block out{oi} is revisited across grid axis "
                f"{ignored[0]} (size {grid[ignored[0]]}) with NO "
                "@pl.when-guarded initialization write — on hardware the "
                "first visit reads uninitialized VMEM (interpret mode "
                "zero-fills, so CI passes)")


def _depends_on_axis(closed, axis: int) -> bool:
    """Forward reachability from grid-index invar ``axis`` to any output
    of the index-map jaxpr (conservative: any marked eqn input marks all
    its outputs, including through sub-jaxpr-carrying eqns)."""
    jaxpr = _jx(closed)
    if axis >= len(jaxpr.invars):
        return False
    marked = {id(jaxpr.invars[axis])}
    for eqn in jaxpr.eqns:
        if any(not _is_literal(v) and id(v) in marked for v in eqn.invars):
            marked.update(id(o) for o in eqn.outvars)
    return any(not _is_literal(v) and id(v) in marked
               for v in jaxpr.outvars)


def _k005(rec: KernelRecord, cfg: AuditConfig) -> Iterable[Violation]:
    pf = [b.role for b in rec.blocks if _index_map_reads_prefetch(b.index_map)]
    if pf:
        yield Violation(
            "K005", rec.name, "index-map",
            f"index map(s) for {', '.join(pf)} read prefetched scalars — "
            "block choice is data-dependent, so an out-of-range VALUE "
            "(not shape) decides what is fetched; Mosaic and interpret "
            "mode disagree on the out-of-bounds result.  Excusable only "
            "with a wrapper-side clamp and a reasoned allow")
    dyn = False
    for nm, eqn, root, _ in _body_events(rec):
        if nm == "get":
            extra = eqn.invars[1:]
        elif nm == "swap":
            extra = eqn.invars[2:]
        elif nm == "dma_start":
            extra = [v for v in eqn.invars if not _is_ref(v)]
        else:
            continue
        if any(not _is_literal(v) for v in extra):
            dyn = True
            break
    if dyn:
        yield Violation(
            "K005", rec.name, "dynamic-ds",
            "kernel body uses dynamic addressing (pl.ds with traced "
            "starts) — out-of-bounds load semantics differ between "
            "interpret mode and Mosaic.  Excusable only when the wrapper "
            "pads/clamps every window in range, with a reasoned allow")


def _k006(rec: KernelRecord, cfg: AuditConfig) -> Iterable[Violation]:
    events = _body_events(rec)
    starts = sum(1 for nm, *_ in events if nm == "dma_start")
    waits = sum(1 for nm, *_ in events if nm == "dma_wait")
    if starts and not waits:
        yield Violation(
            "K006", rec.name, "unwaited",
            f"{starts} async-copy start(s) with NO dma_wait anywhere in "
            "the kernel body — the copy may still be in flight when the "
            "buffer is read (interpret mode completes copies "
            "synchronously, so CI cannot catch it)")
    sems = [s for s in rec.scratch if s.is_semaphore and len(s.shape) >= 1]
    depths = {s.shape[0] for s in rec.scratch
              if s.space == "vmem" and not s.is_semaphore
              and len(s.shape) >= 2}
    for s in sems:
        if depths and s.shape[0] not in depths:
            yield Violation(
                "K006", rec.name, f"slot-mismatch:{s.role}",
                f"DMA semaphore {s.role} has {s.shape[0]} slot(s) but the "
                f"double-buffered VMEM scratch uses depth "
                f"{sorted(depths)} — a slot collision serializes (or "
                "corrupts) the pipeline")


_RULE_FNS = (_k001, _k002, _k003, _k004, _k005, _k006)


def run_rules(records: Sequence[KernelRecord],
              cfg: Optional[AuditConfig] = None) -> List[Violation]:
    """All K-rule violations over ``records`` (unsuppressed view — allows
    and baseline are applied by the caller/CLI)."""
    cfg = cfg or AuditConfig()
    out: List[Violation] = []
    for rec in records:
        for rule in _RULE_FNS:
            out.extend(rule(rec, cfg))
    return sorted(out, key=lambda v: (v.kernel, v.rule, v.detail))


# ------------------------------------------------------------- tracing ------


def record_fn(name: str, fn, *avals, family: str = "fixture"
              ) -> List[KernelRecord]:
    """Trace ``fn`` at ``avals`` and return one record per pallas_call
    reached — the fixture-test entry point, bypassing the registry."""
    import jax
    jaxpr = jax.make_jaxpr(fn)(*avals)
    eqns = list(_pallas_eqns(jaxpr))
    return [KernelRecord(name if len(eqns) == 1 else f"{name}#{i}",
                         family, eqn)
            for i, eqn in enumerate(eqns)]


def record_job(job) -> List[KernelRecord]:
    """Trace one registry job (interpret=False — the Mosaic-path config)
    on abstract inputs; concrete values are never materialized."""
    import functools

    import jax
    import numpy as np
    ins = job.make_inputs(0)
    avals = jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(np.shape(a), np.asarray(a).dtype),
        ins, is_leaf=lambda x: isinstance(x, np.ndarray))
    fn = functools.partial(job.fn, interpret=False)
    return record_fn(job.name, fn, *avals, family=job.family)


def trace_kernels(jobs=None,
                  failures: Optional[List[Violation]] = None
                  ) -> List[KernelRecord]:
    """Records for every registry job (the full audit universe).  With a
    ``failures`` list, a job whose kernel cannot even trace becomes a
    K000 violation there (the audit keeps going and fails loudly but
    readably); without one, the exception propagates."""
    if jobs is None:
        from repro.kernels import registry
        jobs = registry.jobs()
    out: List[KernelRecord] = []
    for job in jobs:
        try:
            out.extend(record_job(job))
        except Exception as e:                  # noqa: BLE001 — reported
            if failures is None:
                raise
            failures.append(Violation(
                "K000", job.name, "trace",
                f"kernel failed to trace at its registry shapes — "
                f"{type(e).__name__}: {e}"))
    return out


# ----------------------------------------------------------- suppression ----


def scan_allows(paths: Sequence[str]) -> List[Tuple[Set[str], str, str]]:
    """Collect ``# palkit: allow(K00x) kernel=<glob> <reason>`` comments
    from the source tree.  Kernels have no useful source lines (the
    violation lives in a BlockSpec config, often built dynamically), so
    allows are per-kernel: the glob names the registry job(s) being
    excused, and a missing reason does not suppress."""
    from repro.analysis.lint import iter_py_files
    out: List[Tuple[Set[str], str, str]] = []
    for path in iter_py_files(paths):
        with open(path, "r", encoding="utf-8") as fh:
            for line in fh:
                m = _ALLOW_RE.search(line)
                if m:
                    rules = {r.strip() for r in m.group(1).split(",")
                             if r.strip()}
                    out.append((rules, m.group(2), m.group(3).strip()))
    return out


def suppressed(v: Violation,
               allows: Sequence[Tuple[Set[str], str, str]]) -> bool:
    return any(v.rule in rules and reason
               and fnmatch.fnmatchcase(v.kernel, glob)
               for rules, glob, reason in allows)


# ---------------------------------------------------------------- budgets ---

_BUDGET_FIELDS = ("vmem_bytes",)


def measure(records: Sequence[KernelRecord]) -> Dict[str, dict]:
    """Per-kernel VMEM rows keyed by registry job name.  Pure static
    shape arithmetic — identical on every machine, so the committed
    budgets can be pinned by tier-1, not just CI."""
    out: Dict[str, dict] = {}
    for rec in records:
        blocks, scratch = rec.vmem_bytes()
        out[rec.name] = dict(
            family=rec.family,
            grid="x".join(map(str, rec.grid)) or "-",
            block_bytes=blocks,
            scratch_bytes=scratch,
            vmem_bytes=blocks + scratch,
        )
    return out


def load_budgets(path: str) -> dict:
    if not os.path.exists(path):
        return {}
    with open(path, "r", encoding="utf-8") as fh:
        return json.load(fh)


def write_budgets(path: str, measured: Dict[str, dict],
                  tolerance: float) -> None:
    import jax
    payload = {
        "_meta": dict(
            tolerance=tolerance,
            generated=time.strftime("%Y-%m-%dT%H:%M:%S"),
            jax=jax.__version__,
            command="python -m repro.analysis.palkit --update",
            note="committed per-kernel per-grid-step VMEM footprints "
                 "(bytes; pipelined blocks double-buffered + scratch) — "
                 "--check fails when a kernel exceeds its budget by more "
                 "than the tolerance or is unbudgeted",
        ),
        "kernels": {k: measured[k] for k in sorted(measured)},
    }
    with open(path, "w", encoding="utf-8") as fh:
        json.dump(payload, fh, indent=1, sort_keys=True)
        fh.write("\n")


def compare_budgets(measured: Dict[str, dict], budgets: dict,
                    tolerance: float = DEFAULT_TOLERANCE) -> dict:
    """Budget-vs-actual diff, same verdicts as tracekit: ``breaches``
    (actual > budget * (1+tol)), ``missing`` (audited but unbudgeted),
    ``stale`` (budgeted but gone from the registry), ``improved``
    (ratchet candidates), and the full ``rows`` table."""
    entries = budgets.get("kernels", {})
    breaches, missing, improved, rows = [], [], [], []
    for key, act in sorted(measured.items()):
        bud = entries.get(key)
        if bud is None:
            missing.append(key)
            rows.append((key, None, act, "MISSING"))
            continue
        verdict = "ok"
        for field in _BUDGET_FIELDS:
            b, a = bud.get(field), act.get(field)
            if b in (None, 0) or a is None:
                continue
            if a > b * (1.0 + tolerance):
                verdict = "BREACH"
                breaches.append(
                    f"{key}: {field} {a} > budget {b} "
                    f"(+{(a / b - 1) * 100:.1f}%, tolerance "
                    f"{tolerance * 100:.0f}%)")
            elif a < b / (1.0 + tolerance) and verdict == "ok":
                verdict = "improved"
        if verdict == "improved":
            improved.append(key)
        rows.append((key, bud, act, verdict))
    stale = sorted(set(entries) - set(measured))
    return dict(breaches=breaches, missing=missing, stale=stale,
                improved=improved, rows=rows)


def render_budget_table(rows) -> str:
    out = [f"{'kernel':<46s} {'grid':>6s} {'blocks':>10s} "
           f"{'scratch':>9s} {'vmem':>10s} {'budget':>10s}  verdict"]
    for key, bud, act, verdict in rows:
        b = "-" if bud is None or bud.get("vmem_bytes") is None \
            else str(bud["vmem_bytes"])
        out.append(
            f"{key:<46s} {act.get('grid', '-'):>6s} "
            f"{act.get('block_bytes', 0):>10d} "
            f"{act.get('scratch_bytes', 0):>9d} "
            f"{act.get('vmem_bytes', 0):>10d} {b:>10s}  {verdict}")
    return "\n".join(out)


# ----------------------------------------------------------- kernel audit ---


def audit_kernels(jobs=None, *, audit_cfg: Optional[AuditConfig] = None,
                  src: Sequence[str] = (DEFAULT_SRC,),
                  baseline_path: str = DEFAULT_BASELINE) -> dict:
    """Trace the whole registry and run every K rule.  Returns
    ``violations`` (every hit), ``suppressed`` (allowed in-tree),
    ``fresh`` (neither allowed nor baselined — the failing set),
    ``measured`` (the VMEM rows budgets are checked against) and the
    ``records`` themselves."""
    failures: List[Violation] = []
    records = trace_kernels(jobs, failures)
    violations = failures + run_rules(records, audit_cfg)
    allows = scan_allows(list(src)) if src else []
    unsuppressed = [v for v in violations if not suppressed(v, allows)]
    base = _baseline.load_baseline(baseline_path)
    fresh = _baseline.new_violations(unsuppressed, base)
    return dict(records=records, violations=violations,
                suppressed=[v for v in violations
                            if suppressed(v, allows)],
                fresh=fresh, measured=measure(records))


_BASELINE_HEADER = (
    "# palkit baseline — accepted pre-existing debt, one\n"
    "# 'RULE kernel detail' key per violation.  Regenerate with\n"
    "#   python -m repro.analysis.palkit --write-baseline\n"
    "# New violations (keys not in this file) fail the audit; prefer\n"
    "# reasoned '# palkit: allow(K00x) kernel=<glob> <reason>' comments\n"
    "# in-tree so the debt stays visible next to its owner.\n")


def main(argv: Optional[Sequence[str]] = None) -> int:
    ap = argparse.ArgumentParser(
        prog="python -m repro.analysis.palkit",
        description="Pallas kernel-level static audit + VMEM budgets "
                    "over the kernel registry (K001-K006)")
    mode = ap.add_mutually_exclusive_group()
    mode.add_argument("--check", action="store_true", default=True,
                      help="audit + budget check (default); exit 1 on new "
                      "violations, budget breaches, or unbudgeted "
                      "kernels")
    mode.add_argument("--update", action="store_true",
                      help="regenerate VMEM_BUDGETS.json with a printed "
                      "diff against the committed budgets")
    mode.add_argument("--write-baseline", action="store_true",
                      help="accept current K-violations as the baseline")
    ap.add_argument("--budgets", default=DEFAULT_BUDGETS,
                    help="budget file (default: committed "
                    "VMEM_BUDGETS.json)")
    ap.add_argument("--baseline", default=DEFAULT_BASELINE)
    ap.add_argument("--src", nargs="*", default=[DEFAULT_SRC],
                    help="source tree scanned for allow comments")
    ap.add_argument("--tolerance", type=float, default=None,
                    help="budget tolerance (default: the budget file's, "
                    f"else {DEFAULT_TOLERANCE})")
    ap.add_argument("--vmem-limit", type=int, default=None,
                    help="K002 absolute per-core VMEM ceiling in bytes")
    ap.add_argument("-q", "--quiet", action="store_true")
    args = ap.parse_args(argv)

    acfg = AuditConfig()
    if args.vmem_limit is not None:
        acfg.vmem_limit_bytes = args.vmem_limit

    result = audit_kernels(audit_cfg=acfg, src=args.src,
                           baseline_path=args.baseline)
    fresh, measured = result["fresh"], result["measured"]

    if args.write_baseline:
        unsuppressed = [v for v in result["violations"]
                        if v not in result["suppressed"]]
        _baseline.write_baseline(args.baseline, unsuppressed,
                                 _BASELINE_HEADER)
        print(f"baseline written: {len(unsuppressed)} entries -> "
              f"{args.baseline}")
        return 0

    budgets = load_budgets(args.budgets)
    tol = args.tolerance if args.tolerance is not None \
        else budgets.get("_meta", {}).get("tolerance", DEFAULT_TOLERANCE)

    if args.update:
        diff = compare_budgets(measured, budgets, tol)
        write_budgets(args.budgets, measured, tol)
        print(f"budgets written: {len(measured)} kernels -> "
              f"{args.budgets}")
        if not args.quiet:
            print(render_budget_table(diff["rows"]))
            for line in diff["breaches"]:
                print(f"  was-breach: {line}")
            for key in diff["stale"]:
                print(f"  dropped stale kernel: {key}")
        return 0

    # --check
    if not args.quiet:
        for v in fresh:
            print(v.render())
    counts = _baseline.per_rule_counts(result["violations"], RULES)
    fresh_counts = _baseline.per_rule_counts(fresh, RULES)
    print("palkit per-rule counts (total / new):")
    for rule in sorted(counts):
        print(f"  {rule}: {counts[rule]} / {fresh_counts.get(rule, 0)}"
              f"  — {RULES.get(rule, 'internal')}")
    n_sup = len(result["suppressed"])
    print(f"{len(result['violations'])} violation(s), {n_sup} allowed, "
          f"{len(fresh)} new")

    diff = compare_budgets(measured, budgets, tol)
    print(f"VMEM budgets ({args.budgets}, tolerance {tol * 100:.0f}%):")
    print(render_budget_table(diff["rows"]))
    for line in diff["breaches"]:
        print(f"BUDGET BREACH: {line}")
    for key in diff["missing"]:
        print(f"NO BUDGET: {key} — run --update and commit the diff")
    for key in diff["stale"]:
        print(f"stale budget (kernel left the registry): {key}")
    ok = not fresh and not diff["breaches"] and not diff["missing"]
    print("palkit:", "clean" if ok else "FAILED")
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
