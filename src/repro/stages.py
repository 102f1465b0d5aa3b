"""Staged lowering + keyed compile cache — the one front door for jit.

The paper's deployment launches 34,000 hierarchical D4M instances at once
(arXiv:1902.00846), which makes fleet COLD-START a first-class cost: every
(cuts x block_size x dtype x batch_mode x semiring x fused/lazy/kernel/chunk)
combination used to re-trace and re-jit independently at each of a
half-dozen scattered ``jax.jit`` call sites.  This module replaces those
sites with an explicit three-stage pipeline (modeled on JaCe's
Wrapped -> Lowered -> Compiled translation cache):

    wrap(fn, entry, sig)  ->  Wrapped
    Wrapped.lower(*args)  ->  Lowered      (cached per config signature)
    Lowered.compile()     ->  Compiled     (cached + persisted to disk)

The process-wide cache key is a canonical **config signature**
(``Signature``: cuts, block_size, dtype, semiring, fused/lazy_l0/
use_kernel/chunk, batch_mode, mesh/shard layout, query knobs) plus the
abstract input shapes (treedef + shaped avals), so the same configuration
never lowers or compiles twice in a process.  ``signature_of`` is ALSO the
single knob canonicalizer/validator: every entry point (``stream``,
``hier``, ``distributed``, ``query``, ``launch``) routes its knob
validation through it, so an invalid combination fails with the same
error message everywhere.

Persistence: ``set_cache_dir(path)`` makes ``path`` JAX's persistent
compilation cache, which keys every executable by its lowered program and
compile options, so a fresh process (or CI run, see
.github/workflows/ci.yml) re-lowers and then reports disk hits instead of
re-compiling.  Launchers call ``set_cache_dir(default_cache_dir())``:
``$JAX_COMPILATION_CACHE_DIR`` when it is set, else the fixed
``<checkout>/.jax-cache``.  Call it BEFORE the first compile.

Tracing: ``Compiled.op_scopes()`` / ``op_scopes(entry)`` read each
executable's own optimized HLO into a table from instruction name to
``op_name`` (the ``jax.named_scope`` path), keyed by HLO module name — the
join a device trace needs, since its op events carry only instruction
names.  ``stats()["per_entry"]`` splits each entry's set-up into
``lower_s`` and ``load_s``.

``precompile_fleet(cfg)`` enumerates a ``D4MConfig``'s dispatch set
(instance-batched ingest with/without telemetry, the service query/
analytics dispatches, the single-instance hier ops, the sharded fns when a
mesh is given) and compiles it once at launch; ``stats()`` counts
lowerings/compiles/cache hits so tests and benchmarks can assert "zero
retraces after warmup" (tests/test_stages.py).
"""
from __future__ import annotations

import dataclasses
import hashlib
import os
import re
import threading
import time
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

# Canonical knob domains — stream.py/hier.py re-export BATCH_MODES from here
# so there is exactly one source of truth for the allowed values.
BATCH_MODES = ("grouped", "bucketed", "branchfree", "switch")
L0_MODES = ("auto", "scan", "canon")

_LOCK = threading.RLock()
_WRAPPED: dict = {}        # (entry, sig, static, jit_kwargs) -> Wrapped
_LOWERED: dict = {}        # full key -> Lowered
_COMPILED: dict = {}       # full key -> Compiled
_STATS = dict(lowerings=0, compiles=0, memory_hits=0, disk_hits=0,
              dispatches=0, disk_writes=0)
_ENTRY_STATS: dict = {}    # entry -> dispatches, wall_s, lower_s, load_s
_DIGESTS: dict = {}        # full key -> short signature digest (hook only)
_CACHE_DIR: Optional[str] = None

# Observability (repro.obs.trace) installs a per-dispatch hook + optional
# jax.profiler annotation class here.  Both are HOST-side: they wrap the
# already-compiled executable call and never participate in tracing, so
# production jaxprs are bit-identical whether observability is on or off
# and the off-path cost is one module-global read per dispatch.
_TRACE_HOOK: Optional[Callable] = None
_TRACE_ANNOTATION = None


def set_trace_hook(hook: Optional[Callable], annotation=None) -> None:
    """Install (or clear, with ``None``) the dispatch-span hook.  The hook
    is called as ``hook(entry=, digest=, wall_s=, compile_s=, lower_s=,
    load_s=, provenance=, t0_ns=, t1_ns=)`` after every concrete ``Wrapped``
    dispatch: ``compile_s`` = ``lower_s`` (trace and lower) + ``load_s``
    (XLA compile, or the persistent cache's load), all 0 on a memory hit;
    ``t0_ns``/``t1_ns`` bracket the dispatch on ``time.time_ns()``, the
    epoch clock of a profiler trace.  ``annotation``, when given, is a
    context-manager class (``jax.profiler.TraceAnnotation``) nested around
    the executable call."""
    global _TRACE_HOOK, _TRACE_ANNOTATION
    _TRACE_HOOK = hook
    _TRACE_ANNOTATION = annotation if hook is not None else None


# ------------------------------------------------------------ signatures ----


@dataclasses.dataclass(frozen=True)
class Signature:
    """Canonical, hashable config signature — the cache key's static half.

    ``None`` fields mean "not pinned by this entry point" (e.g. the service
    query dispatch carries no cuts — the hierarchy geometry rides in the
    abstract input shapes instead).  ``extra`` holds entry-specific static
    knobs as a sorted ``((name, value), ...)`` tuple.
    """
    cuts: Optional[Tuple[int, ...]] = None
    block_size: Optional[int] = None
    dtype: str = "float32"
    sr: str = "plus.times"
    fused: bool = True
    lazy_l0: bool = False
    use_kernel: bool = False
    chunk: int = 1
    batch_mode: Optional[str] = None
    mesh: Tuple[Tuple[str, int], ...] = ()
    data_axes: Tuple[str, ...] = ()
    l0_mode: Optional[str] = None
    extra: Tuple[Tuple[str, Any], ...] = ()


def _invalid(msg: str) -> ValueError:
    # ONE message shape for every entry point (ISSUE 6 satellite: an invalid
    # knob combination fails identically everywhere).
    return ValueError(f"invalid d4m config signature: {msg}")


def signature_of(cfg=None, *, cuts=None, block_size=None, dtype=None,
                 sr=None, fused=None, lazy_l0=None, use_kernel=None,
                 chunk=None, batch_mode=None, mesh=None, data_axes=None,
                 l0_mode=None, extra=(),
                 allowed_batch_modes: Optional[Tuple[str, ...]] = None
                 ) -> Signature:
    """Canonicalize + validate a knob set into a ``Signature``.

    ``cfg`` may be a ``configs.D4MConfig`` (fields are read off it, keyword
    overrides win).  This is the shared validator: bad cuts, unknown
    semirings/dtypes, ``lazy_l0`` outside plus.times, and batch modes
    outside ``allowed_batch_modes`` (default: all of ``BATCH_MODES``) all
    raise the same ``invalid d4m config signature: ...`` ValueError at
    every entry point.
    """
    def pick(override, attr, default):
        if override is not None:
            return override
        if cfg is not None and hasattr(cfg, attr):
            return getattr(cfg, attr)
        return default

    cuts = pick(cuts, "cuts", None)
    block_size = pick(block_size, "block_size", None)
    dtype = pick(dtype, "dtype", "float32")
    fused = bool(pick(fused, "fused", True))
    lazy_l0 = bool(pick(lazy_l0, "lazy_l0", False))
    use_kernel = bool(pick(use_kernel, "use_kernel", False))
    chunk = pick(chunk, "chunk", 1)
    batch_mode = pick(batch_mode, "batch_mode", None)
    l0_mode = pick(l0_mode, "query_l0_mode", None)

    if cuts is not None:
        try:
            cuts = tuple(int(c) for c in cuts)
        except (TypeError, ValueError):
            raise _invalid(f"cuts must be an int tuple, got {cuts!r}")
        if not cuts or any(c <= 0 for c in cuts) \
                or any(a >= b for a, b in zip(cuts, cuts[1:])):
            raise _invalid(f"cuts must be positive and strictly "
                           f"increasing, got {cuts}")
    if block_size is not None:
        block_size = int(block_size)
        if block_size < 1:
            raise _invalid(f"block_size must be >= 1, got {block_size}")
    try:
        dtype = jnp.dtype(dtype).name
    except TypeError:
        raise _invalid(f"unknown dtype {dtype!r}")
    sr_name = getattr(sr, "name", sr)
    if sr_name is None:
        sr_name = "plus.times"
    from repro.core import semiring as sr_mod
    try:
        sr_mod.get(sr_name)
    except (KeyError, ValueError):
        raise _invalid(f"unknown semiring {sr_name!r}")
    if not isinstance(chunk, int) or chunk < 1:
        raise _invalid(f"chunk must be an int >= 1, got {chunk!r}")
    allowed = allowed_batch_modes or BATCH_MODES
    if batch_mode is not None and batch_mode not in allowed:
        raise _invalid(f"batch_mode must be one of {allowed}, "
                       f"got {batch_mode!r}")
    if lazy_l0 and sr_name != "plus.times":
        raise _invalid(f"lazy_l0 requires the plus.times semiring, "
                       f"got {sr_name!r}")
    if l0_mode is not None and l0_mode not in L0_MODES:
        raise _invalid(f"l0_mode must be one of {L0_MODES}, "
                       f"got {l0_mode!r}")
    if mesh is not None and not isinstance(mesh, tuple):
        mesh = tuple(zip(mesh.axis_names,
                         (int(s) for s in mesh.devices.shape)))
    return Signature(cuts=cuts, block_size=block_size, dtype=dtype,
                     sr=sr_name, fused=fused, lazy_l0=lazy_l0,
                     use_kernel=use_kernel, chunk=chunk,
                     batch_mode=batch_mode, mesh=mesh or (),
                     data_axes=tuple(data_axes or ()), l0_mode=l0_mode,
                     extra=tuple(extra))


def signature_for_state(h, **kw) -> Signature:
    """``signature_of`` with cuts/block_size/dtype derived from a live
    ``HierAssoc`` (batched or single-instance; works on tracers — cuts are
    static metadata and capacity/dtype are shape attributes)."""
    l0 = h.layers[0]
    cap0 = int(l0.hi.shape[-1])
    kw.setdefault("cuts", tuple(h.cuts))
    kw.setdefault("block_size", cap0 - int(h.cuts[0]))
    kw.setdefault("dtype", l0.val.dtype)
    return signature_of(**kw)


def check_state(sig: Signature, h, block: Optional[int] = None) -> None:
    """Trace-time geometry check shared by the pinned-config entry points
    (``stream.ingest_jit``): the state and stream must match the signature
    the function was specialized to."""
    from repro.core import hier
    if tuple(h.cuts) != sig.cuts:
        raise _invalid(f"state cuts {tuple(h.cuts)} != configured "
                       f"{sig.cuts}")
    caps = hier.layer_capacities(sig.cuts, sig.block_size)
    state_caps = tuple(int(l.hi.shape[-1]) for l in h.layers)
    if state_caps != caps:
        raise _invalid(f"state capacities {state_caps} != {caps} "
                       f"(block_size {sig.block_size})")
    if jnp.dtype(h.layers[0].val.dtype) != jnp.dtype(sig.dtype):
        raise _invalid(f"state dtype {h.layers[0].val.dtype} != "
                       f"{sig.dtype}")
    if block is not None and block != sig.block_size:
        raise _invalid(f"stream block {block} != configured block_size "
                       f"{sig.block_size}")


# ----------------------------------------------------------------- keying ---


def _leaf_key(x):
    if isinstance(x, jax.ShapeDtypeStruct):
        return (tuple(x.shape), jnp.dtype(x.dtype).name, False)
    aval = jax.typeof(x)
    return (tuple(aval.shape), aval.dtype.name, bool(aval.weak_type))


def is_tracing(*args) -> bool:
    """True when any pytree leaf is a JAX tracer — the wrapped function must
    then inline into the surrounding trace instead of dispatching."""
    return any(isinstance(l, jax.core.Tracer)
               for l in jax.tree_util.tree_leaves(args))


def _args_key(args):
    leaves, treedef = jax.tree_util.tree_flatten(args)
    return treedef, tuple(_leaf_key(l) for l in leaves)


def abstract_args(key):
    """Rebuild the abstract argument pytree a cache key was lowered under.

    The key already carries everything needed — treedef + per-leaf
    (shape, dtype, weak_type) — so a ``Compiled`` loaded from disk (whose
    executable may not support introspection) can be re-lowered ON DEMAND
    without the original concrete arrays (tracekit + ``Compiled.as_text``
    degradation, ISSUE 8)."""
    treedef, avals = key[4], key[5]
    leaves = [jax.ShapeDtypeStruct(shape, jnp.dtype(dtype))
              for shape, dtype, _weak in avals]
    return jax.tree_util.tree_unflatten(treedef, leaves)


def _count(name: str, n: int = 1) -> None:
    with _LOCK:
        _STATS[name] += n


def _note_dispatch(entry: str, wall_s: float, lower_s: float,
                   load_s: float) -> None:
    with _LOCK:
        es = _ENTRY_STATS.get(entry)
        if es is None:
            es = _ENTRY_STATS[entry] = dict(dispatches=0, wall_s=0.0,
                                            lower_s=0.0, load_s=0.0)
        es["dispatches"] += 1
        es["wall_s"] += wall_s
        es["lower_s"] += lower_s
        es["load_s"] += load_s


def _key_digest(key) -> str:
    """Short config-signature digest for trace spans; memoized because the
    full ``_digest`` hashes the whole repr'd key on every call."""
    with _LOCK:
        d = _DIGESTS.get(key)
    if d is None:
        d = _digest(key)[:12]
        with _LOCK:
            _DIGESTS[key] = d
    return d


def _freeze(x):
    """Hashable, deterministic stand-in for a jit-kwarg value.

    ``in_shardings``/``out_shardings`` pytrees contain dicts (unhashable)
    and sharding objects; the cache key needs a hashable mirror while the
    ``Wrapped`` keeps the real values for ``jax.jit``.  Hashable leaves
    pass through untouched so plain kwargs key exactly as before."""
    if isinstance(x, dict):
        return ("dict",) + tuple((k, _freeze(v))
                                 for k, v in sorted(x.items(), key=repr))
    if isinstance(x, (list, tuple)):
        return ("seq",) + tuple(_freeze(v) for v in x)
    try:
        hash(x)
    except TypeError:
        return repr(x)
    return x


# ---------------------------------------------------------------- storage ---


# JAX's cache settings as they stood before ``set_cache_dir`` changed them
# (``$JAX_COMPILATION_CACHE_DIR`` is already applied by jax itself), so
# ``set_cache_dir(None)`` hands the process back to the outside setting.
_CACHE_CONFIG = ("jax_compilation_cache_dir",
                 "jax_persistent_cache_min_compile_time_secs",
                 "jax_persistent_cache_min_entry_size_bytes")
_OUTSIDE_CONFIG = {k: getattr(jax.config, k) for k in _CACHE_CONFIG}
_CHECKOUT_CACHE = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax-cache")


def default_cache_dir() -> str:
    """``$JAX_COMPILATION_CACHE_DIR`` when set, else ``<checkout>/.jax-cache``
    — one fixed path, because a cache directory that moves never hits."""
    return os.environ.get("JAX_COMPILATION_CACHE_DIR") or _CHECKOUT_CACHE


def set_cache_dir(path: Optional[str]) -> None:
    """Make ``path`` the persistent compile cache (None restores the outside
    setting).

    ``path`` becomes JAX's ``jax_compilation_cache_dir``, with the
    min-compile-time/min-entry-size gates opened, since the whole point is
    caching many small per-config programs.  Run it BEFORE the first
    compile of the process: XLA's cache decision is memoized at first use,
    which ``reset_cache`` re-arms for a directory set mid-process.
    """
    global _CACHE_DIR
    from jax.experimental.compilation_cache import compilation_cache
    _CACHE_DIR = os.path.abspath(path) if path else None
    if _CACHE_DIR:
        os.makedirs(_CACHE_DIR, exist_ok=True)
        settings = dict(zip(_CACHE_CONFIG, (_CACHE_DIR, 0.0, 0)))
    else:
        settings = _OUTSIDE_CONFIG
    for name, value in settings.items():
        jax.config.update(name, value)
    compilation_cache.reset_cache()


def cache_dir() -> Optional[str]:
    return _CACHE_DIR


def _digest(key) -> str:
    entry, sig, static, jk, treedef, avals = key
    text = "|".join([
        jax.__version__, jax.default_backend(), str(jax.device_count()),
        entry, repr(sig), repr(static), repr(jk), str(treedef), repr(avals),
    ])
    return hashlib.sha256(text.encode()).hexdigest()[:32]


# JAX raises its persistent-cache events in the compiling thread, so
# ``Lowered.compile`` reads them per thread to tell a disk hit from a real
# XLA compile.  The cache itself is keyed by the lowered program and its
# compile options: an edited program body never loads a stale executable.
_CACHE_EVENTS = {"/jax/compilation_cache/cache_hits": "hits",
                 "/jax/compilation_cache/cache_misses": "writes"}
_TLS = threading.local()


def _on_jax_event(event: str, **_kwargs) -> None:
    field = _CACHE_EVENTS.get(event)
    if field is not None:
        setattr(_TLS, field, getattr(_TLS, field, 0) + 1)


jax.monitoring.register_event_listener(_on_jax_event)


# ----------------------------------------------------------------- stages ---


class Compiled:
    """Stage 3: an executable specialized to one (signature, avals) key.

    Introspection (``cost_analysis``/``as_text``/``memory_analysis``) is
    explicit rather than pure delegation: an executable JAX loaded from its
    persistent cache (``from_disk=True``) may not implement the analysis
    surface — instead
    of raising ``AttributeError`` into tracekit or ``stats()`` consumers,
    the methods degrade gracefully by re-lowering the entry on demand from
    the cache key's abstract avals (``abstract_args``) and answering from
    the fresh IR.  Everything else still delegates to the underlying
    ``jax.stages.Compiled``."""

    def __init__(self, key, executable, from_disk: bool = False):
        self.key = key
        self.from_disk = from_disk
        self._executable = executable
        self._scopes: Optional[Dict[str, Dict[str, str]]] = None

    def __call__(self, *args):
        return self._executable(*args)

    def __getattr__(self, name):
        return getattr(self._executable, name)

    def _relowered(self) -> "Lowered":
        """Re-lower this entry from its key (cached in ``_LOWERED``); the
        introspection fallback for executables that cannot answer."""
        with _LOCK:
            low = _LOWERED.get(self.key)
            w = _WRAPPED.get(self.key[:4])
        if low is not None:
            return low
        if w is None:
            raise AttributeError(
                f"stages.Compiled for entry {self.key[0]!r} was loaded "
                "from disk and its executable supports no introspection; "
                "re-lowering needs the Wrapped builder, which is not in "
                "the cache — rebuild it (wrap/dispatch the entry once) "
                "before auditing")
        return w.lower(*abstract_args(self.key))

    def _introspect(self, name: str):
        try:
            return getattr(self._executable, name)()
        except Exception:
            # deserialized executables can't always answer (jax-version /
            # backend dependent) — degrade to the re-lowered IR, whose
            # jax.stages.Lowered implements the same analysis surface
            return getattr(self._relowered(), name)()

    def cost_analysis(self) -> dict:
        """XLA cost model for this executable."""
        return dict(self._introspect("cost_analysis") or {})

    def as_text(self) -> str:
        return self._introspect("as_text")

    def op_scopes(self) -> Dict[str, Dict[str, str]]:
        """``{HLO module name: {instruction name: op_name}}`` of this
        executable, read from its own optimized HLO (never from a
        re-lowering, whose instruction names differ): the table that joins
        a device trace's op events, named by instruction, to the program's
        ``jax.named_scope`` paths.  An instruction without metadata maps to
        ``""``.  Raises when the executable cannot print its HLO."""
        if self._scopes is None:
            self._scopes = parse_op_scopes(self._executable.as_text())
        return self._scopes

    def memory_analysis(self):
        """``None`` when the executable cannot answer — unlike
        cost/IR there is no memory surface on a re-lowered
        ``jax.stages.Lowered`` to degrade to."""
        try:
            return self._executable.memory_analysis()
        except Exception:
            return None


class Lowered:
    """Stage 2: lowered-but-not-compiled IR for one key.  ``compile()``
    consults the in-memory cache, then XLA through JAX's persistent cache.
    Carries the closed ``jaxpr`` captured at trace time — the substrate
    tracekit's J-rules walk (a ``jax.stages.Lowered`` alone does not
    expose it)."""

    def __init__(self, key, lowered, jaxpr=None):
        self.key = key
        self.jaxpr = jaxpr
        self._lowered = lowered

    def compile(self) -> Compiled:
        with _LOCK:
            comp = _COMPILED.get(self.key)
        if comp is not None:
            _count("memory_hits")
            return comp
        _TLS.hits = _TLS.writes = 0
        executable = self._lowered.compile()
        from_disk = _TLS.hits > 0
        _count("disk_hits" if from_disk else "compiles")
        _count("disk_writes", _TLS.writes)
        with _LOCK:
            return _COMPILED.setdefault(
                self.key, Compiled(self.key, executable, from_disk))

    def __getattr__(self, name):
        return getattr(self._lowered, name)


class Wrapped:
    """Stage 1: a python callable bound to an entry name + config signature.

    Calling it with tracers inlines the plain function (so it composes with
    jit/vmap/scan around it); calling it with concrete arrays dispatches
    through the keyed cache: memory, else lower + compile (which JAX's
    persistent cache may serve from disk).
    """

    def __init__(self, fn: Callable, entry: str, sig: Signature,
                 static: Tuple = (), jit_kwargs: Tuple = ()):
        self.fn = fn
        self.entry = entry
        self.sig = sig
        self.static = tuple(static)
        self.jit_kwargs = tuple(jit_kwargs)
        self._jk_key = _freeze(self.jit_kwargs)

    def _key(self, args):
        treedef, avals = _args_key(args)
        return (self.entry, self.sig, self.static, self._jk_key,
                treedef, avals)

    def lower(self, *args) -> Lowered:
        """Stage the function for the given (abstract or concrete) args;
        cached per (signature, avals) so re-lowering is free."""
        key = self._key(args)
        with _LOCK:
            low = _LOWERED.get(key)
        if low is not None:
            return low
        # trace explicitly so the closed jaxpr is kept on the Lowered:
        # tracekit's J-rules audit the jaxpr, not just the HLO text
        traced = jax.jit(self.fn, **dict(self.jit_kwargs)).trace(*args)
        low = Lowered(key, traced.lower(), jaxpr=traced.jaxpr)
        with _LOCK:
            _LOWERED.setdefault(key, low)
            _STATS["lowerings"] += 1
        return low

    def __call__(self, *args):
        if is_tracing(args):
            return self.fn(*args)
        _count("dispatches")
        hook = _TRACE_HOOK
        t0_ns = time.time_ns() if hook is not None else 0
        t0 = time.perf_counter()
        key = self._key(args)
        with _LOCK:
            comp = _COMPILED.get(key)
        provenance, lower_s, load_s = "memory", 0.0, 0.0
        if comp is not None:
            _count("memory_hits")
        else:
            c0 = time.perf_counter()
            low = self.lower(*args)
            c1 = time.perf_counter()
            comp = low.compile()
            lower_s, load_s = c1 - c0, time.perf_counter() - c1
            provenance = "disk" if comp.from_disk else "compile"
        ann = _TRACE_ANNOTATION
        if ann is not None:
            with ann(self.entry):
                out = comp(*args)
        else:
            out = comp(*args)
        wall = time.perf_counter() - t0
        _note_dispatch(self.entry, wall, lower_s, load_s)
        if hook is not None:
            try:
                hook(entry=self.entry, digest=_key_digest(key),
                     wall_s=wall, compile_s=lower_s + load_s,
                     lower_s=lower_s, load_s=load_s,
                     provenance=provenance, t0_ns=t0_ns,
                     t1_ns=time.time_ns())
            except Exception:
                pass        # observability must never break the dispatch
        return out


def wrap(fn: Callable, entry: str, sig: Optional[Signature] = None, *,
         static: Tuple = (), donate_argnums=None, **jit_kwargs) -> Wrapped:
    """Bind ``fn`` to the keyed cache as ``entry`` under ``sig``.

    Memoized on (entry, sig, static, jit options): wrapping the same
    configuration twice returns the same ``Wrapped`` (and therefore the
    same compiled executables), which is what lets scattered call sites —
    service builders, launch CLIs, ``precompile_fleet`` — share one cache
    entry per configuration.
    """
    sig = sig if sig is not None else Signature()
    if donate_argnums is not None:
        jit_kwargs["donate_argnums"] = tuple(donate_argnums)
    jk = tuple(sorted(jit_kwargs.items(), key=lambda kv: kv[0]))
    memo_key = (entry, sig, tuple(static), _freeze(jk))
    with _LOCK:
        w = _WRAPPED.get(memo_key)
        if w is None:
            w = Wrapped(fn, entry, sig, static=tuple(static), jit_kwargs=jk)
            _WRAPPED[memo_key] = w
    return w


def dispatch(entry: str, sig: Signature, make_fn: Callable[[], Callable],
             *args, static: Tuple = ()):
    """Eager front door for public API functions (``hier.update``,
    ``stream.ingest``, ``query.engine`` ...): route a concrete call through
    the keyed cache, or inline under an ambient trace.  ``make_fn`` builds
    the knob-closed implementation; it runs at most once per (entry, sig,
    static) thanks to the ``wrap`` memo."""
    memo_key = (entry, sig, tuple(static), ())
    with _LOCK:
        w = _WRAPPED.get(memo_key)
    if w is None:
        w = wrap(make_fn(), entry, sig, static=static)
    return w(*args)


# ------------------------------------------------------------ bookkeeping ---


def stats(reset: bool = False) -> dict:
    """Compile-event counters: ``lowerings``/``compiles`` count actual
    staging work, ``memory_hits``/``disk_hits`` count cache service,
    ``dispatches`` counts concrete calls through any ``Wrapped``.

    ``per_entry`` breaks dispatches down by entry name with cumulative
    dispatch wall seconds and set-up seconds (``lower_s``: trace and lower;
    ``load_s``: XLA compile or the persistent cache's load; a memory hit
    adds to neither) — the gauges ``obs.metrics.export_stages_gauges``
    exports.  ``reset=True`` snapshots and zeroes the counters in ONE
    locked step, so concurrent emitters never lose a count between the
    read and the reset (tests/test_obs.py concurrent-emission test)."""
    with _LOCK:
        out = dict(_STATS)
        out["memory_entries"] = len(_COMPILED)
        out["per_entry"] = {e: dict(v) for e, v in _ENTRY_STATS.items()}
        if reset:
            for k in _STATS:
                _STATS[k] = 0
            _ENTRY_STATS.clear()
    return out


def reset_stats() -> None:
    with _LOCK:
        for k in _STATS:
            _STATS[k] = 0
        _ENTRY_STATS.clear()


def clear_memory_cache() -> None:
    """Drop every in-process cache entry (wrapped/lowered/compiled) but
    leave the persistent cache alone — a simulated cold start: the next
    dispatch of a persisted configuration re-lowers, then must report a
    ``disk_hits`` event and zero ``compiles`` (tests/test_stages.py
    round-trip)."""
    with _LOCK:
        _WRAPPED.clear()
        _LOWERED.clear()
        _COMPILED.clear()


# ------------------------------------------------------------- audit hooks --


def lowered_keys() -> Tuple:
    """Snapshot of every cache key lowered so far this process — tracekit's
    J006 (retrace-surface leak) counts distinct aval signatures per
    (entry, signature) over this set."""
    with _LOCK:
        return tuple(_LOWERED.keys())


# One instruction of an optimized HLO module's text:
# "  %fusion.80 = f32[17]{0} fusion(...), ..., metadata={op_name="a/b" ...}"
_HLO_MODULE = re.compile(r"HloModule ([^\s,]+)")
_HLO_INSTR = re.compile(r"\s+(?:ROOT )?%?([\w.\-]+) = ")
_OP_NAME = re.compile(r'metadata=\{[^}]*?op_name="((?:[^"\\]|\\.)*)"')


def parse_op_scopes(text: str) -> Dict[str, Dict[str, str]]:
    """``{module name: {instruction name: op_name}}`` from the text of
    optimized HLO modules (``Compiled.as_text()``).  The ``op_name`` path
    carries every ``jax.named_scope`` the instruction was traced under
    (``jit(run)/while/body/cohort.d1/.../canon.sort/sort``); scope names
    of this program are dotted, ``<layer>.<part>``."""
    out: Dict[str, Dict[str, str]] = {}
    table: Dict[str, str] = {}
    for line in text.splitlines():
        if line.startswith("HloModule "):
            table = out.setdefault(_HLO_MODULE.match(line)[1], {})
            continue
        m = _HLO_INSTR.match(line)
        if m:
            op = _OP_NAME.search(line, m.end())
            table[m[1]] = op[1] if op else ""
    return out


def op_scopes(entry: Optional[str] = None) -> Dict[str, Dict[str, str]]:
    """The op-to-scope tables (``Compiled.op_scopes``) of every executable
    in memory, or of ``entry``'s, merged by HLO module name.  Executables
    that share a module name (every ``jit(run)``) and disagree on an
    instruction leave it out: a trace could not tell them apart."""
    with _LOCK:
        comps = [c for k, c in _COMPILED.items()
                 if entry is None or k[0] == entry]
    out: Dict[str, Dict[str, str]] = {}
    clash: Dict[str, set] = {}
    for comp in comps:
        for module, table in comp.op_scopes().items():
            merged = out.setdefault(module, {})
            bad = clash.setdefault(module, set())
            for name, path in table.items():
                if merged.setdefault(name, path) != path:
                    bad.add(name)
    for module, bad in clash.items():
        for name in bad:
            del out[module][name]
    return out


def compiled_for(wrapped: "Wrapped", *args) -> Compiled:
    """The ``Compiled`` behind one (wrapped, args) dispatch — memory, else
    lower+compile.  Benchmarks use this to read ``cost_analysis`` off
    exactly the executable they just timed."""
    with _LOCK:
        comp = _COMPILED.get(wrapped._key(args))
    return comp if comp is not None else wrapped.lower(*args).compile()


def cost_of(wrapped: "Wrapped", *args) -> dict:
    """Normalized cost columns for one dispatch: ``flops``,
    ``bytes_accessed`` and (when the backend reports it) ``peak_bytes``.
    Values are ``None`` when the executable cannot answer even after the
    re-lowering fallback."""
    comp = compiled_for(wrapped, *args)
    try:
        cost = comp.cost_analysis()
    except Exception:
        cost = {}
    out = dict(flops=cost.get("flops"),
               bytes_accessed=cost.get("bytes accessed"))
    mem = comp.memory_analysis()
    out["peak_bytes"] = None if mem is None \
        else int(getattr(mem, "temp_size_in_bytes", 0))
    return out


def audit(cfg=None, **kw):
    """Post-lowering static analysis over the staged artifacts — the
    ``stages``-side front door to ``repro.analysis.tracekit``.  With a
    config/signature it audits that fleet's dispatch set
    (``tracekit.audit_fleet``); imported lazily so ``stages`` never
    depends on the analysis package."""
    from repro.analysis import tracekit
    return tracekit.audit_fleet(cfg, **kw)


# ------------------------------------------------------- fleet precompile ---


def fleet_jobs(cfg, *, instances: Optional[int] = None,
               blocks: Optional[int] = None,
               queries: Optional[int] = None,
               analytics_num_rows: int = 0, analytics_k: int = 8,
               mesh=None, data_axes=None) -> list:
    """Enumerate a config's production dispatch set as
    ``[(entry, Wrapped, abstract_args), ...]`` — the shared job list behind
    ``precompile_fleet`` (which compiles it) and
    ``repro.analysis.tracekit`` (which audits the same artifacts, so the
    audit set and the launch-warmup set can never drift apart)."""
    from repro.core import distributed, hier, stream
    from repro.core import semiring as sr_mod
    from repro.query import service

    sig = cfg if isinstance(cfg, Signature) else signature_of(cfg)
    sr = sr_mod.get(sig.sr)
    dtype = jnp.dtype(sig.dtype)
    I = (instances if instances is not None
         else getattr(cfg, "instances_per_device", 4))
    T = blocks if blocks is not None else getattr(cfg, "blocks_per_step", 8)
    Q = queries if queries is not None else getattr(cfg, "query_batch", 256)
    B = sig.block_size
    cuts = sig.cuts

    states_abs = jax.eval_shape(
        lambda: distributed.create_instances(I, cuts, B, dtype, sr))
    h_abs = jax.eval_shape(lambda: hier.create(cuts, B, dtype, sr))
    stream_abs = tuple(jax.ShapeDtypeStruct((I, T, B), d)
                       for d in (jnp.int32, jnp.int32, dtype))
    block_abs = tuple(jax.ShapeDtypeStruct((B,), d)
                      for d in (jnp.int32, jnp.int32, dtype))
    q_abs = (jax.ShapeDtypeStruct((Q,), jnp.int32),
             jax.ShapeDtypeStruct((Q,), jnp.int32))

    jobs = []
    # ingest-side sigs never pin the query-only l0_mode knob
    # (signature_for_state / the CLIs leave it None) — strip it so the
    # precompiled entries land on exactly the keys the ingest dispatches use
    ingest_sig = dataclasses.replace(sig, l0_mode=None)
    jobs.append(("stream.ingest_instances",
                 stream.ingest_instances_jit(ingest_sig),
                 (states_abs,) + stream_abs))
    jobs.append(("service.ingest",
                 service.make_ingest_fn(
                     sr, use_kernel=sig.use_kernel, lazy_l0=sig.lazy_l0,
                     fused=sig.fused, chunk=sig.chunk,
                     batch_mode=sig.batch_mode or "grouped"),
                 (states_abs,) + stream_abs))
    jobs.append(("service.point_query",
                 service.make_point_query_fn(
                     sr, use_kernel=sig.use_kernel,
                     l0_mode=sig.l0_mode or "auto"),
                 (states_abs,) + q_abs))
    if analytics_num_rows:
        jobs.append(("service.analytics",
                     service.make_analytics_fn(analytics_num_rows,
                                               analytics_k, sr),
                     (states_abs,)))
    # single-instance core ops (checkpoint/drain/read paths); hier.update
    # only executes switch/branchfree — map the batched modes to the
    # single-instance default.
    single_mode = "branchfree" if sig.batch_mode == "branchfree" \
        else "switch"
    single_sig = dataclasses.replace(ingest_sig, batch_mode=single_mode,
                                     chunk=1)
    jobs.append(("hier.update", hier.update_wrapped(single_sig),
                 (h_abs,) + block_abs + (None,)))
    jobs.append(("hier.flush", hier.flush_wrapped(single_sig), (h_abs,)))
    jobs.append(("hier.query_all", hier.query_all_wrapped(single_sig),
                 (h_abs,)))
    from repro.query import engine
    jobs.append(("query.engine.point_lookup",
                 engine.point_lookup_wrapped(
                     dataclasses.replace(single_sig,
                                         l0_mode=sig.l0_mode or "auto")),
                 (h_abs,) + q_abs))
    # fleet observability sample (obs.metrics.fleet_sample): knob-free —
    # the snapshot reads counters/occupancy only, so its signature pins
    # geometry alone and every (sr, fused, ...) variant shares one entry
    jobs.append(("hier.metrics_snapshot",
                 hier.metrics_snapshot_wrapped(
                     signature_of(cuts=cuts, block_size=B, dtype=dtype)),
                 (states_abs,)))
    if mesh is not None:
        jobs.append(("distributed.sharded_ingest_fn",
                     distributed.sharded_ingest_fn(
                         mesh, data_axes, sr, lazy_l0=sig.lazy_l0,
                         use_kernel=sig.use_kernel, fused=sig.fused,
                         chunk=sig.chunk,
                         batch_mode=sig.batch_mode or "grouped"),
                     (states_abs,) + stream_abs))
        jobs.append(("distributed.sharded_query_fn",
                     distributed.sharded_query_fn(
                         mesh, data_axes, sr, use_kernel=sig.use_kernel,
                         l0_mode=sig.l0_mode or "auto"),
                     (states_abs,) + q_abs))
    return jobs


def kernel_jobs() -> list:
    """Enumerate the Pallas kernel families' representative jobs
    (``repro.kernels.registry.jobs()``) — the kernel-level sibling of
    ``fleet_jobs``: ``repro.analysis.palkit`` audits this list (K001-K006
    + VMEM budgets), tests/test_kernel_registry.py checks each job
    against its oracle, and a TPU launch can warm exactly the same set.
    Imported lazily so ``stages`` never depends on the kernels package."""
    from repro.kernels import registry
    return registry.jobs()


def precompile_fleet(cfg, *, instances: Optional[int] = None,
                     blocks: Optional[int] = None,
                     queries: Optional[int] = None,
                     analytics_num_rows: int = 0, analytics_k: int = 8,
                     mesh=None, data_axes=None) -> dict:
    """Compile a ``D4MConfig``'s whole dispatch set once, at launch.

    Enumerates the production entry points a fleet run touches
    (``fleet_jobs``) — the instance-batched ingest step with telemetry
    (``launch/ingest``) and the donated telemetry-free service variant,
    the service point-query and top-k analytics dispatches, the
    single-instance ``hier``/``engine`` ops, and the sharded ingest/query
    programs when ``mesh``/``data_axes`` are given — and drives each
    through lower+compile against abstract inputs.  With a warm persistent
    cache this is lowering plus deserialization: ``stats()["compiles"]``
    stays 0 and a subsequent ``launch/ingest`` + ``launch/query`` run performs
    ZERO compile events beyond its one-off set-up programs, fleet
    construction and the synthetic stream generator (asserted in
    tests/test_stages.py).

    ``instances``/``blocks``/``queries`` override the config's
    ``instances_per_device``/``blocks_per_step``/``query_batch`` so a CLI
    can precompile the exact shapes it is about to dispatch.  ``cfg`` may
    also be an already-canonical ``Signature`` (the launch CLIs build one
    from argparse knobs).  Returns ``{entry: "compiled"|"disk"|"cached"}``.
    """
    jobs = fleet_jobs(cfg, instances=instances, blocks=blocks,
                      queries=queries,
                      analytics_num_rows=analytics_num_rows,
                      analytics_k=analytics_k, mesh=mesh,
                      data_axes=data_axes)
    report = {}
    for entry, wrapped, args in jobs:
        before = stats()
        compiled_for(wrapped, *args)
        after = stats()
        if after["compiles"] > before["compiles"]:
            report[entry] = "compiled"
        elif after["disk_hits"] > before["disk_hits"]:
            report[entry] = "disk"
        else:
            report[entry] = "cached"
    return report

