"""Unit tests for the associative-array segment layer."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import assoc, semiring

SRS = [semiring.PLUS_TIMES, semiring.MAX_PLUS, semiring.MIN_PLUS]


def dense_ref(rows, cols, vals, shape, sr):
    out = np.full(shape, {"plus.times": 0.0, "max.plus": -np.inf,
                          "min.plus": np.inf, "max.min": -np.inf}[sr.name])
    for r, c, v in zip(np.asarray(rows), np.asarray(cols), np.asarray(vals)):
        if sr.name == "plus.times":
            out[r, c] += v
        elif sr.name in ("max.plus", "max.min"):
            out[r, c] = max(out[r, c], v)
        else:
            out[r, c] = min(out[r, c], v)
    return out


@pytest.mark.parametrize("sr", SRS, ids=lambda s: s.name)
def test_from_coo_matches_dense(sr):
    rng = np.random.default_rng(0)
    rows = jnp.asarray(rng.integers(0, 10, 64), jnp.int32)
    cols = jnp.asarray(rng.integers(0, 10, 64), jnp.int32)
    vals = jnp.asarray(rng.normal(size=64), jnp.float32)
    seg, ovf = assoc.from_coo(rows, cols, vals, 128, sr)
    assert int(ovf) == 0
    got = np.asarray(assoc.to_dense(seg, 10, 10, sr))
    want = dense_ref(rows, cols, vals, (10, 10), sr)
    mask = ~np.isinf(want)
    np.testing.assert_allclose(got[mask], want[mask], rtol=1e-6)


def test_canonical_form_invariants():
    rng = np.random.default_rng(1)
    rows = jnp.asarray(rng.integers(0, 50, 100), jnp.int32)
    cols = jnp.asarray(rng.integers(0, 50, 100), jnp.int32)
    vals = jnp.ones(100, jnp.float32)
    seg, _ = assoc.from_coo(rows, cols, vals, 128)
    nnz = int(seg.nnz)
    hi, lo = np.asarray(seg.hi), np.asarray(seg.lo)
    keys = hi[:nnz].astype(np.int64) * (2**31) + lo[:nnz]
    assert np.all(np.diff(keys) > 0), "live keys sorted and unique"
    assert np.all(hi[nnz:] == assoc.SENTINEL)
    assert np.all(np.asarray(seg.val)[nnz:] == 0.0)


def test_merge_commutes_and_overflow():
    rng = np.random.default_rng(2)
    def mk(seed, n):
        r = np.random.default_rng(seed)
        return assoc.from_coo(
            jnp.asarray(r.integers(0, 30, n), jnp.int32),
            jnp.asarray(r.integers(0, 30, n), jnp.int32),
            jnp.asarray(r.normal(size=n), jnp.float32), n)[0]
    a, b = mk(3, 40), mk(4, 24)
    ab, o1 = assoc.merge(a, b, 64)
    ba, o2 = assoc.merge(b, a, 64)
    assert int(o1) == int(o2) == 0
    np.testing.assert_allclose(np.asarray(assoc.to_dense(ab, 30, 30)),
                               np.asarray(assoc.to_dense(ba, 30, 30)), rtol=1e-6)
    # forced overflow drops the largest keys, keeps the sorted prefix
    small, ovf = assoc.merge(a, b, 8)
    assert int(small.nnz) == 8 and int(ovf) == int(ab.nnz) - 8
    np.testing.assert_array_equal(np.asarray(small.hi[:8]), np.asarray(ab.hi[:8]))


def test_mask_and_duplicates():
    rows = jnp.array([5, 5, 5, 2], jnp.int32)
    cols = jnp.array([7, 7, 7, 1], jnp.int32)
    vals = jnp.array([1., 2., 4., 8.])
    mask = jnp.array([True, True, False, True])
    seg, _ = assoc.from_coo(rows, cols, vals, 8, mask=mask)
    assert int(seg.nnz) == 2
    assert float(assoc.lookup(seg, 5, 7)) == 3.0
    assert float(assoc.lookup(seg, 2, 1)) == 8.0
    assert float(assoc.lookup(seg, 9, 9)) == 0.0


def test_reductions_and_spmv():
    rows = jnp.array([0, 0, 1, 2], jnp.int32)
    cols = jnp.array([1, 2, 2, 0], jnp.int32)
    vals = jnp.array([1., 2., 3., 4.])
    seg, _ = assoc.from_coo(rows, cols, vals, 8)
    np.testing.assert_allclose(np.asarray(assoc.reduce_rows(seg, 3)),
                               [3., 3., 4.])
    np.testing.assert_allclose(np.asarray(assoc.reduce_cols(seg, 3)),
                               [4., 1., 5.])
    # Fig 1 neighbor query: x = indicator of node 0 -> neighbors of 0
    x = jnp.array([1., 0., 0.])
    y = assoc.spmv(seg, x, 3)          # A @ x over rows: who does 0 point to?
    # y[r] = sum_c A[r,c] x[c]; indicator on col 0 -> in-edges of node 0
    np.testing.assert_allclose(np.asarray(y), [0., 0., 4.])


def test_vmap_instances():
    rng = np.random.default_rng(5)
    rows = jnp.asarray(rng.integers(0, 10, (3, 32)), jnp.int32)
    cols = jnp.asarray(rng.integers(0, 10, (3, 32)), jnp.int32)
    vals = jnp.ones((3, 32), jnp.float32)
    segs, _ = jax.vmap(lambda r, c, v: assoc.from_coo(r, c, v, 64))(rows, cols, vals)
    dense = jax.vmap(lambda s: assoc.to_dense(s, 10, 10))(segs)
    for i in range(3):
        want = dense_ref(rows[i], cols[i], vals[i], (10, 10), semiring.PLUS_TIMES)
        np.testing.assert_allclose(np.asarray(dense[i]), want, rtol=1e-6)


def test_int_values_max_semiring():
    rows = jnp.array([1, 1, 0], jnp.int32)
    cols = jnp.array([1, 1, 0], jnp.int32)
    vals = jnp.array([3, 9, 5], jnp.int32)
    seg, _ = assoc.from_coo(rows, cols, vals, 4, semiring.MAX_PLUS)
    assert int(assoc.lookup(seg, 1, 1, semiring.MAX_PLUS)) == 9
    assert int(assoc.lookup(seg, 0, 0, semiring.MAX_PLUS)) == 5


ALL_SRS = SRS + [semiring.MAX_MIN]
_ADD = {"plus.times": lambda a, b: a + b, "max.plus": max, "min.plus": min,
        "max.min": max}
_ZERO = {"plus.times": 0.0, "max.plus": -np.inf, "min.plus": np.inf,
         "max.min": -np.inf}


def _canon_case(case, rng):
    """(hi, lo, val, out_capacity) for one ``_canonicalize`` input shape."""
    S = assoc.SENTINEL
    if case == "all_unique":
        n = 300
        key = rng.permutation(n * 4)[:n]
        hi, lo = key // 4, key % 4
    elif case == "one_run":
        n = 20_000          # a run longer than every row of the blocked scan
        hi, lo = np.full(n, 7), np.full(n, 3)
    elif case == "mixed_runs":
        lengths = rng.choice([1, 1, 1, 2, 5, 129, 700], size=120)
        hi = np.repeat(np.arange(lengths.size) // 3, lengths)
        lo = np.repeat(np.arange(lengths.size) % 3, lengths)
        order = rng.permutation(hi.size)
        hi, lo = hi[order], lo[order]
    elif case == "sentinel_masked":
        n = 500
        hi, lo = rng.integers(0, 40, n), rng.integers(0, 5, n)
        dead = rng.random(n) < 0.3
        hi, lo = np.where(dead, S, hi), np.where(dead, S, lo)
    elif case == "all_sentinel":
        hi = lo = np.full(256, S)
    else:                   # overflow: fewer output slots than unique keys
        hi, lo = rng.integers(0, 30, 400), rng.integers(0, 4, 400)
    n = hi.size
    val = rng.integers(-8, 9, n).astype(np.float32)
    cap = 37 if case == "overflow" else n + 5
    return hi.astype(np.int32), lo.astype(np.int32), val, cap


def _scatter_prims(jaxpr):
    found = [e.primitive.name for e in jaxpr.eqns
             if e.primitive.name.startswith("scatter")]
    for e in jaxpr.eqns:
        for p in e.params.values():
            for sub in (p if isinstance(p, (list, tuple)) else [p]):
                inner = getattr(sub, "jaxpr", sub)
                if hasattr(inner, "eqns"):
                    found += _scatter_prims(inner)
    return found


@pytest.mark.parametrize("case", ["all_unique", "one_run", "mixed_runs",
                                  "sentinel_masked", "all_sentinel",
                                  "overflow"])
@pytest.mark.parametrize("sr", ALL_SRS, ids=lambda s: s.name)
def test_canonicalize_matches_a_dict_reference(sr, case):
    """``_canonicalize`` against a plain dict fold of the same entries, on
    the run shapes the segmented scan and the compaction sort must get
    right; integer values make plus.times exact in any summation order.
    The largest keys drop first past ``out_capacity``, and no scatter is
    traced."""
    hi, lo, val, cap = _canon_case(case, np.random.default_rng(14))
    ref = {}
    for h, l, v in zip(hi.tolist(), lo.tolist(), val.tolist()):
        if h != assoc.SENTINEL:
            ref[h, l] = _ADD[sr.name](ref[h, l], v) if (h, l) in ref else v
    want = sorted(ref.items())
    args = (jnp.asarray(hi), jnp.asarray(lo), jnp.asarray(val))
    seg, ovf = jax.jit(assoc._canonicalize, static_argnums=(3, 4))(
        *args, cap, sr)
    nnz = int(seg.nnz)
    assert nnz == min(len(want), cap)
    assert int(ovf) == max(len(want) - cap, 0)
    got = list(zip(zip(np.asarray(seg.hi[:nnz]).tolist(),
                       np.asarray(seg.lo[:nnz]).tolist()),
                   np.asarray(seg.val[:nnz]).tolist()))
    assert got == want[:cap]
    assert seg.hi.shape == seg.lo.shape == seg.val.shape == (cap,)
    assert seg.val.dtype == jnp.float32
    assert np.all(np.asarray(seg.hi[nnz:]) == assoc.SENTINEL)
    assert np.all(np.asarray(seg.lo[nnz:]) == assoc.SENTINEL)
    assert np.all(np.asarray(seg.val[nnz:]) == _ZERO[sr.name])
    jaxpr = jax.make_jaxpr(
        lambda h, l, v: assoc._canonicalize(h, l, v, cap, sr))(*args)
    assert _scatter_prims(jaxpr.jaxpr) == []
