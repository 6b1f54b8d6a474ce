"""jaxpr walker census (core/tracing.py): per-primitive trace_gs
coverage, canonical primitive naming, and the depth-guarded traversal
the spatterlint rules share (ISSUE 6 satellites)."""
import jax
import jax.numpy as jnp
import pytest

from repro.core.tracing import (JaxprWalkError, count_primitives,
                                find_dtype_eqns, find_primitive_eqns,
                                hlo_stats, iter_eqns, normalize_primitive,
                                trace_gs)

X = jnp.arange(16.0)
I = jnp.array([1, 5, 9], jnp.int32)
V = jnp.ones(3)


# ---------------------------------------------------------------------------
# trace_gs covers every _GS_PRIMS primitive (one test per primitive)
# ---------------------------------------------------------------------------

def _one_access(fn, *args):
    report = trace_gs(fn, *args)
    assert len(report.accesses) == 1, \
        [a.primitive for a in report.accesses]
    return report.accesses[0]


def test_trace_gs_gather():
    a = _one_access(lambda x, i: x[i], X, I)
    assert (a.primitive, a.kind) == ("gather", "gather")
    assert a.n_lookups == 3 and a.moved_bytes == 3 * 4


def test_trace_gs_scatter():
    a = _one_access(lambda x, i, v: x.at[i].set(v), X, I, V)
    assert (a.primitive, a.kind) == ("scatter", "scatter")
    assert a.n_lookups == 3


def test_trace_gs_scatter_add():
    a = _one_access(lambda x, i, v: x.at[i].add(v), X, I, V)
    assert (a.primitive, a.kind) == ("scatter_add", "scatter")


def test_trace_gs_scatter_mul():
    a = _one_access(lambda x, i, v: x.at[i].mul(v), X, I, V)
    assert (a.primitive, a.kind) == ("scatter_mul", "scatter")


def test_trace_gs_scatter_min():
    a = _one_access(lambda x, i, v: x.at[i].min(v), X, I, V)
    assert (a.primitive, a.kind) == ("scatter_min", "scatter")


def test_trace_gs_scatter_max():
    a = _one_access(lambda x, i, v: x.at[i].max(v), X, I, V)
    assert (a.primitive, a.kind) == ("scatter_max", "scatter")


def test_trace_gs_dynamic_slice():
    a = _one_access(lambda x: jax.lax.dynamic_slice(x, (2,), (4,)), X)
    assert (a.primitive, a.kind) == ("dynamic_slice", "gather")


def test_trace_gs_dynamic_update_slice():
    a = _one_access(
        lambda x, v: jax.lax.dynamic_update_slice(x, v, (2,)), X, V)
    assert (a.primitive, a.kind) == ("dynamic_update_slice", "scatter")


@pytest.mark.parametrize("mode", ["fill", "clip"])
def test_trace_gs_gather_mode_variants(mode):
    # jnp.take(mode=...) wraps the gather in a pjit body — the recursive
    # walk must still count it (the undercount this satellite fixes)
    a = _one_access(lambda x, i: jnp.take(x, i, mode=mode), X, I)
    assert (a.primitive, a.kind) == ("gather", "gather")
    a = _one_access(
        lambda x, i: x.at[i].get(mode=mode, fill_value=0.0), X, I)
    assert a.kind == "gather"


def test_trace_gs_counts_all_scatter_variants_together():
    def mixed(x, i, v):
        x = x.at[i].add(v)
        x = x.at[i].min(v)
        x = x.at[i].max(v)
        return x

    report = trace_gs(mixed, X, I, V)
    assert sorted(a.primitive for a in report.accesses) == \
        ["scatter_add", "scatter_max", "scatter_min"]
    assert all(a.kind == "scatter" for a in report.accesses)
    assert report.gs_bytes == 3 * (3 * 4)     # three 3-lane f32 updates


# ---------------------------------------------------------------------------
# canonical primitive names (the sort/sort_p unification satellite)
# ---------------------------------------------------------------------------

def test_normalize_primitive():
    assert normalize_primitive("sort") == "sort"
    assert normalize_primitive("sort_p") == "sort"
    assert normalize_primitive("scatter-add") == "scatter_add"
    assert normalize_primitive("scatter_add") == "scatter_add"
    assert normalize_primitive("scatter-add_p") == "scatter_add"
    assert normalize_primitive("pallas_call") == "pallas_call"


def test_count_primitives_uses_canonical_names():
    counts = count_primitives(jax.make_jaxpr(jnp.sort)(X))
    # ONE lookup suffices now; no hyphen/underscore/suffix aliases
    assert counts["sort"] == 1
    assert "sort_p" not in counts
    counts = count_primitives(
        jax.make_jaxpr(lambda x, i, v: x.at[i].add(v))(X, I, V))
    assert counts["scatter_add"] == 1
    assert "scatter-add" not in counts


def test_count_primitives_recurses_into_jit_bodies():
    counts = count_primitives(
        jax.make_jaxpr(jax.jit(lambda x: jnp.sort(x) * 2))(X))
    assert counts["sort"] == 1


def test_find_primitive_eqns_matches_any_spelling():
    jaxpr = jax.make_jaxpr(jnp.sort)(X)
    for spelling in ("sort", "sort_p"):
        hits = find_primitive_eqns(jaxpr, (spelling,))
        assert len(hits) == 1 and hits[0][0] == "sort"
        assert "sort" in hits[0][1]


# ---------------------------------------------------------------------------
# depth-guarded traversal + dtype and HLO censuses (walker growth)
# ---------------------------------------------------------------------------

def test_iter_eqns_depth_guard_raises_not_undercounts():
    fn = lambda x: x + 1                               # noqa: E731
    for _ in range(12):
        fn = jax.jit(fn)
    jaxpr = jax.make_jaxpr(fn)(X)
    assert count_primitives(jaxpr)["add"] == 1         # default: deep enough
    with pytest.raises(JaxprWalkError, match="max_depth"):
        count_primitives(jaxpr, max_depth=4)
    with pytest.raises(JaxprWalkError):
        list(iter_eqns(jaxpr, max_depth=4))


def test_find_dtype_eqns():
    with jax.enable_x64():
        j64 = jax.make_jaxpr(lambda x: x * 2.0)(
            jnp.arange(4, dtype=jnp.float64))
    assert find_dtype_eqns(j64, "float64")
    j32 = jax.make_jaxpr(lambda x: x * 2.0)(X)
    assert find_dtype_eqns(j32, "float64") == []


def test_hlo_stats_reads_donation_markers():
    aval = jax.ShapeDtypeStruct((8,), jnp.float32)
    plain = jax.jit(lambda a, b: a + b)
    donating = jax.jit(lambda a, b: a + b, donate_argnums=(0,))
    assert hlo_stats(plain.lower(aval, aval).as_text()) == {
        "num_partitions": 1, "shardings": set(), "aliased_params": 0}
    st = hlo_stats(donating.lower(aval, aval).as_text())
    assert st["aliased_params"] >= 1
