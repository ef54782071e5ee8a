"""Per-kernel allclose sweeps against the ref.py pure-jnp oracles
(spec deliverable c): shapes x dtypes x mask patterns, interpret mode."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref

SHAPES = [(256, 512), (512, 1024), (300, 700), (257, 513)]
DIMS = [2, 3, 8, 11]


def _mk(rng, nq, nd, d, dtype):
    q = rng.normal(size=(nq, d)).astype(dtype)
    dd = rng.normal(loc=0.5, size=(nd, d)).astype(dtype)
    qv = rng.random(nq) > 0.05
    dv = rng.random(nd) > 0.05
    qv[0] = dv[0] = True
    return (jnp.asarray(q), jnp.asarray(dd), jnp.asarray(qv),
            jnp.asarray(dv))


@pytest.mark.parametrize("nq,nd", SHAPES)
@pytest.mark.parametrize("d", DIMS)
def test_hausdorff_kernel_sweep(nq, nd, d):
    rng = np.random.default_rng(nq + nd + d)
    q, dd, qv, dv = _mk(rng, nq, nd, d, np.float32)
    got = ops.directed_hausdorff(q, dd, qv, dv)
    want = ref.directed_hausdorff(q, dd, qv, dv)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("nq,nd", SHAPES[:2])
def test_nn_distance_kernel_sweep(nq, nd):
    rng = np.random.default_rng(nq)
    q, dd, qv, dv = _mk(rng, nq, nd, 2, np.float32)
    gd, gi = ops.nn_distance(q, dd, qv, dv)
    wd, wi = ref.nn_distance(q, dd, qv, dv)
    np.testing.assert_allclose(gd, wd, rtol=1e-5, atol=1e-5)
    assert (np.asarray(gi) == np.asarray(wi)).all()


@pytest.mark.parametrize("n,m", [(256, 256), (300, 400), (512, 257)])
@pytest.mark.parametrize("d", [2, 3])
def test_bound_matrix_kernel_sweep(n, m, d):
    rng = np.random.default_rng(n + m)
    oq = jnp.asarray(rng.normal(size=(n, d)).astype(np.float32))
    od = jnp.asarray(rng.normal(size=(m, d)).astype(np.float32))
    rq = jnp.asarray(rng.uniform(0, 2, n).astype(np.float32))
    rd = jnp.asarray(rng.uniform(0, 2, m).astype(np.float32))
    glb, gub = ops.bound_matrices(oq, rq, od, rd)
    wlb, wub = ref.bound_matrix(oq, rq, od, rd)
    np.testing.assert_allclose(glb, wlb, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(gub, wub, rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("na,nb,w", [(256, 256, 32), (300, 270, 8),
                                     (512, 300, 64)])
def test_set_intersect_kernel_sweep(na, nb, w):
    rng = np.random.default_rng(na + w)
    sa = jnp.asarray(rng.integers(0, 2**32, (na, w), dtype=np.uint32))
    sb = jnp.asarray(rng.integers(0, 2**32, (nb, w), dtype=np.uint32))
    got = ops.set_intersect_counts(sa, sb)
    want = ref.set_intersect_count(sa, sb)
    assert (np.asarray(got) == np.asarray(want)).all()


def test_hausdorff_bf16_tolerance():
    rng = np.random.default_rng(9)
    q, dd, qv, dv = _mk(rng, 256, 512, 2, np.float32)
    got = ops.directed_hausdorff(q.astype(jnp.bfloat16).astype(jnp.float32),
                                 dd, qv, dv)
    want = ref.directed_hausdorff(q, dd, qv, dv)
    np.testing.assert_allclose(got, want, rtol=2e-2, atol=2e-2)


def test_kernel_vs_ref_path_boundary():
    """Sizes below tile thresholds must route to ref and stay correct."""
    rng = np.random.default_rng(3)
    q, dd, qv, dv = _mk(rng, 10, 20, 2, np.float32)
    got = ops.directed_hausdorff(q, dd, qv, dv)
    want = ref.directed_hausdorff(q, dd, qv, dv)
    np.testing.assert_allclose(got, want, rtol=1e-6)


@pytest.mark.parametrize("nq,nd", [(24, 100), (32, 130)])
def test_hausdorff_grid_matches_op_per_pair(nq, nd):
    """The (B, C) pair-grid evaluator (ExactHaus phase-2 hot path) must be
    BITWISE equal per pair to the jitted per-pair op (the host oracle's
    evaluation path) on sub-threshold shapes — tiled streaming (incl. a
    non-tile-multiple nd, which pads with invalid columns) reassociates
    only exact min/max.  The eager ref differs by fusion ulps (no FMA
    contraction outside jit), which is why the pipeline bit-identity
    contract is stated between the jitted programs."""
    rng = np.random.default_rng(nq + nd)
    B, C = 3, 4
    q = jnp.asarray(rng.normal(size=(B, nq, 2)).astype(np.float32))
    ds = jnp.asarray(rng.normal(size=(B, C, nd, 2)).astype(np.float32))
    qv = jnp.asarray(rng.random((B, nq)) > 0.1)
    dv = jnp.asarray(rng.random((B, C, nd)) > 0.3)
    got = np.asarray(ops.directed_hausdorff_grid(q, ds, qv, dv, tile=64))
    per_pair = jax.jit(jax.vmap(ref.directed_hausdorff,
                                in_axes=(None, 0, None, 0)))
    for b in range(B):
        want = np.asarray(per_pair(q[b], ds[b], qv[b], dv[b]))
        np.testing.assert_array_equal(got[b], want)


# ---------------------------------------------------------------------------
# Routing-boundary bit-identity (autotuner safety net): at, just below, and
# just above every kernel-vs-ref threshold the DEFAULT route must be bitwise
# one of the two explicitly-forced routes (routing determinism — resolve()
# picks a path, it never computes a third thing), and the two forced routes
# must agree with each other.  Kernel-vs-ref agreement is asserted BITWISE
# wherever XLA's FMA-contraction choice coincides for the two program
# shapes (empirically stable at the pinned shapes below) and within ~ulp
# tolerance elsewhere; production routing shifts are additionally gated
# bitwise per shape bucket by the engine tuner (engine/tune.py), so a
# tuned table can never shift a result.
# ---------------------------------------------------------------------------

BOUNDARY = [(255, 512), (256, 512), (257, 513)]


def _routes(fn, *args, **kw):
    """(default, forced-kernel, forced-ref) outputs of one op."""
    return (np.asarray(fn(*args, **kw)),
            np.asarray(fn(*args, use_kernel=True, **kw)),
            np.asarray(fn(*args, use_kernel=False, **kw)))


@pytest.mark.parametrize("nq,nd", BOUNDARY)
def test_hausdorff_routing_boundary(nq, nd):
    rng = np.random.default_rng(nq)
    q, dd, qv, dv = _mk(rng, nq, nd, 2, np.float32)
    default, kern, refp = _routes(ops.directed_hausdorff, q, dd, qv, dv)
    assert default.tobytes() in (kern.tobytes(), refp.tobytes())
    np.testing.assert_array_equal(kern, refp)


@pytest.mark.parametrize("nq,nd", BOUNDARY)
def test_nn_distance_routing_boundary(nq, nd):
    rng = np.random.default_rng(nq + 1)
    q, dd, qv, dv = _mk(rng, nq, nd, 2, np.float32)
    dd_, di = ops.nn_distance(q, dd, qv, dv)
    kd, ki = ops.nn_distance(q, dd, qv, dv, use_kernel=True)
    rd, ri = ops.nn_distance(q, dd, qv, dv, use_kernel=False)
    default, kern, refp = np.asarray(dd_), np.asarray(kd), np.asarray(rd)
    assert default.tobytes() in (kern.tobytes(), refp.tobytes())
    np.testing.assert_array_equal(kern, refp)
    # NN indices must be exactly equal on every route (argmin ties break
    # identically: both paths scan D in the same order)
    np.testing.assert_array_equal(np.asarray(ki), np.asarray(ri))
    np.testing.assert_array_equal(np.asarray(di), np.asarray(ki))


@pytest.mark.parametrize("n,m,bitwise", [(255, 256, True), (256, 256, True),
                                         (257, 256, False)])
def test_bound_matrices_routing_boundary(n, m, bitwise):
    """Single-tile shapes (<= one (256, 256) tile after padding) are
    bitwise across the route flip; the two-tile 257 crosses an XLA
    FMA-contraction boundary and agrees to ~ulp instead."""
    rng = np.random.default_rng(n + m)
    oq = jnp.asarray(rng.normal(size=(n, 2)).astype(np.float32))
    od = jnp.asarray(rng.normal(size=(m, 2)).astype(np.float32))
    rq = jnp.asarray(rng.uniform(0, 2, n).astype(np.float32))
    rd = jnp.asarray(rng.uniform(0, 2, m).astype(np.float32))
    for part in (0, 1):
        default, kern, refp = _routes(
            lambda *a, **k: ops.bound_matrices(*a, **k)[part],
            oq, rq, od, rd)
        assert default.tobytes() in (kern.tobytes(), refp.tobytes())
        if bitwise:
            np.testing.assert_array_equal(kern, refp)
        else:
            np.testing.assert_allclose(kern, refp, rtol=1e-5, atol=1e-6)


LEVELS7 = ((0, 1), (1, 3), (3, 7))


def _mk_grid(rng, B, S, N=7, d=2):
    oq = rng.normal(size=(B, N, d)).astype(np.float32)
    od = rng.normal(size=(S, N, d)).astype(np.float32)
    rq = rng.uniform(0, 1, (B, N)).astype(np.float32)
    rd = rng.uniform(0, 1, (S, N)).astype(np.float32)
    qok = rng.random((B, N)) > 0.2
    dok = rng.random((S, N)) > 0.2
    qok[:, 0] = dok[:, 0] = True
    return tuple(map(jnp.asarray, (oq, rq, qok, od, rd, dok)))


@pytest.mark.parametrize("B,S,bitwise", [(1, 7, True), (3, 5, True),
                                         (4, 17, True), (1, 128, True),
                                         (8, 128, False), (8, 512, False)])
def test_bound_grid_routing_boundary(B, S, bitwise):
    """The fused batched bound kernel vs its fused jnp oracle across the
    engine's actual batch buckets — bitwise at the shapes where XLA's
    contraction choice coincides, ~ulp elsewhere — plus routing
    determinism of the default route."""
    rng = np.random.default_rng(B + S)
    args = _mk_grid(rng, B, S)
    for part in (0, 1):
        default, kern, refp = _routes(
            lambda *a, **k: ops.bound_grid(*a, levels=LEVELS7, **k)[part],
            *args)
        assert default.tobytes() in (kern.tobytes(), refp.tobytes())
        if bitwise:
            np.testing.assert_array_equal(kern, refp)
        else:
            np.testing.assert_allclose(kern, refp, rtol=5e-5, atol=1e-5)


# levels wider than bound_matrix.UNROLL nodes run the kernel's reduction
# as a rolled loop instead of an unrolled one
WIDE_LEVELS = LEVELS7 + ((7, 15), (15, 31), (31, 63))


@pytest.mark.parametrize("N,B,S,bitwise", [(31, 1, 7, True),
                                           (31, 3, 5, True),
                                           (63, 1, 7, False)])
def test_bound_grid_wide_levels(N, B, S, bitwise):
    """Tree levels past the unroll limit (Query.refine_levels >= 5): the
    kernel vs its fused jnp oracle — bitwise where XLA's contraction
    choice coincides for the two program shapes, ~ulp elsewhere."""
    levels = tuple(lv for lv in WIDE_LEVELS if lv[1] <= N)
    args = _mk_grid(np.random.default_rng(N + B + S), B, S, N=N)
    kern = ops.bound_grid(*args, levels=levels, use_kernel=True)
    refp = ops.bound_grid(*args, levels=levels, use_kernel=False)
    for k, r in zip(kern, refp):
        k, r = np.asarray(k), np.asarray(r)
        assert k.shape == (len(levels), B, S)
        if bitwise:
            np.testing.assert_array_equal(k, r)
        else:
            np.testing.assert_allclose(k, r, rtol=5e-5, atol=1e-5)


def test_bound_grid_threshold_crossing(monkeypatch):
    """At the default (256, 256) threshold the route flips to the kernel;
    just below it stays on the fused oracle.  The default route must be
    bitwise equal to whichever forced route resolve() picked (routing
    determinism), and the two routes agree to ~ulp across the flip —
    a tuned table additionally gates any route change on BITWISE equality
    at the probe shape (engine/tune.py)."""
    from repro.kernels import autotune

    # this test pins DEFAULT routing semantics — neutralize the CI
    # forcing env vars (the rest of the suite runs under them unchanged)
    monkeypatch.delenv("REPRO_FORCE_KERNEL", raising=False)
    monkeypatch.delenv("REPRO_FORCE_REF", raising=False)
    assert not autotune.resolve("bound_grid", (255, 256)).use_kernel
    assert autotune.resolve("bound_grid", (256, 256)).use_kernel
    rng = np.random.default_rng(0)
    for B, expect_kernel in ((255, False), (256, True)):
        args = _mk_grid(rng, B, 256)
        default = ops.bound_grid(*args, levels=LEVELS7)
        forced = ops.bound_grid(*args, levels=LEVELS7,
                                use_kernel=expect_kernel)
        other = ops.bound_grid(*args, levels=LEVELS7,
                               use_kernel=not expect_kernel)
        for d, f, o in zip(default, forced, other):
            np.testing.assert_array_equal(np.asarray(d), np.asarray(f))
            np.testing.assert_allclose(np.asarray(f), np.asarray(o),
                                       rtol=5e-5, atol=1e-5)


def test_hausdorff_grid_kernel_path():
    """Kernel-sized shapes route the pair grid through the same Pallas
    streaming kernel as directed_hausdorff (vmapped over the grid), so
    the TPU hot path stays on the kernel; values match the per-pair op."""
    rng = np.random.default_rng(11)
    B, C, nq, nd = 2, 2, 256, 512
    q = jnp.asarray(rng.normal(size=(B, nq, 2)).astype(np.float32))
    ds = jnp.asarray(rng.normal(size=(B, C, nd, 2)).astype(np.float32))
    qv = jnp.asarray(rng.random((B, nq)) > 0.05)
    dv = jnp.asarray(rng.random((B, C, nd)) > 0.05)
    got = np.asarray(ops.directed_hausdorff_grid(q, ds, qv, dv))
    for b in range(B):
        for c in range(C):
            want = ops.directed_hausdorff(q[b], ds[b, c], qv[b], dv[b, c])
            np.testing.assert_allclose(got[b, c], np.asarray(want),
                                       rtol=1e-6)
