"""Sender (S3) solver quad: scan vs fused vs resident vs lazy.

Acceptance criteria pinned here:
  * every solver path is bit-identical to "scan" in seeds, rows,
    covered, and gains — including the lowest-index argmax tie-break —
    across non-tile-aligned n / W and k > #useful-rows;
  * every solver path matches the NumPy lazy-greedy oracle's coverage;
  * solver="resident" and solver="lazy" each compile the whole greedy
    solve to exactly ONE pallas_call (jaxpr assertion), "scan" to zero;
  * solver="lazy" actually skips tiles (tiles_swept < k * num_tiles)
    on a skewed gain distribution while staying bit-exact.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import bitset, maxcover

SOLVERS = ("scan", "fused", "resident", "lazy")

# Non-tile-aligned vertex/word counts on purpose (the kernels pad to
# 8-sublane x 128-lane tiles internally).
PARITY_SHAPES = [(37, 3, 5), (100, 7, 8), (8, 128, 4), (130, 5, 17),
                 (1, 1, 3), (257, 12, 16)]


def _random_rows(n, w, seed, density_mask=True):
    rng = np.random.default_rng(seed)
    rows = rng.integers(0, 2**32, (n, w), dtype=np.uint32)
    if density_mask:  # AND two draws: ~25% bit density, gain ties likely
        rows &= rng.integers(0, 2**32, (n, w), dtype=np.uint32)
    return jnp.asarray(rows)


@pytest.mark.parametrize("n,w,k", PARITY_SHAPES)
@pytest.mark.parametrize("solver", SOLVERS[1:])
def test_solver_parity_bit_identical(n, w, k, solver):
    rows = _random_rows(n, w, seed=n * 31 + w * 7 + k)
    want = maxcover.greedy_maxcover(rows, k, solver="scan")
    got = maxcover.greedy_maxcover(rows, k, solver=solver)
    for field in ("seeds", "rows", "covered", "gains", "coverage"):
        np.testing.assert_array_equal(
            np.asarray(getattr(got, field)),
            np.asarray(getattr(want, field)),
            err_msg=f"solver={solver} field={field} n={n} w={w} k={k}")


@pytest.mark.parametrize("n,w,k", PARITY_SHAPES)
def test_all_solvers_match_lazy_oracle_coverage(n, w, k):
    rows = _random_rows(n, w, seed=n + w + k)
    _, lazy_cov = maxcover.lazy_greedy_maxcover_np(np.asarray(rows), k)
    for solver in SOLVERS:
        sol = maxcover.greedy_maxcover(rows, k, solver=solver)
        assert int(sol.coverage) == lazy_cov, (solver, n, w, k)


@pytest.mark.parametrize("solver", SOLVERS)
def test_tie_break_lowest_index(solver):
    """Equal-gain candidates: every path must pick the LOWEST index
    (the jnp.argmax convention), each pick."""
    w = 5
    base = np.zeros((9, w), dtype=np.uint32)
    base[0] = base[4] = base[7] = [0xF, 0, 0, 0, 0]   # three-way tie
    base[1] = base[6] = [0, 0xF0, 0, 0, 0]            # two-way tie
    base[2] = [0, 0, 0x3, 0, 0]                       # smaller, unique
    rows = jnp.asarray(base)
    sol = maxcover.greedy_maxcover(rows, 3, solver=solver)
    # pick 1: tie between 0/4/7 -> 0; pick 2: tie between 1/6 -> 1;
    # pick 3: unique row 2.
    np.testing.assert_array_equal(np.asarray(sol.seeds), [0, 1, 2])
    np.testing.assert_array_equal(np.asarray(sol.gains), [4, 4, 2])


@pytest.mark.parametrize("solver", SOLVERS)
def test_duplicate_row_not_repicked(solver):
    """A picked row's duplicate has gain 0 afterwards; with no other
    positive gain left the remaining picks must be -1, and the picked
    row itself must never be selected twice."""
    w = 2
    rows = jnp.asarray(np.array([[0xFF, 0], [0xFF, 0], [0xFF, 0]],
                                dtype=np.uint32))
    sol = maxcover.greedy_maxcover(rows, 3, solver=solver)
    np.testing.assert_array_equal(np.asarray(sol.seeds), [0, -1, -1])
    np.testing.assert_array_equal(np.asarray(sol.gains), [8, 0, 0])
    assert int(sol.coverage) == 8


@pytest.mark.parametrize("solver", SOLVERS)
def test_exhausted_gain_early_stop(solver):
    """k > #useful-rows: once every nonzero row is taken (or fully
    covered), the remaining seeds are -1 with gain 0 and the covered
    mask stops changing — identical across paths."""
    rng = np.random.default_rng(3)
    dense = rng.random((6, 40)) < 0.4
    dense[4] = dense[0]          # duplicate -> at most 5 useful picks
    dense[5] = False             # empty row -> never picked
    rows = bitset.pack_bool_matrix(jnp.asarray(dense))
    k = 10
    want = maxcover.greedy_maxcover(rows, k, solver="scan")
    got = maxcover.greedy_maxcover(rows, k, solver=solver)
    np.testing.assert_array_equal(np.asarray(got.seeds),
                                  np.asarray(want.seeds))
    np.testing.assert_array_equal(np.asarray(got.gains),
                                  np.asarray(want.gains))
    tail = np.asarray(got.seeds)[np.asarray(got.gains) == 0]
    assert np.all(tail == -1)
    _, lazy_cov = maxcover.lazy_greedy_maxcover_np(np.asarray(rows), k)
    assert int(got.coverage) == lazy_cov


@pytest.mark.parametrize("solver", ("resident", "lazy"))
def test_resident_single_pallas_call_jaxpr(solver):
    """Acceptance criterion: solver="resident" and solver="lazy" each
    compile the whole S3 greedy solve to exactly ONE pallas_call
    equation (structurally walked, not string-grepped); "scan" to
    zero.  The full contract (VMEM footprint, dtypes, aliasing) lives
    in repro.analysis.contracts."""
    from repro.analysis import jaxpr_check

    rows = _random_rows(64, 4, seed=0)
    jx = jax.make_jaxpr(
        lambda r: maxcover.greedy_maxcover(r, 8, solver=solver))(rows)
    (site,) = jaxpr_check.launch_sites(jx)
    assert not site.in_loop     # all k picks inside ONE launch
    jx_scan = jax.make_jaxpr(
        lambda r: maxcover.greedy_maxcover(r, 8, solver="scan"))(rows)
    assert jaxpr_check.count_pallas_calls(jx_scan) == 0


def test_lazy_skips_tiles_on_skewed_gains():
    """The lazy kernel's stale bounds must actually pay off: on a
    power-law gain profile (a few heavy rows, a long light tail) the
    tiles-swept counter stays well below the resident kernel's
    k * num_tiles full re-read, while seeds/gains match "scan"
    bit-for-bit.  On this multi-tile input at least pick 1's full pass
    plus one tile per later pick is unavoidable, so the bound below is
    the loosest meaningful one."""
    from repro.kernels import lazy_greedy, ops

    rng = np.random.default_rng(11)
    n, w, k = 512, 8, 6
    density = 0.6 * (np.arange(n) + 1.0) ** -0.8
    dense = rng.random((n, w * 32)) < density[:, None]
    rows = bitset.pack_bool_matrix(jnp.asarray(dense))

    want = maxcover.greedy_maxcover(rows, k, solver="scan")
    # block_v pinned: the skip claim needs a multi-tile launch, and
    # block_v=None would consult the tuned table (which may legally
    # prefer a tile size that makes this input single-tile).
    seeds, sel_rows, covered, gains, swept = \
        lazy_greedy.greedy_maxcover_lazy_pallas(
            rows, k, block_v=128, interpret=True)
    np.testing.assert_array_equal(np.asarray(seeds),
                                  np.asarray(want.seeds))
    np.testing.assert_array_equal(np.asarray(gains),
                                  np.asarray(want.gains))
    np.testing.assert_array_equal(np.asarray(covered),
                                  np.asarray(want.covered))
    num_tiles = lazy_greedy.num_row_tiles(n, block_v=128)
    assert num_tiles >= 4          # the skew claim needs >1 tile
    assert int(swept) >= num_tiles  # pick 1 always sweeps everything
    assert int(swept) < k * num_tiles, (int(swept), k * num_tiles)


def test_lazy_swept_counter_exact_on_uniform_single_tile():
    """One-tile inputs degenerate to the resident kernel: every pick
    sweeps the single tile, so tiles_swept == k exactly."""
    from repro.kernels import lazy_greedy, ops

    rows = _random_rows(64, 4, seed=7)
    assert lazy_greedy.num_row_tiles(64) == 1
    *_, swept = ops.greedy_maxcover_lazy(rows, 5)
    assert int(swept) == 5


@pytest.mark.parametrize("solver", SOLVERS)
def test_cover_solution_carries_tiles_swept(solver):
    """``CoverSolution.tiles_swept`` is the lazy kernel's own count of
    the tiles it swept, and the full sweep k * tiles on every other
    solver; one per query in a batch."""
    from repro.kernels import lazy_greedy, ops

    rng = np.random.default_rng(5)
    n, w, k = 1000, 4, 6
    density = 0.6 * (np.arange(n) + 1.0) ** -0.8
    rows = bitset.pack_bool_matrix(
        jnp.asarray(rng.random((n, w * 32)) < density[:, None]))
    want = (int(ops.greedy_maxcover_lazy(rows, k)[4]) if solver == "lazy"
            else k * lazy_greedy.num_row_tiles(n))
    assert maxcover.full_sweep_tiles(n, k) == k * lazy_greedy.num_row_tiles(n)
    assert int(maxcover.greedy_maxcover(rows, k, solver=solver)
               .tiles_swept) == want
    batch = maxcover.greedy_maxcover_batch(
        rows, jnp.full((2, 1), -1, jnp.int32), k, solver=solver)
    assert np.asarray(batch.tiles_swept).tolist() == [want, want]


def test_use_kernel_alias_deprecated():
    """use_kernel still works (True -> fused, False -> scan) but warns."""
    rows = _random_rows(32, 2, seed=1)
    with pytest.warns(DeprecationWarning):
        a = maxcover.greedy_maxcover(rows, 4, use_kernel=True)
    b = maxcover.greedy_maxcover(rows, 4, solver="fused")
    np.testing.assert_array_equal(np.asarray(a.seeds), np.asarray(b.seeds))
    with pytest.raises(ValueError):
        maxcover.greedy_maxcover(rows, 4, solver="heap")


def test_vmapped_solver_parity():
    """randgreedi vmaps the local solve over machines; all solver
    paths must survive vmap bit-identically."""
    rng = np.random.default_rng(5)
    rows = jnp.asarray(rng.integers(0, 2**32, (3, 48, 5), dtype=np.uint32)
                       & rng.integers(0, 2**32, (3, 48, 5),
                                      dtype=np.uint32))
    want = jax.vmap(
        lambda r: maxcover.greedy_maxcover(r, 6, solver="scan"))(rows)
    for solver in SOLVERS[1:]:
        got = jax.vmap(
            lambda r, s=solver: maxcover.greedy_maxcover(
                r, 6, solver=s))(rows)
        np.testing.assert_array_equal(np.asarray(got.seeds),
                                      np.asarray(want.seeds), solver)
        np.testing.assert_array_equal(np.asarray(got.gains),
                                      np.asarray(want.gains), solver)
