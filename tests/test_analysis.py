"""The contract checker's own coverage (repro.analysis).

Acceptance criteria pinned here:
  * every violation fixture in tests/bad_kernels.py is caught by
    EXACTLY the intended rule — no more, no less;
  * the real contract registry passes clean and covers all six kernel
    families;
  * the structural walker gets the loop accounting right (scan-length
    multipliers, while = dynamic) and rejects pre-stringified jaxprs;
  * the repo-wide AST lint is clean;
  * hlo_analysis._shape_bytes raises on unknown dtypes instead of
    silently guessing 4 bytes;
  * the CLI (python -m repro.analysis.check) works end to end and
    writes the JSON report CI uploads.
"""
import inspect
import json
import textwrap

import jax
import jax.numpy as jnp
import pytest

import bad_kernels
from conftest import REPO
from repro.analysis import ast_rules, check, contracts, jaxpr_check
from repro.analysis.contracts import KernelContract, ShapePattern


def _fixture_contract(fn, **overrides):
    defaults = dict(
        name="fixture", family="fixture", description="",
        build=lambda: (fn, (bad_kernels.fixture_arg(),)),
        expected_launches=1, check_hlo=False)
    defaults.update(overrides)
    return KernelContract(**defaults)


def _rules(contract):
    report = contracts.run_contract(contract, skip_hlo=True)
    return [v.rule for v in report.violations]


# ------------------------------------------------ contract-rule corpus
def test_extra_launch_caught_by_launch_count_only():
    c = _fixture_contract(bad_kernels.double_launch)
    assert _rules(c) == ["launch-count"]


def test_loop_hidden_launch_caught_by_launch_context_only():
    c = _fixture_contract(bad_kernels.loop_launch, expect_in_loop=False)
    assert _rules(c) == ["launch-context"]


def test_f64_leak_caught_by_dtype_whitelist_only():
    c = _fixture_contract(bad_kernels.f64_leak,
                          dtype_whitelist=frozenset({"float32"}))
    with jax.enable_x64(True):
        report = contracts.run_contract(c, skip_hlo=True)
    (violation,) = report.violations
    assert violation.rule == "dtype-whitelist"
    assert "float64" in violation.message


def test_gmask_shaped_intermediate_caught_by_forbidden_rule_only():
    c = _fixture_contract(
        bad_kernels.gmask_intermediate, expected_launches=0,
        forbidden=(ShapePattern("uint32", (4, 7, 2), "gmask"),))
    assert _rules(c) == ["forbidden-intermediate"]


def test_required_intermediate_missing_caught():
    """The forbidden pattern's twin: a contract requiring a shape the
    trace never materializes (keeps forbidden checks non-vacuous)."""
    c = _fixture_contract(
        bad_kernels._identity,
        required=(ShapePattern("uint32", (4, 7, 2)),))
    assert _rules(c) == ["missing-intermediate"]


def test_hardcoded_interpret_false_caught():
    assert jax.default_backend() != "tpu"   # the premise of the rule
    c = _fixture_contract(bad_kernels.uninterpreted_launch)
    assert _rules(c) == ["interpret-flag"]


def test_unexpected_aliasing_caught():
    c = _fixture_contract(bad_kernels.aliased_launch)
    assert _rules(c) == ["aliasing"]


def test_vmem_budget_overflow_caught():
    # identity on [8, 128] f32 holds 8 KiB of VMEM refs; a 1 KiB
    # budget must trip the footprint rule (and nothing else)
    c = _fixture_contract(bad_kernels._identity, max_vmem_bytes=1024)
    assert _rules(c) == ["vmem-footprint"]


def test_grid_mismatch_caught():
    c = _fixture_contract(bad_kernels._identity, expected_grid=(2,))
    assert _rules(c) == ["launch-grid"]


def test_clean_fixture_passes():
    c = _fixture_contract(bad_kernels._identity)
    assert _rules(c) == []


# --------------------------------------------------- structural walker
def test_scan_launch_iteration_accounting():
    def f(x):
        return jax.lax.scan(
            lambda c, _: (bad_kernels._identity(c), None), x, None,
            length=3)[0]

    (site,) = jaxpr_check.launch_sites(
        jax.make_jaxpr(f)(bad_kernels.fixture_arg()))
    assert site.in_loop
    assert site.iterations == 3     # scan length multiplies


def test_while_launch_dynamic_trip_count():
    def f(x):
        return jax.lax.while_loop(
            lambda v: v[0, 0] < 10.0,
            lambda v: bad_kernels._identity(v) + 1.0, x)

    (site,) = jaxpr_check.launch_sites(
        jax.make_jaxpr(f)(bad_kernels.fixture_arg()))
    assert site.in_loop
    assert site.iterations is None  # while trip count is dynamic


def test_stringified_jaxpr_rejected():
    jx = jax.make_jaxpr(lambda x: x + 1)(1.0)
    with pytest.raises(TypeError, match="never accepts"):
        jaxpr_check.count_pallas_calls(str(jx))


# --------------------------------------------------------- AST corpus
def _lint_fn(fn):
    src = textwrap.dedent(inspect.getsource(fn))
    return [v.rule for v in ast_rules.lint_source(src, "fixture.py")]


def test_traced_if_in_kernel_body_caught():
    assert _lint_fn(bad_kernels.bad_traced_if_kernel) == ["traced-if"]


def test_host_numpy_in_jit_caught():
    assert _lint_fn(bad_kernels.bad_host_call) == ["host-call-in-jit"]
    assert _lint_fn(bad_kernels.bad_host_call_partial) == [
        "host-call-in-jit"]


def test_unpadded_blockspec_caught():
    assert _lint_fn(bad_kernels.bad_blockspec_factory) == [
        "blockspec-pad"]


def test_missing_interpret_caught():
    assert _lint_fn(bad_kernels.bad_missing_interpret) == [
        "missing-interpret"]


def test_clean_kernel_wrapper_passes_lint():
    assert _lint_fn(bad_kernels._identity) == []
    assert _lint_fn(bad_kernels._copy_kernel) == []


def test_repo_wide_ast_lint_clean():
    assert ast_rules.lint_paths(repo_root=REPO) == []


# ------------------------------------------------------- real registry
def test_registry_clean_pass_and_family_coverage():
    reports = [contracts.run_contract(c, skip_hlo=True)
               for c in contracts.build_registry()]
    failures = [(r.name, r.violations) for r in reports if not r.ok]
    assert not failures, failures
    assert {r.family for r in reports} == set(contracts.FAMILIES)


@pytest.mark.parametrize("batch", [1, 4, 8])
def test_service_batched_contract_one_gridless_launch(batch):
    """The service's batched solve is one launch outside any loop with
    no grid, whatever B: the batch is swept inside the kernel, not run
    as a grid that streams the pool once per query."""
    c = contracts.contracts_by_name()["service.batched"]
    assert c.expected_grid == () and c.expected_launches == 1
    fn, args = contracts._build_maxcover_batch(batch)()
    (site,) = jaxpr_check.launch_sites(jax.make_jaxpr(fn)(*args))
    assert site.grid == () and not site.in_loop
    assert site.name == "greedy_pick_resident"


def test_one_contract_through_hlo_pass():
    """One registry entry end to end with the compile-based HLO pass
    (the CI job runs all of them; keeping one in tier-1 pins the
    hlo_analysis integration)."""
    c = contracts.contracts_by_name()["bucket_insert.chunk"]
    report = contracts.run_contract(c)
    assert report.ok, report.violations
    assert report.stats["hlo_collectives"] == 0


# --------------------------------------------------------- _shape_bytes
def test_shape_bytes_unknown_dtype_raises():
    from repro.distributed import hlo_analysis
    with pytest.raises(ValueError, match="unknown HLO dtype"):
        hlo_analysis._shape_bytes("q7", "8,8")
    assert hlo_analysis._shape_bytes("f32", "8,8") == 256
    assert hlo_analysis._shape_bytes("bf16", "4") == 8


# ------------------------------------------------------------------ CLI
def test_cli_ast_json_report(tmp_path):
    path = tmp_path / "report.json"
    rc = check.main(["--ast", "--repo-root", REPO, "--json", str(path)])
    assert rc == 0
    payload = json.loads(path.read_text())
    assert payload["ok"] is True
    assert payload["ast"]["violations"] == []


def test_cli_single_contract(capsys):
    rc = check.main(["--contracts", "bucket_insert.chunk", "--skip-hlo"])
    assert rc == 0
    assert "bucket_insert.chunk" in capsys.readouterr().out


def test_cli_list(capsys):
    assert check.main(["--list"]) == 0
    out = capsys.readouterr().out
    for family in contracts.FAMILIES:
        assert family in out


def test_cli_unknown_contract_rejected():
    with pytest.raises(SystemExit, match="unknown contract"):
        check.main(["--contracts", "nope.nothing"])
