"""``chip_smoke.py`` rehearsed on the CPU at a tiny size: its phases
take the right paths and arguments, and ``main`` refuses to run
anywhere but on a TPU.  Kernels run interpreted here, so the
TPU-only check of phase 3 (compiled kernels, ``tpu_custom_call``) is
replaced by a recorder of the traced launches."""
import numpy as np
import pytest

import chip_smoke as cs
from repro.kernels import ops
from tests.conftest import REPO, run_with_devices

TINY = dict(n_log2=9, k=8, sample_chunks=2)


@pytest.mark.parametrize("argv", [[], ["--four-chips"]])
def test_main_refuses_without_tpu(capsys, argv):
    assert cs.main(argv) != 0
    out, err = capsys.readouterr()
    assert "no TPU" in err
    assert out == ""


def test_build_graph_keeps_every_edge():
    g, (nbr, prob, wt), (fwd_nbr, _) = cs.build_graph(512, 8, seed=0)
    assert nbr.shape[1] == g.max_in_degree()
    assert int((np.asarray(nbr) >= 0).sum()) == g.num_edges
    assert int((np.asarray(fwd_nbr) >= 0).sum()) == g.num_edges


@pytest.mark.parametrize("launches,needle", [
    ([("lazy_greedy", True), ("bucket_insert_stream", False)],
     "interpreted"),
    ([("bucket_insert_stream", False)], "no sender"),
    ([("greedy_pick_resident", False)], "no receiver"),
])
def test_check_launches_rejects(launches, needle):
    with pytest.raises(AssertionError, match=needle):
        cs.check_launches(launches)


def test_check_kernel_round_needs_tpu_custom_call():
    with pytest.raises(AssertionError, match="tpu_custom_call"):
        cs.check_kernel_round("lazy", None, (), "HloModule m")


def test_check_identical_names_the_field():
    ref = {f: np.array([1, 2]) for f in cs.OUT_FIELDS}
    got = dict(ref, coverage=np.array([1, 3]))
    with pytest.raises(AssertionError, match="coverage"):
        cs.check_identical("lazy", got, ref)


def test_kernel_round_traces_compiled_launches(monkeypatch):
    """With interpret off (as on a TPU) the lazy round's trace holds the
    sender and receiver launches, none interpreted."""
    monkeypatch.setattr(ops, "_interpret", lambda: False)
    mesh = cs.make_im_mesh(1)
    g, arrays, fwd = cs.build_graph(256, 8, seed=0)
    fn, _ = cs.build(mesh, g, fwd, theta=512, k=4, sample_chunks=1,
                     aggregate="gather", variant="lazy")
    launches = cs.kernel_launches(fn, cs.place(mesh, arrays,
                                               cs.jax.random.key(0)))
    cs.check_launches(launches)
    assert {n for n, _ in launches} == {"lazy_greedy",
                                        "bucket_insert_stream"}


def test_one_chip_phases_tiny(monkeypatch, capsys):
    seen = []

    def record(name, fn, args, hlo):
        seen.append(name)
        return cs.kernel_launches(fn, args)

    monkeypatch.setattr(cs, "check_kernel_round", record)
    out = cs.one_chip(theta=1024, sims=64, slab=256, **TINY)
    assert seen == ["lazy", "resident"]
    for variant in ("lazy", "resident"):
        for f in cs.OUT_FIELDS:
            np.testing.assert_array_equal(out[variant][f], out["scan"][f])
    log = capsys.readouterr().out
    assert "every edge kept" in log
    assert "bit-identical to sequential answer_one" in log
    assert "peak_bytes_in_use" in log


def test_four_chips_rehearsal_on_fake_devices():
    out = run_with_devices(f"""
import sys
sys.path.insert(0, {REPO!r})
import chip_smoke as cs
cs.check_kernel_round = lambda name, fn, args, hlo: cs.kernel_launches(
    fn, args)
res = cs.four_chips(n_log2=9, theta_per_chip=512, k=8, sample_chunks=2)
assert len(res) == 4
print("four OK")
""", num_devices=4)
    assert "outputs span 4 devices" in out
    assert "four OK" in out
