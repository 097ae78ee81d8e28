"""CPU rehearsal of ``chip_smoke.py``: its phases at tiny sizes with the
Pallas kernels in interpret mode, and its refusal to run off a TPU."""
import importlib.util
import json
import os

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", os.path.join(REPO, "chip_smoke.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_main_refuses_to_run_off_the_chip(smoke, capsys):
    assert smoke.main([]) != 0
    out, err = capsys.readouterr()
    assert "platform 'cpu'" in err
    assert '"ok"' not in out


@pytest.mark.parametrize("d", [28, 768])
def test_kernel_phase_interpret(smoke, d):
    rec = smoke.kernel_phase(s=256, k=25, d=d, impl="interpret")
    json.dumps(rec)  # printable as one line
    assert rec["label_agreement_up_to_ties"] >= smoke.KERNEL_AGREEMENT
    assert rec["dist_rel_err"] <= smoke.KERNEL_RTOL
    assert rec["sums_rel_err"] <= smoke.KERNEL_RTOL
    assert rec["counts_equal"]
    assert rec["kernel_dist_vs_f64"] <= smoke.KERNEL_RTOL


def test_stream_and_reference_phases_interpret(smoke):
    rec, arrays = smoke.stream_phase(
        d=16, k=4, workers=2, sample=128, rounds=2, window=2048, windows=3,
        holdout=3000, impl="interpret")
    json.dumps(rec)
    assert rec["windows"] == 3 and rec["rounds_total"] == 6
    assert rec["monotone"]
    # Interpret mode lowers no TPU kernel: main() refuses such a program.
    assert rec["round_program_tpu_custom_calls"] == 0
    assert rec["holdout_rel_gap"] <= smoke.OBJECTIVE_RTOL
    assert arrays["first_window"].shape == (2048, 16)
    ref = smoke.reference_phase(x=arrays["first_window"],
                                holdout=arrays["holdout"], k=4)
    json.dumps(ref)
    assert np.isfinite(ref["holdout_objective_host_f64"])


def test_sharded_phase_interpret(smoke):
    rec = smoke.sharded_phase(d=16, k=4, sample=128, rounds=2, window=2048,
                              windows=3, holdout=3000, impl="interpret")
    json.dumps(rec)
    assert rec["windows"] == 3 and rec["monotone"]
    assert rec["reservoir_devices"] == [0]
    assert rec["holdout_rel_gap"] <= smoke.OBJECTIVE_RTOL


def test_host_objective_matches_direct_sum(smoke):
    rng = np.random.default_rng(0)
    x = rng.normal(size=(1000, 7)).astype(np.float32)
    c = rng.normal(size=(3, 7)).astype(np.float32)
    direct = ((x[:, None, :].astype(np.float64) - c[None]) ** 2).sum(-1)
    np.testing.assert_allclose(smoke.host_objective(x, c, batch=128),
                               direct.min(axis=1).sum(), rtol=1e-12)
