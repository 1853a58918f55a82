"""``correct`` on a tiny copy of the BLSTM cell, on the CPU: the program
passes, and the float8 control in its place fails the comparison, under
the tiny cell's limits and under every cell's committed limits."""
import pytest

import tiny
from harness import compare, reference, spec


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return tiny.make_root(tmp_path_factory.mktemp("tiny"))


def test_program_is_correct(root):
    r = tiny.run_cell(root)
    assert r["correct"], r["checks"]
    assert r["attempted"] >= 1 and r["failed"] == 0
    assert list(r)[-1] == "checks"
    assert set(r["metrics"]) == {"audio_s_per_s_per_chip", "step_ms_p95",
                                 "peak_hbm_gib", "setup_s"}
    assert r["device"]["count"] == 1


def test_traced_run_reports_per_layer_metrics(root):
    r = tiny.run_cell(root, traced=True)
    assert r["correct"], r["checks"]
    assert "input_wait_ms" in r["metrics"]
    assert "audio_s_per_s_per_chip" not in r["metrics"]
    assert set(r["breakdown"]) == {"device_ops", "idle_gaps"}
    assert {"busy_s", "window_s"} <= set(r["device"])


def test_float8_control_fails(root):
    import jax

    cell = spec.load_cell(root, "tiny-cell", bench=root / "bench")
    args = (cell, 7, cell.batches(7)[:3], jax.devices()[:1])
    want = reference.run(*args)
    got = reference.run(*args, variant="fp8")
    chk = compare.checks(compare.numbers(got, want), cell.limits)
    assert not compare.passed(chk), chk
    assert tiny.fails_everywhere(chk), chk
