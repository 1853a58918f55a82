"""Launcher smoke tests: the real CLIs end-to-end in subprocesses
(train, serve, and one dry-run pair with the 512-device env)."""
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ENV = {**os.environ, "PYTHONPATH": os.path.join(REPO, "src")}


@pytest.fixture(scope="module", autouse=True)
def _compile_cache_env(tmp_path_factory):
    """The CLIs keep their compile cache under pytest's tmp dir, not in
    the checkout."""
    ENV["JAX_COMPILATION_CACHE_DIR"] = str(tmp_path_factory.mktemp("jax_cache"))


def run(args, timeout=420):
    return subprocess.run([sys.executable, "-m"] + args, cwd=REPO, env=ENV,
                          capture_output=True, text=True, timeout=timeout)


def test_train_cli(tmp_path):
    r = run(["repro.launch.train", "--arch", "swb2000-blstm", "--reduced",
             "--learners", "2", "--strategy", "sd_psgd", "--steps", "12",
             "--log-every", "5", "--ckpt-dir", str(tmp_path / "ck"),
             "--ckpt-every", "10"])
    assert r.returncode == 0, r.stderr[-2000:]
    assert "done: 12 steps" in r.stdout
    assert any(d.startswith("step_") for d in os.listdir(tmp_path / "ck"))


def test_train_cli_whisper():
    """whisper-large-v3 trains through the same CLI, log-mel segments into
    its conv front end, at the CLI's reduced sizes."""
    r = run(["repro.launch.train", "--arch", "whisper-large-v3", "--reduced",
             "--learners", "2", "--strategy", "ad_psgd", "--steps", "3",
             "--log-every", "1"])
    assert r.returncode == 0, r.stderr[-2000:]
    assert "done: 3 steps in" in r.stdout and "[ad_psgd, L=2]" in r.stdout
    assert "final loss nan" not in r.stdout


def test_serve_cli():
    r = run(["repro.launch.serve", "--arch", "smollm-360m", "--requests",
             "2", "--slots", "1", "--max-new", "4", "--prompt-len", "8",
             "--max-len", "32"])
    assert r.returncode == 0, r.stderr[-2000:]
    assert "served 2 requests" in r.stdout


@pytest.mark.slow
def test_dryrun_cli_one_pair(tmp_path):
    """One real multi-pod dry-run in a fresh process (512 host devices)."""
    r = run(["repro.launch.dryrun", "--arch", "smollm-360m", "--shape",
             "decode_32k", "--multipod", "--out-dir", str(tmp_path)],
            timeout=560)
    assert r.returncode == 0, r.stderr[-2000:]
    assert "all dry-runs passed" in r.stdout
    import json
    rec = json.load(open(
        tmp_path / "smollm-360m__decode_32k__multipod_2x16x16.json"))
    assert rec["status"] == "ok"
    assert rec["chips"] == 512
    assert rec["roofline"]["bound_s"] > 0
