"""The benchmark refuses to run without a TPU: it exits non-zero and
prints no result line, both in a whole checkout and in a directory that
holds only BENCHMARK.json and the benchmark's own files."""
import json
import os
import shutil
import subprocess
import sys

import tiny

ARGS = ["--workload", "blstm-adpsgd-1chip", "--seed", "2147483653",
        "--seconds", "1", "--trace", "0"]


def _run(cwd):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    return subprocess.run([sys.executable, "bench/run.py", *ARGS], cwd=cwd,
                          env=env, capture_output=True, text=True,
                          timeout=240)


def _no_result(out: str) -> bool:
    for line in out.splitlines():
        try:
            if "correct" in json.loads(line):
                return False
        except (ValueError, TypeError):
            continue
    return True


def test_no_tpu_no_result():
    r = _run(tiny.REPO)
    assert r.returncode != 0
    assert _no_result(r.stdout)
    assert "no TPU" in r.stderr


def test_benchmark_files_alone_do_not_run(tmp_path):
    shutil.copy(tiny.REPO / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    shutil.copytree(tiny.BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    r = _run(tmp_path)
    assert r.returncode != 0
    assert _no_result(r.stdout)
