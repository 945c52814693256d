"""The command measures nothing where it cannot: no TPU, or no program."""
import os
import shutil
import subprocess
import sys

from bench import harness

CMD = [sys.executable, "bench/run.py", "--workload", "taobao.zipf.sat",
       "--seed", str(2**31 + 5), "--seconds", "1", "--trace", "0"]


def _run(cwd):
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    env.pop("PYTHONPATH", None)
    return subprocess.run(CMD, cwd=cwd, env=env, capture_output=True, text=True, timeout=120)


def test_refuses_a_cpu_backend():
    r = _run(harness.ROOT)
    assert r.returncode == 2, r.stderr[-2000:]
    assert r.stdout.strip() == ""
    assert "no accelerator" in r.stderr


def test_fails_without_the_program(tmp_path):
    shutil.copy(harness.ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(harness.BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    r = _run(tmp_path)
    assert r.returncode != 0
    assert r.stdout.strip() == ""
