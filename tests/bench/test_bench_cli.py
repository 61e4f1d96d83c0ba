"""The measurement path refuses to run without a chip: on the CPU
backend, and in a directory that holds only the benchmark's files, the
command prints no result and exits with another code than 0."""

import os
import shutil
import subprocess
import sys

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", ".."))
ARGS = ["--workload", "qwen2-1.5b.fit", "--seed", str(2**33 + 1),
        "--seconds", "1", "--trace", "0"]


def _run(cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    env.update(env_extra or {})
    return subprocess.run([sys.executable, "bench/run.py", *ARGS], cwd=cwd,
                          env=env, capture_output=True, text=True,
                          timeout=240)


def test_cpu_backend_fails_and_prints_no_result():
    p = _run(ROOT)
    assert p.returncode == 2, p.stderr[-2000:]
    assert p.stdout.strip() == ""
    assert "needs a TPU" in p.stderr


def test_a_checkout_of_the_benchmark_alone_fails(tmp_path):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(ROOT, "bench"), tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = _run(tmp_path)
    assert p.returncode != 0
    assert p.stdout.strip() == ""


def test_an_unknown_device_kind_is_an_error():
    sys.path.insert(0, ROOT)
    from bench import run
    try:
        run.load_peaks("TPU v0 imaginary")
    except run.NoChip as e:
        assert "peaks.json" in str(e)
    else:
        raise AssertionError("an unknown device kind was accepted")
    assert run.load_peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
