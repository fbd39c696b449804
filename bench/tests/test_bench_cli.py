"""The command refuses to run without a TPU, and without the program."""
import shutil
import subprocess
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
ARGS = ["--workload", "lr.hourly_score_4096", "--seed", "3", "--seconds",
        "1", "--trace", "0"]


def _run(cwd):
    env = {"PATH": "/usr/bin:/bin", "JAX_PLATFORMS": "cpu",
           "BENCH_RUN": "1"}
    return subprocess.run([sys.executable, "bench/run.py", *ARGS], cwd=cwd,
                          env=env, capture_output=True, text=True,
                          timeout=120)


def test_no_tpu_exits_2_and_prints_no_result():
    p = _run(ROOT)
    assert p.returncode == 2, p.stderr
    assert p.stdout == ""
    assert "no TPU" in p.stderr


def test_benchmark_files_alone_do_not_run(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = _run(tmp_path)
    assert p.returncode != 0
    assert p.stdout == ""
