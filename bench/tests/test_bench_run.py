"""``bench/run.py`` refuses to run off the chip, and without the program."""
import os
import shutil
import subprocess
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
ARGS = ["--workload", "ingest-distinct", "--seed", "3", "--seconds", "1",
        "--trace", "0"]


def run(cwd: Path, script: Path):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    return subprocess.run([sys.executable, str(script), *ARGS], cwd=cwd,
                          env=env, capture_output=True, text=True,
                          timeout=120)


def test_fails_off_tpu_naming_the_platform():
    res = run(ROOT, ROOT / "bench" / "run.py")
    assert res.returncode != 0
    assert "'cpu'" in res.stderr and "TPU" in res.stderr
    assert res.stdout.strip() == ""


def test_fails_with_only_the_benchmark_files(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    res = run(tmp_path, tmp_path / "bench" / "run.py")
    assert res.returncode != 0
    assert res.stdout.strip() == ""
