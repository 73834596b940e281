"""The command as the driver runs it: without a TPU it exits non-zero and
prints no result line."""
import subprocess
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]


def test_run_refuses_without_a_tpu():
    p = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "glm3l-m23-closed",
         "--seed", str(2**33 + 1), "--seconds", "1", "--trace", "0"],
        cwd=ROOT, capture_output=True, text=True, timeout=300,
        env={"JAX_PLATFORMS": "cpu", "PATH": "/usr/bin:/bin",
             "HOME": str(ROOT / ".bench_runs")})
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "needs 1 TPU chip" in p.stderr
