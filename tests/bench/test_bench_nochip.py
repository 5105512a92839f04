"""Without a TPU the benchmark prints no result and exits non-zero."""
import os
import subprocess
import sys
from pathlib import Path

REPO = Path(__file__).resolve().parents[2]


def test_cpu_run_exits_nonzero_without_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, str(REPO / "perfbench" / "run.py"), "--workload",
         "products-sampled", "--seed", "1", "--seconds", "1"],
        capture_output=True, text=True, env=env, timeout=120, cwd=REPO)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "no TPU" in p.stderr
