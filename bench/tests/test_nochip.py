"""Without a TPU the command exits non-zero and prints no result."""

import os
import subprocess
import sys

from bench.cell import BENCH


def test_no_tpu_exits_nonzero_with_no_metrics():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, "bench/run.py", "--workload",
         "qwen3-4b.lmsys-chat", "--seed", str(2 ** 31 + 7), "--seconds",
         "1", "--trace", "0"],
        cwd=BENCH.parent, env=env, capture_output=True, text=True,
        timeout=300)
    assert p.returncode != 0
    assert "metrics" not in p.stdout and p.stdout.strip() == ""
    assert "no TPU" in p.stderr
