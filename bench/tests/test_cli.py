"""Without a TPU the benchmark exits non-zero and prints no result."""
import json
import os
import subprocess
import sys

from bench import spec


def test_no_chip_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "qwen3-0.6b.chat",
         "--seed", str(2**31 + 1), "--seconds", "1", "--trace", "0"],
        cwd=spec.ROOT, env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    for line in p.stdout.splitlines():
        assert "correct" not in json.loads(line)
    assert "no CPU fallback" in p.stderr
