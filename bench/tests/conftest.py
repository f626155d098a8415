import os
import sys
import tempfile
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
for p in (str(ROOT / "src"), str(ROOT)):
    if p not in sys.path:
        sys.path.insert(0, p)

# the tests' compilations stay out of the checkout's cache
os.environ.setdefault("JAX_COMPILATION_CACHE_DIR",
                      tempfile.mkdtemp(prefix="bench_tests_jax_"))


def pytest_sessionfinish(session, exitstatus):
    import shutil

    d = os.environ.get("JAX_COMPILATION_CACHE_DIR", "")
    if os.path.basename(d).startswith("bench_tests_jax_"):
        shutil.rmtree(d, ignore_errors=True)
