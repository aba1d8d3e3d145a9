"""The benchmark's own tests under the tier-1 gate.

``benchmarks/`` + ``PERF_LEDGER.jsonl`` + ``PERF.md`` are the repo's one
account of speed, and ``benchmarks/tests`` (tiny cells through the real
drivers, readers and references) is what tells a program PR that it
dropped a counter or a trace name a reader needs.  Tier-1 collects
``tests/`` only, so each file there runs here as one case, in a process
of its own: that directory has its own ``conftest.py`` (four virtual
devices, not this suite's eight) and must see a fresh jax.
"""

import glob
import os
import re
import subprocess
import sys

import pytest

_REPO = os.path.abspath(os.path.join(os.path.dirname(__file__),
                                     os.pardir, os.pardir))
_FILES = sorted(glob.glob(os.path.join(_REPO, "benchmarks", "tests",
                                       "test_*.py")))


def test_benchmark_tests_found():
    assert _FILES, "no benchmarks/tests/test_*.py: the glob or the tree moved"


@pytest.mark.parametrize("path", _FILES,
                         ids=[os.path.basename(p) for p in _FILES])
def test_benchmarks_test_file(path):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    # tests/conftest.py's settings are this suite's, not that one's
    env.pop("XLA_FLAGS", None)
    env.pop("DS_ACCELERATOR", None)
    proc = subprocess.run(
        [sys.executable, "-m", "pytest", path, "-q", "-p", "no:cacheprovider",
         "-p", "no:xdist", "-p", "no:randomly"],
        cwd=_REPO, env=env, capture_output=True, text=True, timeout=300)
    tail = (proc.stdout + proc.stderr)[-4000:]
    assert proc.returncode == 0, tail
    passed = re.search(r"(\d+) passed", proc.stdout)
    assert passed and int(passed.group(1)) >= 1, tail
