"""The benchmark's own tests under the tier-1 gate.

``benchmarks/`` + ``PERF_LEDGER.jsonl`` + ``PERF.md`` are the repo's one
account of speed, and ``benchmarks/tests`` (tiny cells through the real
drivers, readers and references) is what tells a program PR that it
dropped a counter or a trace name a reader needs.  Tier-1 collects
``tests/`` only, so each file there is one case here, run by ``python -m
pytest`` in a child process: that directory's ``conftest.py`` wants four
virtual devices where this suite's process has eight, and a child gives
it a jax of its own.

The children are started from one module fixture, three alive at a time,
and a case waits for its own.  Under ``--dist loadfile`` this file is one
worker's, and one child after another made it the longest thing a worker
was handed (664 s of a 1,230 s run at PR 61); ONE child for the whole
directory saves the 27 imports and little else (the files share no
program: 306 s alone, 625 s beside five busy workers), so the file stayed
the longest.  Three at a time the file is a third of that, on cores six
workers leave idle.
"""

import concurrent.futures
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
_ALIVE = 3


def test_benchmark_tests_found():
    assert _FILES, "no benchmarks/tests/test_*.py: the glob or the tree moved"


def _run(path):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    # tests/conftest.py's settings are this suite's, not that one's
    env.pop("XLA_FLAGS", None)
    env.pop("DS_ACCELERATOR", None)
    return subprocess.run(
        [sys.executable, "-m", "pytest", path, "-q", "-p", "no:cacheprovider",
         "-p", "no:xdist", "-p", "no:randomly"],
        cwd=_REPO, env=env, capture_output=True, text=True, timeout=600)


@pytest.fixture(scope="module")
def children():
    """path -> the future of its child; a timeout is raised in the case that
    waits for it."""
    pool = concurrent.futures.ThreadPoolExecutor(_ALIVE)
    yield {path: pool.submit(_run, path) for path in _FILES}
    pool.shutdown(wait=False, cancel_futures=True)   # a run of a few cases


@pytest.mark.parametrize("path", _FILES,
                         ids=[os.path.basename(p) for p in _FILES])
def test_benchmarks_test_file(children, path):
    proc = children[path].result()
    tail = (proc.stdout + proc.stderr)[-4000:]
    assert proc.returncode == 0, tail
    passed = re.search(r"(\d+) passed", proc.stdout)
    assert passed and int(passed.group(1)) >= 1, tail
