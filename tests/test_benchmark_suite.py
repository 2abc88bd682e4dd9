"""The benchmark's own tests, held by tier-1.

`benchmark/tests` checks `BENCHMARK.json` against the files under
`benchmark/` it names and the trace reduction against a recorded chip
trace. The tier-1 command collects `tests/` only, so without this a PR
could add a cell or a metric whose files do not match the manifest and
no test the driver runs would say so.
"""

import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_benchmark_tests_pass():
    out = subprocess.run(
        [sys.executable, "-m", "pytest", "benchmark/tests", "-q",
         "-p", "no:cacheprovider", "-p", "no:xdist", "-p", "no:randomly"],
        cwd=REPO, capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stdout[-4000:] + out.stderr[-2000:]
