"""Where the benchmark lives, for the tests of this directory (the repo
root goes on ``sys.path`` so that ``benchmark`` imports as a package)."""

import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

BENCH = os.path.join(ROOT, "benchmark")


def manifest_data():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)
