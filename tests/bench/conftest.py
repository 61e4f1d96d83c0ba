import json
import os
import sys

import pytest

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", ".."))
for p in (ROOT, os.path.join(ROOT, "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

DATA = os.path.join(os.path.dirname(__file__), "data")
#: architecture -> the bench directory whose ``arch/`` holds its module,
#: and its tiny configuration under ``data/``: the benchmark's Qwen2, and
#: a test-only Qwen2 without q/k/v biases
ARCHS = {"qwen2": (os.path.join(ROOT, "bench"), "tiny"),
         "qwen2_nobias": (DATA, "tiny-nobias")}


@pytest.fixture(params=sorted(ARCHS))
def arch_case(request):
    """(architecture module, its tiny configuration), for each of
    ``ARCHS``."""
    from bench import run
    bench_dir, name = ARCHS[request.param]
    path = os.path.join(DATA, name + ".json")
    with open(path) as f:
        c = json.load(f)
    return run.load_arch(c, path, bench_dir), c
