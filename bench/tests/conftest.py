"""CPU tests of the benchmark harness: ``pytest bench/tests``.

They run the cell runners, the reference comparison and the result line at
a tiny size on the CPU backend; no number they produce is a device number.
"""
import json
import os
import shutil
import sys
from pathlib import Path

import pytest

os.environ.setdefault("JAX_PLATFORMS", "cpu")

BENCH = Path(__file__).resolve().parents[1]
REPO = BENCH.parent
for p in (str(BENCH), str(REPO / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

# tiny sizes: the published vocabulary and every width stay; the corpus,
# the waves and tau shrink
TINY_CONFIG = {"n_tokens": 16384, "wave_tokens": 2048, "tau": 2}


def make_root(dst: Path) -> Path:
    """A copy of the benchmark's data files and runners, at the tiny sizes,
    under ``dst`` (the harness itself is imported from the repository)."""
    (dst / "bench").mkdir(parents=True)
    for sub in ("configs", "traffic", "metrics", "runners"):
        shutil.copytree(BENCH / sub, dst / "bench" / sub,
                        ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(BENCH / "peaks.json", dst / "bench" / "peaks.json")
    shutil.copy(REPO / "BENCHMARK.json", dst / "BENCHMARK.json")
    for p in (dst / "bench" / "configs").glob("*.json"):
        p.write_text(json.dumps(dict(json.loads(p.read_text()),
                                     **TINY_CONFIG)))
    return dst


@pytest.fixture(scope="module")
def tiny_root(tmp_path_factory) -> Path:
    return make_root(tmp_path_factory.mktemp("bench_root"))
