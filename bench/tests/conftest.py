"""The benchmark's own tests run on the CPU at the program's SMOKE sizes:

    JAX_PLATFORMS=cpu python -m pytest -q bench/tests

They are outside the repository's ``testpaths``, so the tier-1 run does not
collect them."""
import json
import os
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
os.environ.setdefault("JAX_PLATFORMS", "cpu")
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

# the program's SMOKE sizes (configs/lofar_cs302.py SMOKE,
# configs/mri_brain.py WAVELET_SMOKE), with small pools
SMOKE = {
    "lofar-cs302-int8.live-b8": {"resolution": 32, "n_sources": 8, "n_iters": 20,
                                 "pool_chunks": 4, "check_chunks": 2},
    "mri-brain-haar256.exam-b8": {"resolution": 64, "n_sparse": 500, "n_iters": 25,
                                  "pool_chunks": 4, "check_chunks": 2},
}


@pytest.fixture(scope="session")
def bench_root(tmp_path_factory):
    """A checkout whose BENCHMARK.json also names every SMOKE cell, so that
    configurations built here but not yet proved on the chip are rehearsed
    through the same path."""
    root = tmp_path_factory.mktemp("checkout")
    (root / "bench").symlink_to(ROOT / "bench")
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    for cell in SMOKE:
        config, traffic = cell.split(".")
        if not any(c["name"] == config for c in bench["configs"]):
            bench["configs"].append({"name": config, "source": "-", "reduced": [],
                                     "file": f"bench/configs/{config}.json", "why": "-"})
        if not any(w["name"] == cell for w in bench["workloads"]):
            bench["workloads"].append({"name": cell, "config": config, "traffic": traffic,
                                       "chips": 1, "why": "-"})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return root
