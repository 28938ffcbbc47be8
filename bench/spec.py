"""The benchmark's data: ``BENCHMARK.json`` and the files it names.

Everything that belongs to one configuration, traffic mix or per-layer
metric is a file of its own, found by name:

* ``bench/configs/<config>.json`` -- the configuration as it is run; its
  ``problem`` names the module under ``bench/problems/`` that generates it;
* ``bench/traffic/<traffic>.json`` -- the traffic mix;
* ``bench/metrics/<metric>.py`` -- a reader with ``read(ctx)`` that returns
  the metric's value, or None where it finds nothing to read;
* ``bench/peaks.json`` -- the chip's peaks, keyed by ``device_kind``.
"""
from __future__ import annotations

import importlib
import importlib.util
import json
from pathlib import Path

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent


def load_json(path):
    with open(path) as f:
        return json.load(f)


def benchmark(root=ROOT):
    return load_json(Path(root) / "BENCHMARK.json")


def workload(spec, name):
    for w in spec["workloads"]:
        if w["name"] == name:
            return w
    raise KeyError(f"no workload {name!r} in BENCHMARK.json "
                   f"(have: {', '.join(w['name'] for w in spec['workloads'])})")


def config(spec, name, root=ROOT):
    for c in spec["configs"]:
        if c["name"] == name:
            return load_json(Path(root) / c["file"])
    raise KeyError(f"no config {name!r} in BENCHMARK.json")


def traffic(name):
    return load_json(BENCH / "traffic" / f"{name}.json")


def peaks():
    return load_json(BENCH / "peaks.json")


def problem(name):
    return importlib.import_module(f"bench.problems.{name}")


def reader(metric):
    """The ``read`` function of ``bench/metrics/<metric>.py``."""
    path = BENCH / "metrics" / f"{metric}.py"
    mod_spec = importlib.util.spec_from_file_location(f"bench_metric_{metric}", path)
    mod = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(mod)
    return mod.read


def applies(metric, cell):
    """True when ``metric`` is reported in ``cell``."""
    return "workloads" not in metric or cell in metric["workloads"]
