"""``mri-brain-haar256.exam-b8`` as ``BENCHMARK.json`` holds it: the cell, its
configuration and the per-layer metrics that list it, read in a traced CPU
rehearsal from a device plane that carries the program's MRI scopes,
``yquant`` (the quantization of y, one scale c_y a slice) among them."""
import json
import os
import re
import time
from pathlib import Path

import pytest

from bench import harness, spec, xplane
from bench.tests.conftest import ROOT, SMOKE
from bench.tests.test_scopes import BODY, _hlo, xspace

CELL = "mri-brain-haar256.exam-b8"
MRI_METRICS = ("fft_share", "sample_share", "dwt_share", "yquant_share")
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


def test_the_cell_and_its_metrics_are_in_the_benchmark():
    bench = spec.benchmark(ROOT)
    wl = spec.workload(bench, CELL)
    assert (wl["config"], wl["traffic"], wl["chips"]) == ("mri-brain-haar256", "exam-b8", 1)
    run = spec.config(bench, "mri-brain-haar256")
    assert run["problem"] == "mri" and run["bits_y"] == 8
    assert run["n_bands"] == 1          # the paper's single scale c_y per slice
    cfg = next(c for c in bench["configs"] if c["name"] == "mri-brain-haar256")
    assert cfg["reduced"] == [] == run["reduced"]
    assert cfg["file"] == "bench/configs/mri-brain-haar256-single-scale.json"
    by_name = {m["name"]: m for m in bench["per_layer"]}
    for name in MRI_METRICS:
        m = by_name[name]
        assert m["workloads"] == [CELL] and m["moves"] == "recoveries_per_s"
        assert (ROOT / "bench" / "metrics" / f"{name}.py").exists()
    # the accepted metrics without a list apply to the new cell on their own
    applies = {m["name"] for m in bench["per_layer"] + bench["end_to_end"]
               if spec.applies(m, CELL)}
    assert applies >= {"device_idle_share", "threshold_share", "backtracks_per_iter",
                       "window_compiles", "recoveries_per_s", "setup_s", *MRI_METRICS}
    for lofar in ("lofar-cs302-int8.live-b8", "lofar-cs302-int8.archive-b128"):
        assert not any(spec.applies(by_name[n], lofar) for n in MRI_METRICS)


def test_every_name_and_why_keeps_the_form():
    bench = spec.benchmark(ROOT)
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        names = [e["name"] for e in bench[group]]
        assert len(set(names)) == len(names) and all(map(NAME.match, names))
    for entry in bench["configs"] + bench["workloads"]:
        assert 1 <= len(entry["why"]) <= 200 and "\n" not in entry["why"]
    assert len(bench["configs"][-1]["source"]) <= 200


def test_duplicate_cells_in_a_list_read_as_one():
    """``test_scopes._reading_every_scope`` appends the cell to lists that
    already hold it; a metric applies to a cell once either way."""
    metric = {"name": "fft_share", "workloads": [CELL, CELL]}
    assert spec.applies(metric, CELL)
    assert not spec.applies(metric, "lofar-cs302-int8.live-b8")


def test_traced_rehearsal_reads_yquant_and_the_operator_scopes(tmp_path, monkeypatch):
    """The CPU has no TPU plane: beside the real trace of the traced window,
    the rehearsal writes a device plane whose events carry the paths the
    program gives the quantizer and the MRI operators, and reads every
    metric of the cell through ``run_cell`` on the real ``BENCHMARK.json``."""
    paths = [
        "jit(_quantize_per_band)/yquant/jit(_uniform)/threefry2x32:",
        "jit(_quantize_per_band)/yquant/reduce_max:",
        BODY + "/mv/fft/jit(fft)/fft:",
        BODY + "/rmv/sample/scatter:",
        BODY + "/rmv/dwt/while/body/add:",
        BODY + "/threshold/sort:",
        BODY + "/add:",                       # in no scope
    ]
    load = xplane.load

    def load_with_device(path):
        trace = load(path)
        lo, hi = xplane.window(trace)
        slot = (hi - lo) / 1e6 / 10   # ms
        events = [((i + 1) * slot, (i + 2) * slot, _hlo(f"op.{i}"), p)
                  for i, p in enumerate(paths)]
        dev = Path(path) / "zz" / "device.xplane.pb"
        dev.parent.mkdir()
        dev.write_bytes(xspace(events, [], int(lo)))
        later = time.time() + 60
        os.utime(dev, (later, later))
        trace["devices"] = load(dev)["devices"]
        return trace

    monkeypatch.setattr(xplane, "load", load_with_device)
    line = harness.run_cell(CELL, 2**31 + 31, 1.0, True, t_start=time.monotonic(),
                            require_tpu=False, overrides=dict(SMOKE[CELL], trace_seconds=0.01),
                            trace_dir=tmp_path / "trace", device_kind="TPU v5 lite")
    assert line["correct"] is True
    got = {m: line["metrics"][m]["value"] for m in MRI_METRICS}
    busy = len(paths)
    assert got == pytest.approx({"yquant_share": 200 / busy, "fft_share": 100 / busy,
                                 "sample_share": 100 / busy, "dwt_share": 100 / busy})
    assert json.loads(json.dumps(line)) == line
