"""Each configuration's traffic end to end on the CPU at SMOKE size, through
the functions ``bench/run.py`` calls, with the program checked against the
plain references."""
import json
import os
import shutil
import subprocess
import sys
import time

import pytest

from bench import harness, spec
from bench.tests.conftest import ROOT, SMOKE


@pytest.mark.parametrize("cell", sorted(SMOKE))
def test_cell_runs_and_agrees_with_reference(cell, bench_root):
    line = harness.run_cell(cell, 2**31 + 17, 1.0, False, t_start=time.monotonic(),
                            require_tpu=False, overrides=SMOKE[cell], root=bench_root)
    assert line["correct"] is True
    assert line["attempted"] > 0 and line["failed"] == 0
    bench = spec.benchmark(bench_root)
    want = {m["name"] for m in bench["end_to_end"] if spec.applies(m, cell)}
    assert set(line["metrics"]) == want
    assert all(v["value"] > 0 for k, v in line["metrics"].items() if k != "resident_mb")
    assert list(line)[-1] == "checks"
    assert line["checks"]["median_gap"]["value"] < 1e-5


def test_seed_gives_the_same_inputs():
    cell = "lofar-cs302-int8.live-b8"
    cfg = spec.config(spec.benchmark(), "lofar-cs302-int8")
    cfg.update({k: v for k, v in SMOKE[cell].items() if k in cfg})
    from bench.problems import interferometer as itf

    phi = itf.make_phi(*itf.geometry(cfg))
    a = itf.make_rows(phi, harness.seed_keys(5)["pool"], range(8), cfg)[0]
    b = itf.make_rows(phi, harness.seed_keys(5)["pool"], range(8), cfg)[0]
    c = itf.make_rows(phi, harness.seed_keys(6)["pool"], range(8), cfg)[0]
    assert (a == b).all() and not (a == c).all()


def test_no_tpu_exits_nonzero_without_a_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run([sys.executable, str(ROOT / "bench" / "run.py"), "--workload",
                          "lofar-cs302-int8.live-b8", "--seed", "1", "--seconds", "1",
                          "--trace", "0"], capture_output=True, text=True, env=env,
                         cwd=ROOT, timeout=300)
    assert out.returncode != 0
    assert out.stdout.strip() == ""
    assert "needs a TPU" in out.stderr


def test_benchmark_files_alone_do_not_run(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    out = subprocess.run([sys.executable, "bench/run.py", "--workload",
                          "lofar-cs302-int8.live-b8", "--seed", "1", "--seconds", "1",
                          "--trace", "0"], capture_output=True, text=True,
                         cwd=tmp_path, timeout=300)
    assert out.returncode != 0
    assert out.stdout.strip() == ""


def test_result_line_is_json(bench_root):
    cell = "mri-brain-haar256.exam-b8"
    line = harness.run_cell(cell, 3, 0.5, False, t_start=time.monotonic(),
                            require_tpu=False, overrides=SMOKE[cell], root=bench_root)
    assert json.loads(json.dumps(line)) == line


def test_traced_roofline_covers_only_the_traced_chunks(bench_root, tmp_path, monkeypatch):
    """A traced run whose window outlasts the trace: the qmm kernel's work
    and its device time have to cover the same chunks. The CPU has no TPU
    plane, so the trace gets one qmm event for each chunk that ran inside
    it, twice as long as the least time of that chunk's own work: the share
    reads 50 %, where work counted over the whole window would read several
    times that."""
    from bench import work, xplane
    from bench.problems import interferometer as itf

    cell = "lofar-cs302-int8.live-b8"
    peak = spec.peaks()["TPU v5 lite"]
    done = []   # (cfg, counts) of every chunk served, the two warm-up chunks first
    request = itf.Cell.request

    def recording(self, i, key):
        x, counts = request(self, i, key)
        done.append((self.cfg, harness.shard_counts([counts], 1)))
        return x, counts

    def load_with_qmm(path):
        trace = load(path)
        lo, hi = xplane.window(trace)
        traced = [s for s in trace["spans"] if s[2] == "bench.submit" and lo <= s[0] < hi]
        events, t = [], lo
        for cfg, counts in done[2:2 + len(traced)]:
            ns = round(2e9 * work.least_seconds(cfg, 8, counts, peak)[0])
            events.append((t, t + ns) + xplane.describe(
                '%qmm_pallas.1 = f32[8,896]{1,0} custom-call(f32[8,1024] %x), '
                'custom_call_target="tpu_custom_call"'))
            t += ns
        trace["devices"] = {0: events}
        seen.append(len(traced))
        return trace

    load, seen = xplane.load, []
    monkeypatch.setattr(itf.Cell, "request", recording)
    monkeypatch.setattr(xplane, "load", load_with_qmm)
    line = harness.run_cell(cell, 2**31 + 5, 4.0, True, t_start=time.monotonic(),
                            require_tpu=False, overrides=dict(SMOKE[cell], trace_seconds=0.01),
                            root=bench_root, trace_dir=tmp_path, device_kind="TPU v5 lite")
    assert line["correct"] is True
    assert 0 < seen[0] < line["attempted"] // 8 / 2
    assert line["metrics"]["qmm_roofline_share"]["value"] == pytest.approx(50, rel=1e-3)
