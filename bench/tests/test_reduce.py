"""The trace reduction on a trace recorded on the chip.

``fixtures/lofar-live-b8.xplane.pb`` is a 0.9 s traced window of
``lofar-cs302-int8.live-b8`` (two chunks of 8 snapshots) on one TPU v5e,
recorded by ``bench/run.py``'s traced path. The numbers below are what
``bench/xplane.py`` read from it when it was recorded; a change to the
reduction that moves them changes what every per-layer metric means."""
from pathlib import Path

import pytest

from bench import spec, work, xplane

FIXTURE = Path(__file__).parent / "fixtures" / "lofar-live-b8.xplane.pb"


@pytest.fixture(scope="module")
def red():
    return xplane.reduce(xplane.load(FIXTURE), 1)


def test_window_and_busy(red):
    assert red["window_s"] == pytest.approx(0.908834944, rel=1e-9)
    assert red["busy_s"] == pytest.approx(0.900798167, rel=1e-9)


def test_host_spans_and_idle_time(red):
    idle = dict(red["breakdown"]["idle_gaps"])
    assert set(idle) == {"bench.fetch", "bench.submit", "none"}
    assert sum(idle.values()) == pytest.approx(red["window_s"] - red["busy_s"], rel=1e-9)
    assert idle["bench.fetch"] == pytest.approx(0.004007, rel=1e-3)


def test_qmm_kernel_time_and_threshold_share(red):
    assert xplane.seconds(red, [r"\bqmm"]) == pytest.approx(0.73099784, rel=1e-9)
    assert xplane.share(red, [r"^sort ", r"TopK"]) == pytest.approx(1.9501823653, rel=1e-9)
    assert xplane.share(red, [r"^fft "]) is None


def test_breakdown_is_innermost_ops(red):
    ops = red["breakdown"]["device_ops"]
    assert len(ops) == 10
    assert ops[0][0].startswith("%qmm_pallas.134 = f32[8,896]")
    assert ops[0][1] == pytest.approx(0.07152, rel=1e-3)
    assert not any(" while" in name for name, _ in ops)


def test_qmm_roofline_share_reader(red):
    cfg = spec.config(spec.benchmark(), "lofar-cs302-int8")
    peak = spec.peaks()["TPU v5 lite"]
    counts = [(60, 55), (60, 55)]   # two chunks: iterations run, backtrack rounds
    ctx = {"trace": red, "traced_counts": counts, "cfg": cfg, "rows": 8, "shards": 1,
           "peak": peak, "notes": []}
    got = spec.reader("qmm_roofline_share")(ctx)
    least, bound = work.least_seconds(cfg, 8, counts, peak)
    assert bound == "bytes"
    assert got == pytest.approx(100 * least / 0.73099784, rel=1e-9)
    assert 0 < got < 100
