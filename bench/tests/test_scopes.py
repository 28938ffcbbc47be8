"""The named-scope reader (``bench/scopes.py``) on the trace recorded on the
chip, on a hand-made ``XSpace`` where every share is known, and through a
traced CPU rehearsal of ``mri-brain-haar256.exam-b8``."""
import json
import os
import time
from pathlib import Path

import pytest

from bench import harness, scopes, spec, xplane
from bench.tests.conftest import SMOKE

FIXTURE = Path(__file__).parent / "fixtures" / "lofar-live-b8.xplane.pb"
QMM_PATH = "jit(_sharded_call)/while/body/while/body/jit(qmm_pallas)/pallas_call:"
SCOPE_METRICS = {"mv_share": "mv", "rmv_share": "rmv", "hs_share": "threshold",
                 "backtrack_share": "backtrack", "qmm_pad_share": "qmm_pad",
                 "fft_share": "fft", "sample_share": "sample", "dwt_share": "dwt"}
PS_PER_MS = 10**9


# -- a tiny protobuf encoder for XSpace ------------------------------------

def _varint(n):
    out = bytearray()
    while True:
        b, n = n & 0x7F, n >> 7
        out.append(b | 0x80 if n else b)
        if not n:
            return bytes(out)


def _msg(*pairs):
    out = b""
    for num, val in pairs:
        if isinstance(val, int):
            out += _varint(num << 3) + _varint(val)
        else:
            val = val.encode() if isinstance(val, str) else val
            out += _varint(num << 3 | 2) + _varint(len(val)) + val
    return out


def xspace(devices, spans, start_ns):
    """An ``XSpace`` of one ``/device:TPU:0`` plane and one host plane.
    ``devices``: [(start ms, end ms, HLO text, tf_op or None)] on the ``XLA
    Ops`` line; ``spans``: [(start ms, end ms, name)] on the host; times from
    ``start_ns``. Each device event gets metadata of its own, so one HLO text
    given two paths is a name with two paths."""
    meta, evs = [], []
    for i, (s, e, text, path) in enumerate(devices, 1):
        stats = [(5, _msg((1, 1), (5, path)))] if path else []
        meta.append((4, _msg((1, i), (2, _msg((1, i), (2, text), *stats)))))
        evs.append((4, _msg((1, i), (2, int(s * PS_PER_MS)), (3, int((e - s) * PS_PER_MS)))))
    dev = _msg((1, 1), (2, "/device:TPU:0"),
               (3, _msg((1, 1), (2, xplane.OPS_LINE), (3, start_ns), *evs)), *meta,
               (5, _msg((1, 1), (2, _msg((1, 1), (2, scopes.TF_OP))))))
    host_meta = [(4, _msg((1, i), (2, _msg((1, i), (2, n)))))
                 for i, (_, _, n) in enumerate(spans, 1)]
    host_evs = [(4, _msg((1, i), (2, int(s * PS_PER_MS)), (3, int((e - s) * PS_PER_MS))))
                for i, (s, e, _) in enumerate(spans, 1)]
    host = _msg((1, 2), (2, "/host:CPU"),
                (3, _msg((1, 1), (2, "python"), (3, start_ns), *host_evs)), *host_meta)
    return _msg((1, dev), (1, host))


def _hlo(name, op="fusion"):
    return f"%{name} = f32[8,64]{{1,0}} {op}(f32[8,64]{{1,0}} %x), kind=kLoop"


def _ctx(path):
    red = xplane.reduce(xplane.load(path), 1)
    return {"trace": red, "trace_path": path, "notes": []}


# -- the trace recorded on the chip ----------------------------------------

def test_decoder_reads_the_qmm_paths_of_the_chip_trace():
    paths = scopes.decode(FIXTURE)[0]
    qmm = {p for n, p in paths.items() if n.startswith("%qmm_pallas.")}
    assert QMM_PATH in qmm
    assert all(p.endswith("/jit(qmm_pallas)/pallas_call:") for p in qmm)
    assert len(paths) > 100 and None not in paths.values()


def test_existing_metrics_read_the_same_after_the_scope_readers():
    """The recorded trace predates the scopes: every scope reader reads
    nothing there, and every existing reader reads what ``test_reduce``
    pins, before and after the scope readers ran on the same context."""
    ctx = _ctx(FIXTURE)
    ctx.update(cfg=spec.config(spec.benchmark(), "lofar-cs302-int8"), rows=8, shards=1,
               peak=spec.peaks()["TPU v5 lite"], traced_counts=[(60, 55), (60, 55)],
               counts=[(60, 55), (60, 55)], compiles=0)
    existing = ("device_idle_share", "qmm_roofline_share", "threshold_share",
                "backtracks_per_iter", "window_compiles")
    before = {m: spec.reader(m)(ctx) for m in existing}
    assert all(spec.reader(m)(ctx) is None for m in SCOPE_METRICS)
    assert {m: spec.reader(m)(ctx) for m in existing} == before
    assert before["threshold_share"] == pytest.approx(1.9501823653, rel=1e-9)
    assert before["device_idle_share"] == pytest.approx(
        100 * (1 - 0.900798167 / 0.908834944), rel=1e-6)


# -- a hand-made trace ------------------------------------------------------

LOOP = "jit(s)/while"
BODY = LOOP + "/body"
HAND = [  # (start ms, end ms, HLO text, tf_op)
    (10, 60, _hlo("while.1", "while"), LOOP + ":"),
    (10, 20, _hlo("qmm_pallas.2", "custom-call"), BODY + "/mv/jit(qmm_pallas)/pallas_call:"),
    (20, 25, _hlo("pad.3", "pad"), BODY + "/rmv/qmm_pad/jit(_pad)/pad:"),
    (30, 50, _hlo("while.4", "while"), BODY + "/backtrack/while:"),
    (32, 40, _hlo("dot.5", "dot"), BODY + "/backtrack/while/body/mv/dot_general:"),
    (42, 48, _hlo("sort.6", "sort"), BODY + "/backtrack/while/body/threshold/sort:"),
    (52, 56, _hlo("fusion.7"), BODY + "/add:"),                 # in no scope
    (56, 58, _hlo("fusion.8"), "jit(mv)/add:"),                 # a call, not the scope
    (70, 80, _hlo("fusion.9"), BODY + "/mv/add:"),              # one name, two paths
    (85, 86, _hlo("fusion.9"), BODY + "/rmv/add:"),
    (90, 95, _hlo("copy.10", "copy"), None),                    # no path at all
    (120, 130, _hlo("fusion.11"), BODY + "/mv/add:"),           # outside the window
]


@pytest.fixture()
def hand(tmp_path):
    path = tmp_path / "hand.xplane.pb"
    path.write_bytes(xspace(HAND, [(0, 100, xplane.WINDOW)], 1_000_000))
    return _ctx(path)


def test_hand_made_shares(hand):
    busy = 50 + 10 + 1 + 5   # the loop, the two-path name twice, the copy
    assert hand["trace"]["busy_s"] == pytest.approx(busy / 1e3)
    want = {"mv": 10 + 8, "rmv": 5, "qmm_pad": 5, "backtrack": 20, "threshold": 6}
    for scope, ms in want.items():
        assert scopes.share(hand, scope, scope) == pytest.approx(100 * ms / busy)
    assert scopes.share(hand, "fft", "fft") is None
    unscoped = 100 * (1 - (15 + 20) / busy)
    notes = [n for n in hand["notes"] if "of busy time; unscoped" in n]
    assert len(notes) == len(want)
    assert all(n.endswith(f"unscoped {unscoped:.3f} %") for n in notes)
    clash = [n for n in hand["notes"] if n.startswith("scopes: decoded")]
    assert len(clash) == 1 and "1 names with two paths" in clash[0]


def test_scope_matches_whole_components():
    comps = scopes.components(BODY + "/backtrack/while/body/mv/dot_general:")
    assert {"backtrack", "mv"} <= comps and "dot_general" in comps
    assert "mv" not in scopes.components("jit(mv)/add:")
    assert scopes.components("a/b") == {"a", "b"}


def test_readers_find_the_trace_through_their_caller(hand):
    """Without ``trace_path`` in the context, a reader takes the path its
    caller holds, as ``harness.run_cell`` holds it."""
    trace_path = hand.pop("trace_path")
    assert trace_path.name == "hand.xplane.pb"
    assert spec.reader("backtrack_share")(hand) == pytest.approx(100 * 20 / 66)


def test_readers_without_a_trace_read_nothing():
    red = xplane.reduce({"devices": {0: []}, "spans": [(0, 1, xplane.WINDOW)]}, 1)
    ctx = {"trace": red, "notes": []}
    assert spec.reader("mv_share")(ctx) is None
    assert "not known" in ctx["notes"][0]


# -- the traced CPU rehearsal of the MRI cell -------------------------------

def _reading_every_scope(bench_root, root, cell):
    """A checkout whose ``BENCHMARK.json`` reads every scope metric but
    ``qmm_pad_share`` in ``cell``: exam-b8 is not in the benchmark yet, and
    neither are the metrics only it would report."""
    root.mkdir()
    (root / "bench").symlink_to(bench_root / "bench")
    bench = json.loads((bench_root / "BENCHMARK.json").read_text())
    have = {m["name"]: m for m in bench["per_layer"]}
    for name in set(SCOPE_METRICS) - {"qmm_pad_share"}:
        if name not in have:
            have[name] = {"name": name, "unit": "%", "better": "lower",
                          "source": "device_trace", "layer": "operators",
                          "moves": "recoveries_per_s", "workloads": []}
            bench["per_layer"].append(have[name])
        have[name]["workloads"].append(cell)
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return root


def test_traced_exam_b8_rehearsal_reads_every_new_metric(bench_root, tmp_path, monkeypatch):
    """The CPU has no TPU plane: beside the real trace of the traced window,
    the rehearsal writes a device plane whose events carry the paths the
    program's scopes give the MRI solve, and reads every scope metric that
    applies to the cell through ``run_cell``."""
    cell = "mri-brain-haar256.exam-b8"
    root = _reading_every_scope(bench_root, tmp_path / "checkout", cell)
    paths = {"fft": BODY + "/mv/fft/jit(fft)/fft:",
             "sample": BODY + "/rmv/sample/scatter:",
             "dwt": BODY + "/rmv/dwt/while/body/scatter-add:",
             "threshold": BODY + "/threshold/sort:",
             "backtrack": BODY + "/backtrack/while:",
             "bt_mv": BODY + "/backtrack/while/body/mv/dwt/while/body/add:",
             "none": BODY + "/add:"}
    load = xplane.load

    def load_with_device(path):
        trace = load(path)
        lo, hi = xplane.window(trace)
        slot = (hi - lo) / 1e6 / 10   # ms
        events = [((i + 1) * slot, (i + 2) * slot, _hlo(f"op.{i}"), p)
                  for i, p in enumerate(paths.values())]
        events[5] = (5 * slot, 5.5 * slot) + events[5][2:]   # inside the backtrack loop
        dev = Path(path) / "zz" / "device.xplane.pb"
        dev.parent.mkdir()
        dev.write_bytes(xspace(events, [], int(lo)))
        later = time.time() + 60
        os.utime(dev, (later, later))
        trace["devices"] = load(dev)["devices"]
        return trace

    monkeypatch.setattr(xplane, "load", load_with_device)
    line = harness.run_cell(cell, 2**31 + 29, 1.0, True, t_start=time.monotonic(),
                            require_tpu=False, overrides=dict(SMOKE[cell], trace_seconds=0.01),
                            root=root, trace_dir=tmp_path / "trace", device_kind="TPU v5 lite")
    assert line["correct"] is True
    bench = spec.benchmark(root)
    applies = {m["name"] for m in bench["per_layer"]
               if m["name"] in SCOPE_METRICS and spec.applies(m, cell)}
    assert applies == set(SCOPE_METRICS) - {"qmm_pad_share"}
    got = {m: line["metrics"][m]["value"] for m in applies}
    busy = 6   # five slots in a row (the backtrack loop holds the sixth event), and one
    assert got == pytest.approx({"fft_share": 100 / busy, "sample_share": 100 / busy,
                                 "dwt_share": 100 * 1.5 / busy, "hs_share": 100 / busy,
                                 "backtrack_share": 100 / busy,
                                 "mv_share": 100 * 1.5 / busy, "rmv_share": 200 / busy})
