"""The trace reduction on a hand-made trace, where every number is known."""
import pytest

from bench import xplane

MS = 1_000_000
WHILE = "%while.1 = (s32[], f32[8,64]) while((s32[], f32[8,64]) %tuple.2), condition=%c, body=%b"
FUSION = "%fusion.1 = f32[8,64]{1,0} fusion(f32[8,64]{1,0} %sort.3), kind=kLoop"
SORT = "%sort.3 = (f32[8,64]{1,0:T(8,128)}, s32[8,64]) sort(f32[8,64] %x, s32[8,64] %i)"
KERNEL = ('%custom-call.2 = f32[8,870]{1,0} custom-call(f32[8,64] %x), '
          'custom_call_target="tpu_custom_call"')


def ev(s, e, text):
    return (s * MS, e * MS) + xplane.describe(text)


def trace():
    # window 0-100 ms; device busy 10-30 (a loop: a fusion, then a sort) and
    # 50-60 (a kernel); host: submit 0-40, fetch 40-100
    dev = [ev(10, 30, WHILE), ev(12, 18, FUSION), ev(20, 25, SORT), ev(50, 60, KERNEL)]
    spans = [(0, 100 * MS, "bench.window"), (0, 40 * MS, "bench.submit"),
             (40 * MS, 100 * MS, "bench.fetch")]
    return {"devices": {0: dev}, "spans": spans}


def test_describe_reads_the_opcode_not_the_operands():
    assert xplane.describe(FUSION)[1] == "fusion fusion.1"
    assert xplane.describe(SORT)[1] == "sort sort.3"
    assert xplane.describe(KERNEL)[1] == "custom-call custom-call.2 tpu_custom_call"
    assert xplane.describe(SORT)[0].startswith("%sort.3 = (f32[8,64]")


def test_busy_idle_and_breakdown():
    red = xplane.reduce(trace(), 1)
    assert red["busy_s"] == pytest.approx(0.030)
    assert red["window_s"] == pytest.approx(0.100)
    idle = dict(red["breakdown"]["idle_gaps"])
    assert idle == pytest.approx({"bench.fetch": 0.050, "bench.submit": 0.020})
    ops = {k.split(" =")[0]: v for k, v in red["breakdown"]["device_ops"]}
    assert ops == pytest.approx({"%fusion.1": 0.006, "%sort.3": 0.005,
                                 "%custom-call.2": 0.010})


def test_shares_and_seconds():
    red = xplane.reduce(trace(), 1)
    assert xplane.share(red, [r"^sort "]) == pytest.approx(100 * 5 / 30)
    assert xplane.seconds(red, [r"tpu_custom_call"]) == pytest.approx(0.010)
    assert xplane.share(red, [r"^fft "]) is None


def test_events_outside_the_window_do_not_count():
    t = trace()
    t["devices"][0].append(ev(120, 130, FUSION))
    assert xplane.reduce(t, 1)["busy_s"] == pytest.approx(0.030)
