"""The controls and the faults have to come out as not correct.

A control is the plain reference put in the program's place, computed in a
precision below one the configuration states: 4-bit codes for the 8-bit
LOFAR operator, or its products in three or one bfloat16 passes instead of
full float32; bfloat16 data for the float32 MRI path. The fault tests drive
a whole run, with the device check skipped, while the answers are altered
where ``BatchServer.submit`` produces them: every row, or only the first row
of every chunk."""
import pytest

from bench import check, control, spec
from bench.tests.conftest import SMOKE


def limits(cell, root):
    bench = spec.benchmark(root)
    return spec.config(bench, spec.workload(bench, cell)["config"], root)["limits"]


@pytest.mark.parametrize("cell", sorted(SMOKE))
def test_control_is_not_correct(cell, bench_root):
    rows = control.readings(cell, [21], 0.5, require_tpu=False, overrides=SMOKE[cell],
                            root=bench_root)
    (r,) = rows
    assert r["correct"] is True
    assert r["control"]
    for name, numbers in r["control"].items():
        assert check.judge(numbers, limits(cell, bench_root))["correct"] is False, name


@pytest.mark.parametrize("fault", sorted(control.FAULTS))
@pytest.mark.parametrize("cell", sorted(SMOKE))
def test_altered_answers_are_not_correct(cell, fault, bench_root):
    rows = control.readings(cell, [22], 0.5, fault=fault, require_tpu=False,
                            overrides=SMOKE[cell], root=bench_root)
    assert rows[0]["correct"] is False
