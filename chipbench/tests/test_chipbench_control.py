"""The control, the plain reference in bfloat16 in the program's place,
comes out not correct under the cells' limits; the program does not.
The chip readings the limits were set from are in the limits files;
this is the same reading at a size a test run holds (ViG-Ti's published
widths at 224 px, a backlog of full ticks)."""

import pytest

from chipbench.tests import tiny
from chipbench import calibrate, registry, run
from chipbench.entries import vig as entry


@pytest.mark.parametrize("cell", ["vig_ti_iso.poisson_448",
                                  "vig_ti_iso.backlog_224"])
def test_control_fails_the_limits(monkeypatch, cell):
    limits = registry.limits(cell)
    conf = registry.config("vig_ti_iso")
    bench = tiny.install(monkeypatch, dict(conf), tiny.backlog_mix(224),
                         limits)
    monkeypatch.setattr(run, "jax_setup", lambda: None)
    got = calibrate.readings("tiny.cell", 2 ** 36 + 3, 0.5, bench=bench)
    program, n = got["program"]
    control, m = got["control"]
    print(f"{cell}: program {program}, control {control}, limits {limits}")
    assert n == m == run.SAMPLE_REQUESTS
    assert set(program) == set(control) == set(entry.NUMBERS)
    assert all(program[k] <= limits[k] for k in entry.NUMBERS)
    assert any(control[k] > limits[k] for k in entry.NUMBERS)
