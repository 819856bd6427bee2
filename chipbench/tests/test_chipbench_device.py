"""Without a TPU the benchmark prints no result and exits non-zero."""

import pytest

from chipbench.tests import tiny  # noqa: F401
from chipbench import device, run


def test_require_refuses_the_cpu():
    with pytest.raises(device.NoAccelerator):
        device.require(1)


def test_run_without_tpu_prints_no_result(capsys):
    rc = run.main(["--workload", "vig_ti_iso.backlog_224", "--seed", "1",
                   "--seconds", "1", "--trace", "0"])
    out = capsys.readouterr()
    assert rc != 0
    assert "{" not in out.out
    assert "no TPU" in out.err


def test_peaks_table_refuses_unknown_devices():
    assert device.peaks("TPU v5 lite")["bf16_flops"] == 197e12
    with pytest.raises(KeyError):
        device.peaks("cpu")
