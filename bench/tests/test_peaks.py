"""The table of peaks refuses a device it does not know."""
import pytest

from bench import peaks


def test_v5e_peaks():
    p = peaks.peaks_for("TPU v5 lite")
    assert (p.bf16_flops, p.int8_ops, p.hbm_bytes_s, p.hbm_bytes) == (
        197e12, 393e12, 819e9, 16e9)
    assert "TPU v5e" in p.source


@pytest.mark.parametrize("kind", ["cpu", "TPU v4", "TPU v6 lite", ""])
def test_unknown_device_kind_is_an_error(kind):
    with pytest.raises(KeyError):
        peaks.peaks_for(kind)
