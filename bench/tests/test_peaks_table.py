"""The peaks table is keyed by device kind, and an unknown kind is an
error, not a default."""
import pytest

from benchlib import devicetrace as dt


def test_v5e_row():
    row = dt.peaks_for("TPU v5 lite")
    assert row["hbm_bytes_per_s"] == 819e9
    assert row["bf16_flops_per_s"] == 197e12
    assert row["memory_bytes"] == 16e9


@pytest.mark.parametrize("kind", ["cpu", "TPU v4", ""])
def test_unknown_kind_is_refused(kind):
    with pytest.raises(dt.UnknownDeviceError, match="no peak rates"):
        dt.peaks_for(kind)
