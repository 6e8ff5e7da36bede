"""The table of peaks: keyed by device kind, an unknown kind an error."""
import pytest

from chipbench.common import BenchError, peaks


def test_v5e_peaks():
    p = peaks("TPU v5 lite")
    assert p["bf16_flops_per_s"] == 197e12
    assert p["hbm_bytes_per_s"] == 819e9
    assert "TPU v5e" in p["source"]


def test_unknown_kind_is_an_error():
    with pytest.raises(BenchError):
        peaks("TPU v9 imaginary")
