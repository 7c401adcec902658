"""The table of peaks: v5e as published, and no default for an unknown chip."""
import pytest

from bench import run


def test_v5e_peaks():
    p = run.peaks_of("TPU v5 lite")
    assert p["bf16_flop_per_s"] == 197e12
    assert p["hbm_bytes_per_s"] == 819e9


def test_unknown_device_kind_is_refused():
    with pytest.raises(KeyError):
        run.peaks_of("TPU v9 imaginary")
