"""The peaks table: keyed by device kind, an unknown kind is an error."""
import json

import pytest

import smoke  # noqa: F401
from cb.peaks import PEAKS_FILE, UnknownDevice, peaks_for


def test_v5e_peaks_and_source():
    p = peaks_for("TPU v5 lite")
    assert p["bf16_flops_per_s"] == 197e12
    assert p["hbm_bytes_per_s"] == 819e9
    assert "Google Cloud" in p["source"]


@pytest.mark.parametrize("kind", ["cpu", "TPU v4", "TPU v5", "", "tpu"])
def test_unknown_device_kind_is_refused(kind):
    with pytest.raises(UnknownDevice):
        peaks_for(kind)


def test_every_entry_names_its_source():
    table = json.loads(PEAKS_FILE.read_text())
    for kind, p in table.items():
        assert p["source"] and p["hbm_bytes_per_s"] > 0, kind
