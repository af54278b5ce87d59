"""The table of peaks."""
import pytest

from lib import registry
from lib.registry import BenchError


def test_v5e_row_from_the_documentation():
    row = registry.peak_for("TPU v5 lite")
    assert row["flops_per_s"] == 197e12
    assert row["hbm_bytes_per_s"] == 819e9


@pytest.mark.parametrize("kind", ["cpu", "TPU v4", "TPU v5p", ""])
def test_unknown_device_kind_is_refused(kind):
    with pytest.raises(BenchError, match="no peak"):
        registry.peak_for(kind)
