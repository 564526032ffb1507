"""Helpers of the CPU benchmark sections (``benchmarks/common.py``)."""
from benchmarks.common import hbm_peak, structural_bytes_per_elem


def test_structural_bytes_model():
    assert structural_bytes_per_elem("fused_iter", 27) == (27 + 19) * 4
    assert structural_bytes_per_elem("jnp", 27) == (29 + 24 + 3 + 6) * 4
    assert structural_bytes_per_elem("not-a-core", 27) is None


def test_hbm_peak_is_keyed_by_device_kind():
    assert hbm_peak("TPU v5 lite") == 819e9
    assert hbm_peak("cpu") is None  # not measured, never a guessed default
