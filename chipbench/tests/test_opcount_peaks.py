import pytest

from chipbench import opcount


def test_peaks_are_keyed_by_device_kind_with_their_source():
    v5e = opcount.load_peaks("TPU v5 lite")
    assert v5e["bf16_flops_per_s"] == 197e12
    assert v5e["hbm_bytes_per_s"] == 819e9
    assert "source" in v5e and v5e["source"]
    with pytest.raises(KeyError):
        opcount.load_peaks("TPU v9 imaginary")


def test_stencil_bytes():
    assert opcount.stencil_node_bytes(1 << 27) == 8 << 27
    assert opcount.stencil_dag_bytes(1 << 27, 4, 45) == 8 * 45 << 29
    assert opcount.stencil_dag_cells(1 << 27, 4, 45) == 45 << 29


def test_paged_decode_attention_bytes():
    # one slot whose new token sits at position 9 reads 10 rows of K and
    # of V: 2 x 10 x 2 heads x 128 x 2 bytes a layer
    one = opcount.paged_decode_attention_bytes([9], 1, 2, 128, 2)
    assert one == 2 * 10 * 2 * 128 * 2
    many = opcount.paged_decode_attention_bytes([9, 99, 0], 30, 2, 128, 2)
    assert many == 30 * 2 * (10 + 100 + 1) * 2 * 128 * 2
    assert opcount.paged_decode_attention_bytes([], 30, 2, 128) == 0
