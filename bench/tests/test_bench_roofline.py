"""Compulsory bytes of a launch, against shapes worked by hand."""
import pytest

from bench.roofline import peaks, search_bytes


def test_one_row_two_keywords():
    # first list 16 x (ids, parents, ndesc) x 4 B = 192; one other list
    # 32 x (ids, ndesc) x 4 B = 256; 2 lengths x 4 B = 8; out 16 x 5 B = 80
    assert search_bytes(1, 2, 16, 32) == 192 + 256 + 8 + 80


def test_rows_scale_and_more_keywords():
    # R=8, k=4, m0=1024, mo=4096: per row 12288 + 3*32768 + 16 + 5120
    assert search_bytes(8, 4, 1024, 4096) == 8 * (12288 + 98304 + 16 + 5120)


def test_largest_100k_launch_is_megabytes():
    # R=1, k=4, m0=2**17, mo=2**19: about 14.9 MB, 18 us at 819 GB/s
    b = search_bytes(1, 4, 1 << 17, 1 << 19)
    assert b == 1572864 + 12582912 + 16 + 655360
    assert 18e-6 < b / peaks("TPU v5 lite")["hbm_bw"] < 19e-6


def test_unknown_device_is_an_error():
    with pytest.raises(KeyError):
        peaks("cpu")
