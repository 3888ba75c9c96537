"""Byte counts against hand counts, and the peak table."""
import pytest

from bench import costs


def test_lane_bytes_hand_count():
    # 100 accesses over 50 pages: record 4 B, page state 13 B read+written
    assert costs.lane_bytes(100, 50, "none", "lru") == 100 * 30 + 50 * 13
    # hotcold/random add a 4-byte policy word per page
    assert costs.lane_bytes(100, 50, "none", "hotcold") == 100 * 38 + 50 * 17
    # tree lanes add one int32 count per node: 50 * 63/512 -> 6 nodes
    assert costs.lane_bytes(100, 50, "tree", "lru") == 3000 + 650 + 6 * 4
    # oracle lanes read one more int32 per access
    assert costs.lane_bytes(100, 50, "oracle", "lru") == 100 * 34 + 650


def test_peaks_known_and_unknown_device():
    pk = costs.peaks("TPU v5 lite")
    assert pk["bf16_flops_per_s"] == 1.97e14
    assert pk["hbm_bytes_per_s"] == 8.19e11
    with pytest.raises(KeyError, match="no peaks"):
        costs.peaks("TPU v99")
    with pytest.raises(KeyError):
        costs.bytes_roofline_pct(1.0, 1.0, "cpu")


def test_bytes_roofline_pct_at_the_hbm_peak():
    assert costs.bytes_roofline_pct(8.19e11, 2.0, "TPU v5 lite") \
        == pytest.approx(50.0)
    assert costs.bytes_roofline_pct(8.19e11, 1.0, "TPU v5 lite") \
        == pytest.approx(100.0)


@pytest.mark.parametrize("eviction", ["lru", "random", "hotcold"])
@pytest.mark.parametrize("prefetcher", ["none", "block", "tree", "oracle"])
def test_lane_bytes_grow_with_the_work(prefetcher, eviction):
    one = costs.lane_bytes(100, 50, prefetcher, eviction)
    assert costs.lane_bytes(200, 50, prefetcher, eviction) > one
    assert costs.lane_bytes(100, 100, prefetcher, eviction) > one
