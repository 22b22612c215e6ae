"""Compulsory operations and bytes, and the peaks table."""
import pytest

from bench import counts


def test_gcn_counts_by_hand():
    # N=10 nodes (2 float), E=40 edges, widths 3 -> 2 -> 1
    age = counts.age_work("gcn", 10, 40, 2, (3, 2, 1))
    layer1 = counts.Work(2 * 40 * 3, 0, 40 * 8 + (8 * 3 + 2 * 3 * 4) + 10 * 3 * 4)
    layer2 = counts.Work(2 * 40 * 2, 0, 40 * 8 + (8 * 2 + 2 * 2 * 4) + 10 * 2 * 4)
    assert age == layer1 + layer2
    fwd = counts.forward_work("gcn", 10, 40, 2, (3, 2, 1))
    fte1 = counts.Work(2 * 2 * 3 * 2, 2 * 8 * 3 * 2, (8 * 3 + 2 * 3 * 4) + 3 * 2 * 5 + 10 * 2 * 4)
    fte2 = counts.Work(2 * 2 * 2 * 1, 2 * 8 * 2 * 1, (8 * 2 + 2 * 2 * 4) + 2 * 1 * 5 + 10 * 1 * 4)
    assert fwd == age + fte1 + fte2


def test_gat_counts_cover_attention_passes():
    age = counts.age_work("gat", 10, 40, 2, (3, 8, 2), heads=4)
    # hidden layer aggregates 8 = 4 heads x 2, output layer 4 heads x 2 = 8
    one = counts.Work(6 * 40 * 4, 0, 2 * 4 * 10 * 4) + counts.Work(
        2 * 40 * 8, 0, 40 * (4 + 4 * 4) + (8 * 8 + 2 * 8 * 4) + 10 * 8 * 4)
    assert age == one + one


def test_seconds_take_the_larger_bound():
    peaks = counts.peaks_for("TPU v5 lite")
    w = counts.Work(float_flops=197e12, int8_ops=0, bytes=819e9 * 2)
    assert w.seconds(peaks) == pytest.approx(2.0)
    assert w.bound(peaks) == "memory"
    w = counts.Work(float_flops=197e12, int8_ops=393e12, bytes=0)
    assert w.seconds(peaks) == pytest.approx(2.0)
    assert w.bound(peaks) == "compute"


def test_unknown_device_is_an_error():
    with pytest.raises(KeyError, match="no peaks"):
        counts.peaks_for("TPU v99")


def test_unknown_arch_is_an_error():
    with pytest.raises(ValueError):
        counts.age_work("gin", 10, 40, 2, (3, 2, 1))
