"""benchmark/flops_brumby.py by hand: the counts of the power-retention
decode step (float32 S and z, read and written once a layer, at the
symmetric map's own width)."""
import pytest

from bench_paths import BENCH  # noqa: F401 — puts benchmark/ on sys.path
import flops
import flops_brumby as fb

L, HQ, HKV, D = 8, 40, 8, 128      # the brumby_14b_l8 cut


def test_the_symmetric_map_has_no_duplicate():
    assert fb.power_state_dim(D) == 8256
    assert fb.power_state_dim(1) == 1 and fb.power_state_dim(2) == 3


def test_a_sequence_holds_s_and_z_in_float32():
    # 8 layers x 8 heads x 8,256 rows x (128 values + the normaliser) x 4 B
    assert fb.power_state_bytes(L, HKV, D) == 8 * 8 * 8256 * 129 * 4
    assert fb.power_state_bytes(1, 1, D) == 8256 * 129 * 4


def test_decode_moves_the_state_once_each_way():
    n = 13
    assert fb.power_decode_bytes(n, L, HKV, D) \
        == 2 * n * fb.power_state_bytes(L, HKV, D)
    # a program that keeps tiles of 32 channels (D 10,240) moves 10,240 /
    # 8,256 as much: it can read 80.6% of this roofline at most
    assert 8256 / 10240 == pytest.approx(0.806, abs=5e-4)


def test_decode_counts_add_over_tokens_and_layers():
    assert fb.power_decode_bytes(5, L, HKV, D) \
        + fb.power_decode_bytes(7, L, HKV, D) \
        == fb.power_decode_bytes(12, L, HKV, D)
    assert fb.power_decode_flops(3, 2 * L, HQ, HKV, D) \
        == 2 * fb.power_decode_flops(3, L, HQ, HKV, D)


def test_decode_flops_by_kv_heads_and_query_heads():
    values = 8256 * 129
    # decay 1 + rank-1 update 2 a K/V head, readout 2 a query head
    assert fb.power_decode_flops(1, 1, HQ, HKV, D) \
        == values * (3 * 8 + 2 * 40)
    assert fb.power_decode_flops(1, 1, 1, 1, D) == values * 5


def test_decode_is_bound_by_its_bytes():
    peaks = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    f = fb.power_decode_flops(16, L, HQ, HKV, D)
    b = fb.power_decode_bytes(16, L, HKV, D)
    least, which = flops.least_time_s(f, b, peaks)
    assert which == "bytes" and least == pytest.approx(b / 819e9)
    # 104 FLOPs a state value of 8 bytes moved, over 8 K/V heads
    assert f / b == pytest.approx(104 / (8 * 8))
