"""benchmark/flops_zaya.py by hand: the counts of paged attention under
grouped heads (bytes by the pool's K/V heads, FLOPs by the query heads)."""
import pytest

from bench_paths import BENCH  # noqa: F401 — puts benchmark/ on sys.path
import flops
import flops_zaya as fz

L, HQ, HKV, D = 16, 8, 2, 128      # the zaya1_8b_l16 cut


def test_a_cached_token_is_k_and_v_rows_of_the_kv_heads():
    # 16 layers x (256 K + 256 V values) x 2 B: the configuration's 16,384
    assert fz.kv_bytes_per_token(L, HKV, D) == 16384
    assert fz.kv_bytes_per_token(L, HKV, D, itemsize=1) == 8192


def test_decode_reads_by_kv_heads_and_computes_by_query_heads():
    ctx = 1100
    assert fz.cca_decode_bytes(ctx, L, HKV, D) == ctx * 16384
    assert fz.cca_decode_flops(ctx, L, HQ, D) == 4 * ctx * L * HQ * D
    # with a K/V head a query head the two are flops.py's paged counts
    assert fz.cca_decode_bytes(ctx, 24, 16, 64) \
        == flops.paged_decode_bytes(ctx, 24, 16, 64)
    assert fz.cca_decode_flops(ctx, 24, 16, 64) \
        == flops.paged_decode_flops(ctx, 24, 16, 64)
    # a group of 4 reads a quarter of what 8 K/V heads would
    assert 4 * fz.cca_decode_bytes(ctx, L, HKV, D) \
        == flops.paged_decode_bytes(ctx, L, HQ, D)


def test_decode_counts_add_over_the_rows_contexts():
    ctxs = (40, 700, 2500)
    assert sum(fz.cca_decode_bytes(c, L, HKV, D) for c in ctxs) \
        == fz.cca_decode_bytes(sum(ctxs), L, HKV, D)


def test_decode_is_bound_by_its_bytes():
    peaks = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    f = fz.cca_decode_flops(1100, L, HQ, D)
    b = fz.cca_decode_bytes(1100, L, HKV, D)
    least, which = flops.least_time_s(f, b, peaks)
    assert which == "bytes" and least == pytest.approx(b / 819e9)
    # 4 FLOPs a query head and K/V value, 4 heads a K/V head, 2 B a value
    assert f / b == pytest.approx(4.0)


def test_prefill_is_the_causal_square_by_query_heads():
    p = 512
    assert fz.cca_prefill_flops(p, L, HQ, D) \
        == flops.prefill_attention_flops(p, L, HQ, D)
    assert fz.cca_prefill_flops(1, 1, 1, D) == 4 * D
    # Q in and O out by 8 heads, K and V by 2
    assert fz.cca_prefill_bytes(p, L, HQ, HKV, D) \
        == 2 * L * p * (HQ + HKV) * D * 2
    assert fz.cca_prefill_bytes(p, 24, 16, 16, 64) \
        == flops.prefill_attention_bytes(p, 24, 16, 64)
