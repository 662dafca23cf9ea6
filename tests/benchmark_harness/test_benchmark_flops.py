"""flops.py and peaks.json: pure functions of shapes, checked by hand."""
import pytest

import bench_paths  # noqa: F401 — sys.path for the next import
import flops


def test_peaks_v5e_and_no_default():
    p = flops.device_peaks("TPU v5 lite")
    assert p == {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9,
                 "hbm_bytes": 16e9}
    for unknown in ("cpu", "TPU v4", "_source"):
        with pytest.raises(KeyError):
            flops.device_peaks(unknown)


def test_six_n():
    assert flops.train_flops_per_token_6n(355_000_000) == 2_130_000_000


@pytest.mark.parametrize("causal,want", [
    # 4 * B*H*S*T*D = 4 * 2*3*8*8*4 = 6144; causal keeps (S+1)/(2S) = 9/16
    (False, 6144), (True, 3456)])
def test_attention_flops_fwd(causal, want):
    assert flops.attention_flops_fwd(2, 3, 8, 8, 4, causal) == want


def test_causal_needs_square():
    with pytest.raises(ValueError):
        flops.attention_flops_fwd(1, 1, 8, 16, 4, True)


def test_train_is_three_forwards_and_matches_palm_correction():
    fwd = flops.attention_flops_fwd(8, 16, 1024, 1024, 64, False)
    assert flops.attention_flops_train(8, 16, 1024, 64, False) == 3 * fwd
    # summed over 24 layers and divided by the 8 x 1,024 tokens, the
    # non-causal count is PaLM's 12 * L * S * hidden per token
    per_token = 24 * 3 * fwd / (8 * 1024)
    assert per_token == flops.train_attention_extra_flops_per_token(
        24, 1024, 1024, causal=False) == 12 * 24 * 1024 * 1024
    assert flops.train_attention_extra_flops_per_token(
        24, 1024, 1024, causal=True) == 6 * 24 * 1024 * 1024


def test_train_bytes():
    # 12 arrays of B*H*S*D bf16 elements
    assert flops.attention_bytes_train(8, 16, 1024, 64) == \
        12 * 8 * 16 * 1024 * 64 * 2 == 201_326_592


def test_paged_decode():
    # GPT-2-medium, one token at context 300: K and V, 24 layers, 1024 wide
    assert flops.paged_decode_bytes(300, 24, 16, 64) == \
        300 * 2 * 24 * 1024 * 2 == 29_491_200
    assert flops.paged_decode_flops(300, 24, 16, 64) == 4 * 300 * 24 * 1024


def test_prefill():
    one_layer = flops.attention_flops_fwd(1, 16, 256, 256, 64, True)
    assert flops.prefill_attention_flops(256, 24, 16, 64) == 24 * one_layer
    assert flops.prefill_attention_bytes(256, 24, 16, 64) == \
        4 * 24 * 256 * 1024 * 2


def test_least_time_names_the_bound():
    peaks = flops.device_peaks("TPU v5 lite")
    t, which = flops.least_time_s(197e12, 1.0, peaks)
    assert (round(t, 9), which) == (1.0, "flops")
    t, which = flops.least_time_s(1.0, 819e9 * 2, peaks)
    assert (round(t, 9), which) == (2.0, "bytes")


def test_gpt2_medium_step_by_hand():
    """The figures PERF.md quotes for the 8 x 1,024 step."""
    f = 24 * flops.attention_flops_train(8, 16, 1024, 64, True)
    b = 24 * flops.attention_bytes_train(8, 16, 1024, 64)
    assert round(f / 1e12, 3) == 1.238 and round(b / 1e9, 3) == 4.832
    t, which = flops.least_time_s(f, b, flops.device_peaks("TPU v5 lite"))
    assert which == "flops" and round(t * 1e3, 3) == 6.285
