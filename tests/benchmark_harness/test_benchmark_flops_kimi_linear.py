"""flops_kimi_linear.py: pure functions of shapes, checked by hand at small
numbers and once at the cell's own (the figures ISSUE 26 argues from)."""
import bench_paths  # noqa: F401 — sys.path for the next import
import flops_kimi_linear as fk


def test_an_expert_is_three_matrices():
    # gate, up [E, F] and down [F, E]: 3 * 4 * 5 values of 2 bytes
    assert fk.expert_weight_bytes(4, 5) == 120
    assert fk.expert_weight_bytes(4, 5, itemsize=4) == 240
    # the cell's: 3 * 2304 * 1024 * 2 B = 14.16 MB an expert
    assert fk.expert_weight_bytes(2304, 1024) == 14_155_776


def test_grouped_matmul_streams_touched_experts_once():
    # 2 experts of 120 B, and 3 picks' rows of 4 values in and out (2 B)
    assert fk.moe_gmm_bytes(2, 3, 4, 5) == 2 * 120 + 2 * 3 * 4 * 2
    # a pick: 3 matmuls of 4 x 5, 2 FLOPs a multiply-add
    assert fk.moe_gmm_flops(3, 4, 5) == 3 * 3 * 2 * 4 * 5
    # the cell's decode step: all 128 held experts of 4 layers = 7.25 GB
    assert fk.moe_gmm_bytes(4 * 128, 0, 2304, 1024) == 7_247_757_312


def test_kda_state_is_read_and_written_once_a_token_and_layer():
    # 1 token, 1 layer, 2 heads of 3 x 3 float32, each way
    assert fk.kda_decode_bytes(1, 1, 2, 3) == 2 * 2 * 9 * 4
    assert fk.kda_decode_flops(1, 1, 2, 3) == 7 * 2 * 9
    # the cell's: 128 rows x 4 layers x 32 heads x 128 x 128 x 4 B x 2
    assert fk.kda_decode_bytes(128, 4, 32, 128) == 2_147_483_648


def test_kda_decode_is_bound_by_its_state_not_by_its_arithmetic():
    # 7 FLOPs for every 8 bytes moved, whatever the shape: far under the
    # 240 FLOPs a byte at which a v5e turns compute-bound
    for shape in ((1, 1, 2, 3), (128, 4, 32, 128)):
        assert fk.kda_decode_flops(*shape) * 8 == \
            fk.kda_decode_bytes(*shape) * 7


def test_latent_decode_reads_one_row_a_position_for_all_heads():
    assert fk.mla_decode_bytes(100, 2, 6) == 100 * 2 * 6 * 2
    # per head and position: score over 6 values, sum over 4
    assert fk.mla_decode_flops(100, 2, 3, 6, 4) == 2 * 100 * 2 * 3 * 10
    # the cell's: 128 rows at ~2,000 positions of 576 values, 1 layer
    assert fk.mla_decode_bytes(128 * 2000, 1, 576) == 294_912_000


def test_latent_decode_counts_add_over_the_rows_contexts():
    # the reader hands over the SUM of the decode tokens' contexts
    for fn, rest in ((fk.mla_decode_bytes, (1, 576)),
                     (fk.mla_decode_flops, (1, 32, 576, 512))):
        assert fn(300, *rest) + fn(1700, *rest) == fn(2000, *rest)
    # two layers read twice
    assert fk.mla_decode_bytes(7, 2, 576) == 2 * fk.mla_decode_bytes(7, 1, 576)
