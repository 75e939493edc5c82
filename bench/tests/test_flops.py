"""Each model-FLOP count against a hand count at a tiny size."""
import pytest

from bench.flops import ssm, vlm


def test_vlm_hand_count():
    model = {"d_model": 8, "d_ff": 16, "num_heads": 2, "num_kv_heads": 1,
             "head_dim": 4, "vocab_size": 10, "num_layers": 3,
             "vision": {"num_patches": 2}}
    # per position: q 8x8, k 8x4, v 8x4, o 8x8, mlp 3 x 8x16 = 64+32+32+64+384
    proj = 576
    # causal attention over S=6: sum_i (i+1) * 2 matmuls * 4 * 2 heads
    attn = sum(i + 1 for i in range(6)) * 2 * 4 * 2
    head = 4 * 8 * 10                      # 4 text positions
    macs = 3 * (6 * proj + attn) + head
    assert vlm.flops_per_row(model, {"seq_len": 6}) == pytest.approx(6 * macs)


def test_ssm_hand_count():
    model = {"d_model": 4, "vocab_size": 10, "num_layers": 2,
             "ssm": {"expand": 2, "head_dim": 4, "d_state": 3,
                     "chunk_size": 2, "ngroups": 1, "d_conv": 2}}
    # d_inner 8, heads 2; z 4x8, x 4x8, B 4x3, C 4x3, dt 4x2, out 8x4
    proj = 32 + 32 + 12 + 12 + 8 + 32
    conv = 2 * (8 + 6)
    # S=4: 2 chunks of Q=2; lower triangle 3 entries
    scores = 3 * 3                         # C B^T over N=3, one group
    per_head = 3 * 4 + 2 * 2 * 3 * 4 + 3 * 4   # (CB^T)x, states, out, pass
    ssd = 2 * (scores + 2 * per_head)
    macs = 2 * (4 * (proj + conv) + ssd) + 4 * 4 * 10
    assert ssm.flops_per_row(model, {"seq_len": 4}) == pytest.approx(6 * macs)


def test_counts_scale_with_depth_only_in_the_layers():
    model = {"d_model": 8, "d_ff": 16, "num_heads": 2, "num_kv_heads": 1,
             "head_dim": 4, "vocab_size": 10, "num_layers": 1}
    one = vlm.flops_per_row(model, {"seq_len": 6})
    two = vlm.flops_per_row(dict(model, num_layers=2), {"seq_len": 6})
    head = 6 * 6 * 8 * 10
    assert two - one == pytest.approx(one - head)
