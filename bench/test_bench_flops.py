"""The operation and byte counters against hand counts on small batches
(d_model 128, 4 heads x 32, 2 layers, R = 8)."""
import flops
from tiny_cell import TINY

PEAK = {"bf16_flops": 1e3, "hbm_bytes_per_s": 1e3}


def test_counts_of_a_jagged_batch():
    lengths = [1, 2, 3]
    assert flops.tokens(lengths) == 6
    assert flops.causal_pairs(lengths) == 1 + 3 + 6
    # per layer: W1 128 x (4 * 4 * 32) + W2 (4 * 32) x 128
    assert flops.dense_matmul_params(TINY) == 2 * (65536 + 16384)
    dense = 6 * 163840 * 6
    attn = 12 * 4 * 32 * 10 * 2
    neg = 6 * 6 * 9 * 128
    assert flops.model_flops(TINY, lengths) == dense + attn + neg


def test_fuxi_counts_its_ffn():
    fuxi = dict(TINY, block="fuxi", d_ff=96)
    assert (flops.dense_matmul_params(fuxi)
            - flops.dense_matmul_params(TINY)) == 2 * 3 * 128 * 96


def test_kernel_work_and_roofline():
    lengths = [1, 2, 3]
    fwd = flops.attention_fwd(TINY, lengths)
    assert fwd == {"flops": 4 * 4 * 32 * 10, "bytes": 4 * 6 * 4 * 32 * 2}
    bwd = flops.attention_bwd(TINY, lengths)
    assert bwd == {"flops": 8 * 4 * 32 * 10, "bytes": 7 * 6 * 4 * 32 * 2}
    neg = flops.negatives_fwd(TINY, lengths)
    assert neg == {"flops": 2 * 6 * 8 * 128,
                   "bytes": 6 * (8 * 128 * 2 + 128 * 2 + 8 * 4)}
    nb = flops.negatives_bwd(TINY, lengths)
    assert nb["bytes"] == 6 * (8 * 128 * 2 + 2 * 128 * 2 + 8 * 4)
    assert flops.roofline_s(neg, PEAK) == neg["bytes"] / 1e3
    assert flops.roofline_s({"flops": 1e4, "bytes": 1.0}, PEAK) == 10.0


def test_counts_follow_lengths_not_capacity():
    # the same sequences give the same work whatever the padding
    assert flops.model_flops(TINY, [5, 7]) == flops.model_flops(TINY, [7, 5])
    assert flops.causal_pairs([4]) == 10
