"""flops.py against counts made by hand at a reduced size."""
import pytest

from bench import flops
from bench.reference.dense_lm import sizes

HP = {"hidden_size": 8, "intermediate_size": 12, "num_hidden_layers": 2,
      "num_attention_heads": 2, "num_key_value_heads": 1, "vocab_size": 10}
S = sizes(HP)   # D 8, H 2, KV 1, hd 4, F 12, V 10, L 2


def test_sizes():
    assert S == {"D": 8, "H": 2, "KV": 1, "hd": 4, "F": 12, "V": 10, "L": 2}


def test_one_decode_token():
    # per layer: q 8x8, k 8x4, v 8x4, o 8x8 = 192; MLP 3 x 8x12 = 288; 480 MACs
    # two layers: 960 MACs; logits 8x10 = 80 MACs
    # attention to 5 positions: per layer and head, q.k 4 x 5 and p.v 4 x 5
    # = 40 MACs, x 2 heads x 2 layers = 160 MACs
    assert flops.decode_token_flops(S, 5) == 2 * (960 + 80 + 160)


def test_kernels_and_roofline():
    dec = flops.decode_attention_kernel(S, [5, 1])
    # flops: (5 + 1) pairs x 2 x 4 MACs x 2 heads x 2 layers x 2
    assert dec["flops"] == 2 * 6 * 8 * 2 * 2
    # bytes: per layer K and V rows to each position (1 kv head x 4) plus q
    # and o (2 heads x 4), bf16
    assert dec["bytes"] == 2 * ((2 * 5 * 4 + 2 * 8) + (2 * 1 * 4 + 2 * 8)) * 2
    peaks = {"bf16_flops": 100.0, "hbm_bytes_per_s": 10.0}
    r = flops.roofline_time({"flops": 50.0, "bytes": 10.0}, peaks)
    assert r == {"seconds": pytest.approx(1.0), "bound": "memory"}
    r = flops.roofline_time({"flops": 500.0, "bytes": 10.0}, peaks)
    assert r == {"seconds": pytest.approx(5.0), "bound": "compute"}
