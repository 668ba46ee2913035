"""Operations, bytes and peaks against sums worked out by hand: the peaks
table and the least time of a launch (``chipbench.roofline``), and the
dense-GQA layout's counts of a served step's work
(``layouts/dense_gqa.py``)."""

import json

import pytest

from chipbench import harness, roofline
from chipbench_testkit import BENCH_DIR

GQA = harness.load_module(BENCH_DIR, "layouts", "dense_gqa")


def cfg(name):
    return json.loads((BENCH_DIR / "configs" / f"{name}.json").read_text())


QWEN = cfg("qwen2-0.5b")
# A second shape of the same arithmetic, with no bias and an untied head:
# the sizes one chip of four would hold of internlm2-20b's first 8 layers
# under tensor parallelism of its attention heads and vocabulary.
INTERN = {"hidden_size": 6144, "intermediate_size": 16384,
          "num_hidden_layers": 8, "num_attention_heads": 12,
          "num_key_value_heads": 2, "head_dim": 128, "vocab_size": 23136}


def test_peaks_table_and_unknown_device():
    p = roofline.peaks("TPU v5 lite")
    assert (p.flops, p.hbm_bytes_s, p.hbm_bytes) == (197e12, 819e9, 16e9)
    assert "TPU v5e" in p.source
    with pytest.raises(KeyError, match="no peaks"):
        roofline.peaks("cpu")


def test_model_flops_per_token_by_hand():
    # qwen2-0.5b, per layer: 896 * (14 + 2 * 2) * 64 = 1,032,192 (q, k, v)
    # + 14 * 64 * 896 = 802,816 (o) + 3 * 896 * 4864 = 13,074,432 (MLP)
    # = 14,909,440; 24 layers = 357,826,560; head 896 * 151,936 =
    # 136,134,656; attention 24 * 4 * 14 * 64 = 86,016 per key
    assert GQA.matmul_params(QWEN) == 493_961_216
    assert GQA.token_flops(QWEN, 1000) == 987_922_432 + 86_016_000
    # internlm2-20b share, per layer: 6144 * (12 + 2 * 2) * 128 =
    # 12,582,912 + 1536 * 6144 = 9,437,184 + 3 * 6144 * 16384 =
    # 301,989,888 = 324,009,984; 8 layers = 2,592,079,872; head
    # 6144 * 23136 = 142,147,584; attention 8 * 4 * 12 * 128 = 49,152
    assert GQA.matmul_params(INTERN) == 2_734_227_456
    assert GQA.token_flops(INTERN, 300) == 5_468_454_912 + 49_152 * 300
    assert GQA.matmul_params(INTERN, head=False) == 2_592_079_872


def test_prefill_chunk_flops_by_hand():
    # 3 tokens from position 10: keys 11 + 12 + 13 = 36
    want = 2 * 2_592_079_872 * 3 + 8 * 4 * 36 * 12 * 128
    assert GQA.chunk_flops(INTERN, 10, 3) == want


def test_decode_launch_by_hand():
    # qwen2 page: 16 tokens * 2 KV heads * 64 * 2 B = 4096 B; contexts 17
    # and 32 hold 2 pages each, read as K and V: 2 * 4 * 4096 = 32,768;
    # queries and outputs 2 * 2 * 14 * 64 * 2 B = 7,168
    w = GQA.decode_attention(QWEN, 16, [17, 32])
    assert w.flops == 4 * (17 + 32) * 14 * 64
    assert w.bytes == 32_768 + 7_168
    # internlm2 share page: 16 * 2 * 128 * 2 B = 8192 B; context 1 -> 1 page
    w = GQA.decode_attention(INTERN, 16, [1])
    assert (w.flops, w.bytes) == (4 * 12 * 128, 2 * 8192 + 2 * 12 * 128 * 2)


def test_prefill_launch_by_hand():
    # chunks (start 0, 16 tokens): keys 16 * 17 / 2 = 136; (100, 28):
    # 28 * 100 + 28 * 29 / 2 = 3206. Pages: 1 and 8, as K and V:
    # 2 * 9 * 4096 = 73,728; queries and outputs 44 * 2 * 896 * 2 = 157,696
    w = GQA.prefill_attention(QWEN, 16, [(0, 16), (100, 28)])
    assert w.flops == 4 * 3342 * 14 * 64
    assert w.bytes == 73_728 + 157_696


def test_roofline_seconds_take_the_binding_bound():
    p = roofline.peaks("TPU v5 lite")
    w = roofline.Work(flops=197e12, bytes=819e9 / 2)
    assert w.seconds(p) == 1.0
    w = roofline.Work(flops=1.0, bytes=819e9 * 3)
    assert w.seconds(p) == 3.0
    launches = [roofline.Work(0.0, 819e9), roofline.Work(197e12 * 2, 0.0)]
    assert roofline.launches_seconds(INTERN, launches, p) == 8 * (1.0 + 2.0)
