"""The benchmark's operation and byte counts against hand-computed goldens
for qwen1.5-4b decode, chunk and prefill steps."""

import json

import pytest

from bench import harness


def _module(name):
    cfg = json.loads((harness.BENCH / "configs" / f"{name}.json").read_text())
    return cfg, harness.load_module(harness.BENCH / "configs" / f"{name}.py",
                                    name)


def test_qwen_decode_step_costs():
    cfg, mod = _module("qwen1.5-4b")
    # 8 rows, two live slots attending 100 and 130 keys, 64-token pages
    c = mod.step_costs(cfg, {"which": "decode", "rows": 8, "page": 64,
                             "keys": [100, 130]})
    d, ff, v = 2560, 6912, 151936
    # per layer: q, k, v (2560 x 2560, biased), o, gate, up, down
    layer_kn = 4 * d * d + 3 * d * ff                    # 79,298,560
    proj = 40 * layer_kn                                 # 3,171,942,400
    gemm_flops = 2 * 8 * (proj + d * v)
    assert sum(f for f, _ in c["gemm"]) == pytest.approx(gemm_flops)
    # bytes: weights once, 8 rows in and out, biases, all bf16
    w = proj + d * v
    # in: q,k,v,gate,up read 8 x 2560, o reads 8 x 2560, down reads 8 x ff;
    # out: q,k,v,o,down write 8 x 2560, gate and up write 8 x ff
    acts = 40 * (8 * d * 6 + 8 * ff + 8 * d * 5 + 8 * ff * 2) \
        + 8 * d + 8 * v
    bias = 40 * 3 * d
    assert sum(b for _, b in c["gemm"]) == pytest.approx(
        2 * (w + acts + bias))
    # paged attention: 4 * keys * heads * head_dim per layer; whole pages
    # of k and v (2 and 3 pages of 64) read, 20 heads x 128, bf16
    (af, ab), = c["paged_attn"]
    assert af == pytest.approx(40 * 4 * (100 + 130) * 20 * 128)
    assert ab == pytest.approx(40 * 2 * 2 * (2 + 3) * 64 * 20 * 128)
    # the model: two live tokens through every projection, attention over
    # their context, and two sampled rows of the head
    assert c["model"] == pytest.approx(
        2 * 2 * proj + 40 * 4 * 20 * 128 * 230 + 2 * 2 * d * v)


def test_prefill_without_logits_skips_the_head():
    cfg, mod = _module("qwen1.5-4b")
    c = mod.step_costs(cfg, {"which": "prefill_nl", "rows": 64, "page": 64,
                             "start": 0, "tokens": 64})
    d, ff = 2560, 6912
    proj = 40 * (4 * d * d + 3 * d * ff)
    # positions 0..63 attend 1..64 keys; nothing is sampled
    assert c["model"] == pytest.approx(64 * 2 * proj
                                       + 40 * 4 * 20 * 128 * sum(range(1, 65)))
    assert sum(f for f, _ in c["gemm"]) == pytest.approx(2 * 64 * proj)
    assert "paged_attn" not in c


def test_chunk_costs_count_true_tokens_and_one_sampled_row():
    cfg, mod = _module("qwen1.5-4b")
    c = mod.step_costs(cfg, {"which": "chunk", "rows": 64, "page": 64,
                             "start": 128, "tokens": 40})
    d, ff, v = 2560, 6912, 151936
    proj = 40 * (4 * d * d + 3 * d * ff)
    # positions 128..167 attend 129..168 keys
    keys = sum(range(129, 169))
    assert c["model"] == pytest.approx(40 * 2 * proj
                                       + 40 * 4 * 20 * 128 * keys
                                       + 2 * d * v)
    # the GEMMs run all 64 rows, and the last chunk unembeds all of them
    assert sum(f for f, _ in c["gemm"]) == pytest.approx(
        2 * 64 * (proj + d * v))
