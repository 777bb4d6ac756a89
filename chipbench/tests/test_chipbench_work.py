"""The work counts behind decode_hbm_roofline and decode_mfu, by hand."""
import pytest

import smoke
from cb.work import ModelShape, decode_step_work

GQA = ModelShape.from_dict("m", smoke.MOONSHOT | {"n_experts": 8})
MLA = ModelShape.from_dict("c", smoke.MINICPM3)


def test_gqa_moe_decode_step_by_hand():
    # d 64, 4 heads x 16 (4 KV heads), 8 experts of 32 (top-2), vocab 256,
    # 2 layers, bf16 weights, f32 router
    ctx = [10, 20]
    attn = 64 * 64 + 2 * 64 * 64 + 64 * 64              # wq, wk, wv, wo
    expert = 3 * 64 * 32
    distinct = 8 * (1 - (1 - 2 / 8) ** 2)                 # 3.5 experts
    per_layer = attn * 2 + 2 * 64 * 2 + 64 * 8 * 4 + distinct * expert * 2
    kv_tok = 2 * 4 * 16 * 2                               # k and v, bf16
    kv = (11 + 21) * kv_tok + 2 * kv_tok                  # read + write
    want_bytes = 2 * (per_layer + kv) + 64 * 2 + 64 * 256 * 2 + 2 * 64 * 2
    flops_tok = lambda c: 2 * (2 * attn + 4 * 4 * 16 * (c + 1)  # noqa: E731
                               + 2 * 64 * 8 + 2 * 2 * expert) + 2 * 64 * 256
    nb, nf = GQA.decode_step(ctx)
    assert nb == pytest.approx(want_bytes)
    assert nf == pytest.approx(flops_tok(10) + flops_tok(20))


def test_mla_dense_decode_step_by_hand():
    # d 64, 4 heads, q rank 32, kv rank 16, nope 16, rope 8, v 16, d_ff 128
    ctx = [7]
    attn = (64 * 32 + 32 * 4 * 24 + 32 + 64 * 24 + 16 + 16 * 4 * 16
            + 16 * 4 * 16 + 4 * 16 * 64)
    ffn = 3 * 64 * 128
    kv_tok = (16 + 8) * 2
    want_bytes = (2 * ((attn + 2 * 64 + ffn) * 2 + 8 * kv_tok + kv_tok)
                  + 64 * 2 + 64 * 256 * 2 + 64 * 2)
    want_flops = (2 * (2 * attn + 2 * 4 * 24 * 8 + 2 * 4 * 16 * 8
                       + 2 * ffn) + 2 * 64 * 256)
    nb, nf = MLA.decode_step(ctx)
    assert nb == pytest.approx(want_bytes)
    assert nf == pytest.approx(want_flops)


def test_counts_ignore_the_implementation():
    """An empty batch costs nothing; work grows with the context of the
    requests, not with any pool or held-expert size."""
    assert GQA.decode_step([]) == (0.0, 0.0)
    short = decode_step_work({"m": GQA, "c": MLA}, {"m": [5], "c": [5]})
    long = decode_step_work({"m": GQA, "c": MLA}, {"m": [50], "c": [50]})
    assert long[0] > short[0] and long[1] > short[1]
    assert GQA.distinct_experts(1) == pytest.approx(2.0)
    assert GQA.distinct_experts(10**6) == pytest.approx(8.0)
