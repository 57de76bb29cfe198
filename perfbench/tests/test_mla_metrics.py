"""CPU tests of the latent-attention cell's three readers (PR 36), each on a
small hand-made ``ctx``: what a window of launches reads, and nothing where the
program counts no such launches (the parent commit, another model's cell)."""

import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
sys.path.insert(0, BENCH)

import costs_joyai  # noqa: E402
import run  # noqa: E402

SIZES = {"hidden": 2048, "heads": 32, "q_rank": 1536, "kv_rank": 512, "nope": 128, "rope": 64,
         "v_dim": 128, "mlp_types": ["dense", "sparse", "sparse", "sparse", "sparse"],
         "dense_ffn": 7168, "experts": 256, "top_k": 8, "expert_ffn": 768, "shared_ffn": 768,
         "vocab": 129280}


def read(metric: str, ctx: dict):
    return run.load_module("layer_metrics", metric).read(ctx)


def test_keys_per_query_is_pairs_over_real_tokens():
    # two documents of 96 and 2,048 tokens in one launch of 3,072
    pairs = 96 * 97 // 2 + 2048 * 2049 // 2
    delta = {"mla.launches_total": 1, "mla.documents_total": 2, "mla.tokens_total": 2144,
             "mla.bucket_tokens_total": 3072, "mla.attention_pairs_total": pairs}
    assert read("mla.keys_per_query", {"delta": delta}) == pytest.approx(pairs / 2144)
    assert read("mla.padding_share", {"delta": delta}) == pytest.approx(100 * (1 - 2144 / 3072))
    for quiet in ({}, {"mla.tokens_total": 0, "mla.bucket_tokens_total": 0,
                       "mla.attention_pairs_total": 0}, {"ssm.tokens_total": 5}):
        assert read("mla.keys_per_query", {"delta": quiet}) is None
        assert read("mla.padding_share", {"delta": quiet}) is None


def test_the_whole_step_s_share_counts_the_files_made_queryable():
    words = [94, 190, 382, 766, 1534, 2046]
    records = [{"failed": False, "answer": {"passage": 3}},   # 766 words: 768 tokens
               {"failed": False, "answer": {"passage": 11}},  # 2,046 words: 2,048 tokens
               {"failed": True, "answer": {"passage": 5}}]
    peaks = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    ctx = {"facts": {"encoder": SIZES, "document_words": words}, "records": records,
           "seconds": 40.0, "peaks": peaks}
    flops = costs_joyai.forward_flops(768, SIZES) + costs_joyai.forward_flops(2048, SIZES)
    assert read("ingest_mla_step.mfu", ctx) == pytest.approx(100 * flops / (40.0 * 197e12))
    assert 0 < read("ingest_mla_step.mfu", ctx) < 100
    assert read("ingest_mla_step.mfu", dict(ctx, peaks=None)) is None  # off the chip
    other = dict(ctx, facts={"encoder": {"hidden": 2048, "ssm_heads": 32},
                             "document_words": words})
    assert read("ingest_mla_step.mfu", other) is None  # another model's cell
    assert read("ingest_mla_step.mfu", dict(ctx, records=[])) is None


def test_a_token_s_products_are_770_mflop_with_its_document_s_pairs():
    """ISSUE 36's count: 26.3M of a sparse layer's 69.3M multiply-adds a token
    outside attention's pairs, 10,240 a pair and layer."""
    sparse = costs_joyai.attention_params(SIZES) + 2048 * 256 + 9 * costs_joyai.expert_params(SIZES)
    assert (costs_joyai.attention_params(SIZES), sparse) == (26_345_472, 69_337_088)
    assert costs_joyai.attention_flops(1, SIZES) == 5 * 2 * 32 * (192 + 128)
    a_token = costs_joyai.forward_flops(2048, SIZES) / 2048
    assert a_token == pytest.approx(2 * costs_joyai.active_params(SIZES) + 1024.5 * 102_400)
