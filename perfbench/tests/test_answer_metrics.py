"""CPU tests of the per-layer metrics of the answer path (PR 28): each reader
on a made-up ``ctx``, on the recorded traces that hold no decode program
(nothing to read: nothing returned), and the cost function they share."""

import json
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
sys.path.insert(0, BENCH)

import costs  # noqa: E402
import run  # noqa: E402
import trace_reduce  # noqa: E402
from decoders import gpt2 as gpt2_decoder  # noqa: E402

GPT2 = {"decoder": {"vocab_size": 50257, "n_embd": 768, "n_layer": 12, "n_head": 12,
                    "n_inner": 3072, "n_positions": 1024, "layer_norm_epsilon": 1e-5}}
TRACE_READERS = ["decode.device_ms_per_step", "prefill.device_ms_per_launch",
                 "decode_step_roofline"]


def read(metric: str, ctx: dict):
    return run.load_module("layer_metrics", metric).read(ctx)


def facts() -> dict:
    return {"decoder": gpt2_decoder.sizes(GPT2), "decoder_programs": gpt2_decoder.PROGRAMS}


def answered(n: int) -> list[dict]:
    return [{"failed": False} for _ in range(n)] + [{"failed": True}]


def test_gpt2_sizes_and_the_least_bytes_of_a_step():
    sizes = gpt2_decoder.sizes(GPT2)
    # 12 x (4 x 768^2 + 2 x 768 x 3072) + 50,257 x 768
    assert sizes["matrix_params"] == 84934656 + 38597376
    assert sizes["kv_values_per_token"] == 12 * 2 * 768
    # bfloat16: the matrices once, 8 rows of 700 cached positions
    assert costs.decode_step_bytes(123532032, 18432, 8 * 700) == 2 * (123532032 + 18432 * 5600)


def test_tick_wait_of_the_generate_class():
    moved = {"delta": {"runtime.generate.wait_ms_sum": 420.0, "runtime.generate.wait_ms_count": 280}}
    assert read("tick.wait_ms.llm", moved) == pytest.approx(1.5)
    assert read("tick.wait_ms.llm", {"delta": {}}) is None
    assert read("tick.wait_ms.llm", {"delta": {"runtime.generate.wait_ms_sum": 0.0,
                                               "runtime.generate.wait_ms_count": 0}}) is None


def test_retrieval_time_an_answer():
    delta = {"stage.embed.sum": 300.0, "stage.embed.count": 100.0,
             "stage.search.sum": 2700.0, "stage.search.count": 100.0}
    assert read("answer.retrieve_ms", {"delta": delta}) == pytest.approx(30.0)
    assert read("answer.retrieve_ms", {"delta": {}}) is None
    assert read("answer.retrieve_ms", {"delta": {"stage.embed.sum": 0.0,
                                                 "stage.embed.count": 0.0}}) is None


def test_rows_a_launch_and_the_verify_launch_time():
    delta = {'om.pathway_decode_batch_rows_sum{kind="decode_step"}': 300.0,
             'om.pathway_decode_batch_rows_count{kind="decode_step"}': 100.0,
             'om.pathway_decode_batch_rows_sum{kind="verify"}': 8700.0,
             'om.pathway_decode_batch_rows_count{kind="verify"}': 900.0,
             'om.pathway_decode_batch_rows_sum{kind="prefill"}': 5.0,
             'om.pathway_decode_batch_rows_count{kind="prefill"}': 5.0,
             'om.pathway_decode_launch_ms_sum{kind="verify"}': 13500.0,
             'om.pathway_decode_launch_ms_count{kind="verify"}': 900.0}
    assert read("decode.rows_per_step", {"delta": delta}) == pytest.approx(9.0)
    assert read("decode.verify_launch_ms", {"delta": delta}) == pytest.approx(15.0)
    for metric in ("decode.rows_per_step", "decode.verify_launch_ms"):
        assert read(metric, {"delta": {}}) is None


def test_device_time_of_step_and_prefill_reads_the_named_programs_only():
    trace = {"programs": {"jit__paged_step_impl": 0.9, "jit__paged_prefill_impl": 0.06,
                          "jit__paged_multi_step_impl": 4.7, "jit__pallas_fused_dense": 1.0},
             "launches": {"jit__paged_step_impl": 300.0, "jit__paged_prefill_impl": 2.0,
                          "jit__paged_multi_step_impl": 400.0, "jit__pallas_fused_dense": 50.0}}
    ctx = {"trace": trace, "facts": facts()}
    assert read("decode.device_ms_per_step", ctx) == pytest.approx(8.0)  # 5.6 s over 700
    assert read("prefill.device_ms_per_launch", ctx) == pytest.approx(30.0)
    other = {"trace": {"programs": {"jit_pw_encoder_forward": 0.1},
                       "launches": {"jit_pw_encoder_forward": 9.0}}, "facts": facts()}
    for metric in ("decode.device_ms_per_step", "prefill.device_ms_per_launch"):
        assert read(metric, other) is None  # never 0 for a program that did not run
        assert read(metric, {"facts": facts()}) is None  # a run without a trace
        assert read(metric, {"trace": trace, "facts": {}}) is None  # a deployment without a decoder


def test_roofline_and_mfu_against_a_hand_reckoned_window():
    trace = {"programs": {"jit__paged_step_impl": 0.3, "jit__paged_multi_step_impl": 0.6},
             "launches": {"jit__paged_step_impl": 100.0, "jit__paged_multi_step_impl": 200.0}}
    delta = {'om.pathway_decode_batch_rows_sum{kind="decode_step"}': 400.0,
             'om.pathway_decode_batch_rows_count{kind="decode_step"}': 100.0,
             'om.pathway_decode_batch_rows_sum{kind="verify"}': 8600.0,
             'om.pathway_decode_batch_rows_count{kind="verify"}': 900.0}
    ctx = {"trace": trace, "facts": facts(), "delta": delta, "records": answered(100),
           "traffic": {"max_new_tokens": 64}, "seconds": 40.0,
           "peaks": costs.peaks("TPU v5 lite"), "costs": costs,
           "prompt_ids": {i: [0] * 668 for i in range(100)}}  # as checks/answers.py leaves them
    least = 2 * (123532032 + 18432 * 9 * 700)  # bytes: 9 rows of 668 + 32 positions
    assert read("decode_step_roofline", ctx) == pytest.approx(100.0 * (least / 819e9) / 3e-3)
    assert 0 < read("decode_step_roofline", ctx) < 100
    assert read("decode_step_roofline", dict(ctx, peaks=None)) is None
    assert read("decode_step_roofline", dict(ctx, delta={})) is None
    assert read("decode_step_roofline", dict(ctx, trace={"programs": {}, "launches": {}})) is None
    shape = dict(hidden=768, layers=12, ffn=3072, vocab=50257)
    one = (costs.decoder_flops(668, 334, head_tokens=1, **shape)
           + costs.decoder_flops(63, 700, **shape))
    assert read("answer_step.mfu", ctx) == pytest.approx(100.0 * 100 * one / (40.0 * 197e12))
    assert read("answer_step.mfu", dict(ctx, peaks=None)) is None
    assert read("answer_step.mfu", dict(ctx, prompt_ids={})) is None


def test_prompts_are_assembled_once_a_run_from_the_served_contexts(monkeypatch):
    import textgen
    import zlib
    from checks import answers, gpt2

    config = dict(GPT2, payload_texts=8, ingested_passages=2, rows=64)
    config["decoder"] = dict(GPT2["decoder"], builder="gpt2", reference="gpt2")
    traffic = {"min_words": 8, "max_words": 24, "max_new_tokens": 64, "k": 2}
    crc = [zlib.crc32(textgen.passage(j, 7).encode("utf-8")) for j in range(8)]
    records = [{"i": 0, "failed": False, "answer": {"context": [crc[3], crc[2]]}},
               {"i": 1, "failed": True, "answer": "degraded"},
               {"i": 2, "failed": False, "answer": {"context": [crc[0], crc[1]]}}]
    ctx = {"records": records, "config": config, "traffic": traffic, "seed": 7}
    calls, sound = [], gpt2.prompt_ids
    monkeypatch.setattr(gpt2, "prompt_ids", lambda *a: calls.append(1) or sound(*a))
    got = answers.prompt_lengths(ctx)
    texts = textgen.query_texts(3, 7, 8, 24)
    words = [len(answers.prompt_of(texts[i], [textgen.passage(j, 7) for j in js]).split())
             for i, js in ((0, (3, 2)), (2, (0, 1)))]
    assert len(got) == 2 and all(g >= w + 2 for g, w in zip(got, words))  # [CLS] words... [SEP]
    # the two cost readers and the check come after: nothing is tokenised again
    assert answers.prompt_lengths(ctx) == got and sorted(answers.prompt_ids_of(ctx)) == [0, 2]
    assert len(calls) == 2


def test_one_comparison_judges_the_program_and_the_control():
    sound = {"answers_missing": {"value": 0, "limit": 0},
             "logit_gap": {"value": 0.0068, "limit": 0.04},
             "context_shortfall_widest": {"value": 0.9, "limit": None}}  # reported, not held
    assert run.is_correct(sound) is True
    assert run.is_correct(dict(sound, logit_gap={"value": 0.215, "limit": 0.04})) is False
    assert run.is_correct(dict(sound, answers_missing={"value": 1, "limit": 0})) is False


@pytest.mark.parametrize("recorded", ["trace_small.json", "trace_small_pw.json"])
@pytest.mark.parametrize("metric", TRACE_READERS)
def test_readers_on_recorded_traces_without_a_decode_program(metric, recorded):
    """Cuts of retrieve-steady (PR 25) and ingest-live (PR 26) runs: no
    decode session ran, so the decode readers have nothing to read."""
    with open(os.path.join(HERE, recorded)) as f:
        reduced = trace_reduce.reduce(json.load(f)["planes"])
    ctx = {"trace": reduced, "facts": facts(), "delta": {}, "records": [],
           "traffic": {"max_new_tokens": 64}, "peaks": costs.peaks("TPU v5 lite"), "costs": costs}
    assert read(metric, ctx) is None


def test_decode_readers_on_the_recorded_answers_trace():
    """An 80 ms cut of a traced answers-steady run on a TPU v5 lite (PR 28): two single-token
    steps, one multi-token launch and one retrieve tick between them."""
    with open(os.path.join(HERE, "trace_small_answers.json")) as f:
        recorded = json.load(f)
    reduced = trace_reduce.reduce(recorded["planes"])
    for key in ("busy_s", "window_s"):
        assert reduced[key] == pytest.approx(recorded["expect"][key], rel=1e-9)
    assert reduced["launches"]["jit__paged_step_impl"] == 2
    assert reduced["launches"]["jit__paged_multi_step_impl"] == 1
    ctx = {"trace": reduced, "facts": facts()}
    assert read("decode.device_ms_per_step", ctx) == pytest.approx(
        recorded["expect"]["decode.device_ms_per_step"], rel=1e-9)
    assert 5.0 < read("decode.device_ms_per_step", ctx) < 20.0
    assert read("prefill.device_ms_per_launch", ctx) is None  # prompts rode the step programs
    assert reduced["device_ops"][0][0] == "copy"  # the KV pools, copied around every launch
