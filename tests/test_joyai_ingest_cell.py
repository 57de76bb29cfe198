"""The benchmark's cell ``ingest-docs-joyai`` rehearsed end to end on the CPU
at the configuration file's tiny preset (``perfbench/run.py --rehearse``): the
latent-attention embedder behind ``SentenceEncoder`` ->
``SentenceTransformerEmbedder`` -> ``VectorStoreServer`` over a watched
directory, the tick runtime, the packed dispatch, ``ExternalIndexNode.flush``,
the staged upsert, and the check that decides ``correct``."""

from __future__ import annotations

import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "perfbench")
CELL = "ingest-docs-joyai"
SEED = 2147483659


def _rehearse(trace: int) -> dict:
    out = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload", CELL,
         "--seed", str(SEED), "--seconds", "3", "--trace", str(trace), "--rehearse"],
        env=dict(os.environ, JAX_PLATFORMS="cpu"), capture_output=True, text=True, timeout=900)
    assert out.returncode == 0, out.stderr[-3000:]
    return json.loads(out.stdout.strip().splitlines()[-1])


def test_the_cell_rehearses_to_a_correct_result_with_its_two_end_to_end_metrics():
    line = _rehearse(trace=0)
    assert line["correct"] is True and line["failed"] == 0 and line["attempted"] == 12
    assert line["device"]["platform"] == "cpu"  # a CPU run cannot pass for a chip run
    assert set(line["metrics"]) == {"fresh_p95_ms", "setup_s"}
    assert line["metrics"]["fresh_p95_ms"]["value"] > 0
    compared = line["compared"]
    for name in ("files_not_counted", "files_lost", "files_doubled", "own_text_not_first_once"):
        assert compared[name] == {"value": 0, "limit": 0}
    assert compared["answers_compared"]["value"] == 6
    assert compared["score_gap"]["value"] < compared["score_gap"]["limit"] < 1e30
    # what tells the stated precision from the one below it: the program's layers fed
    # the stated-precision reference's own input differ by the order of float32 sums
    assert compared["layer_gap"]["value"] < 1e-5 < compared["layer_gap"]["limit"]


def test_a_traced_rehearsal_prints_the_launch_counters_and_no_device_metric():
    line = _rehearse(trace=1)
    assert line["correct"] is True
    metrics = line["metrics"]
    # counters are counts on any platform; times, shares of a peak and
    # rooflines come from a chip alone, and their readers return nothing here
    assert metrics["embed.docs_per_launch"]["value"] >= 1.0  # a flush shares one packed launch
    assert 0.0 <= metrics["mla.padding_share"]["value"] < 100.0
    # documents of 5 to 64 tokens: a query sees (L + 1) / 2 keys of its own document
    assert 3.0 <= metrics["mla.keys_per_query"]["value"] <= 32.5
    assert metrics["moe.tokens_per_expert"]["value"] > 0
    assert metrics["ingest.rows_per_tick"]["value"] > 0
    for name in ("embed_moe.device_ms_per_launch", "moe.grouped_matmul_roofline",
                 "ingest_mla_step.mfu", "idle.attributed", "ssm.docs_per_launch",
                 "ingest_moe_step.mfu"):
        assert name not in metrics


@pytest.fixture(scope="module")
def bench() -> dict:
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def test_the_cell_is_declared_with_its_configuration_traffic_and_metrics(bench):
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert cell["chips"] == 1 and len(cell["why"]) <= 200
    entry = next(c for c in bench["configs"] if c["name"] == cell["config"])
    assert entry["reduced"] == ["num_hidden_layers", "rows"] and len(entry["why"]) <= 200
    assert entry["source"] == (
        "https://huggingface.co/jdopensource/JoyAI-LLM-Flash/blob/main/config.json")
    with open(os.path.join(ROOT, entry["file"])) as f:
        config = json.load(f)
    with open(os.path.join(BENCH, "traffic", cell["traffic"] + ".json")) as f:
        traffic = json.load(f)
    # the published widths, uncut, and the cut that is stated
    published = {
        "hidden_size": 2048, "intermediate_size": 7168, "moe_intermediate_size": 768,
        "num_attention_heads": 32, "num_key_value_heads": 32, "q_lora_rank": 1536,
        "kv_lora_rank": 512, "qk_nope_head_dim": 128, "qk_rope_head_dim": 64,
        "qk_head_dim": 192, "v_head_dim": 128, "head_dim": 64, "n_routed_experts": 256,
        "num_experts_per_tok": 8, "n_shared_experts": 1, "n_group": 1, "topk_group": 1,
        "first_k_dense_replace": 1, "moe_layer_freq": 1, "vocab_size": 129280,
        "rope_theta": 32000000, "rope_interleave": True, "rope_scaling": None,
        "scoring_func": "sigmoid", "topk_method": "noaux_tc", "norm_topk_prob": True,
        "routed_scaling_factor": 2.5, "hidden_act": "silu", "rms_norm_eps": 1e-06,
        "max_position_embeddings": 131072, "attention_bias": False, "ep_size": 1,
        "tie_word_embeddings": False, "num_nextn_predict_layers": 1,
        "model_type": "joyai_llm_flash"}
    assert {k: config[k] for k in published} == published
    assert config["num_hidden_layers"] == 5 and config["published_num_hidden_layers"] == 40
    assert config["reduced"] == ["num_hidden_layers", "rows"] and config["why_reduced"]
    assert config["rows"] == 61440 and config["index"]["capacity"] == 65536
    assert config["index"]["dim"] == config["hidden_size"] and len(config["guarantees"]) == 3
    assert (config["server"], config["check"]) == ("vector_store_joyai", "ingest_laguna")
    assert config["embedder"] == {"builder": "joyai", "reference": "joyai"}
    assert len(config["token_buckets"]) == 4  # four programs: each loads in set-up
    assert "eight pipeline stages" in config["deployment"]
    assert traffic["generator"] == "file_drop_docs" and traffic["poll_ms"] == 10
    assert traffic["rate_per_s"] == int(traffic["rate_per_s"]) > 0
    reported = {m["name"] for group in ("end_to_end", "per_layer") for m in bench[group]
                if "workloads" not in m or CELL in m["workloads"]}
    assert {"fresh_p95_ms", "setup_s", "ingest_mla_step.mfu", "mla.keys_per_query",
            "mla.padding_share", "moe.grouped_matmul_roofline", "moe.tokens_per_expert",
            "embed.docs_per_launch", "embed_moe.device_ms_per_launch",
            "idle.attributed"} <= reported
    assert "ingest_moe_step.mfu" not in reported  # it counts grouped-query attention
    for name in reported - {"setup_s"}:  # every metric the cell reports has its reader
        kind = "end_to_end" if name == "fresh_p95_ms" else "layer_metrics"
        assert os.path.exists(os.path.join(BENCH, kind, name + ".py")), name
    # the latent family's counters are also laid out by Kimi-Linear's forward
    # (it has a latent layer), so its cell reads the two counter readers too
    for name, cells in (("ingest_mla_step.mfu", [CELL]),
                        ("mla.keys_per_query", [CELL, "ingest-docs-kimi-linear"]),
                        ("mla.padding_share", [CELL, "ingest-docs-kimi-linear"])):
        metric = next(m for m in bench["per_layer"] if m["name"] == name)
        assert metric["workloads"] == cells and metric["moves"] == "fresh_p95_ms"
