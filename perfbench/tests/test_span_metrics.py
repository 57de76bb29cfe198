"""CPU tests of the per-layer metrics that read the program's own spans
(PR 26): each reader on a made-up ``ctx``, and ``idle.attributed`` on a cut
of a traced ``ingest-live`` run."""

import json
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
sys.path.insert(0, BENCH)

import run  # noqa: E402
import trace_reduce  # noqa: E402

#: reader -> (stage whose sum and count it divides)
STAGE_MEANS = {
    "connector.scan_ms": "connector.scan",
    "engine.read_to_indexed_ms": "ingest.read_to_indexed",
    "tick.run_ms.ingest": "tick.run",
    "embed.tokenize_ms": "embed.tokenize",
    "embed.launch_ms": "embed.launch",
    "embed.d2h_wait_ms": "embed.d2h_wait",
    "index.apply_ms": "index.apply",
}


def read(metric: str, ctx: dict):
    return run.load_module("layer_metrics", metric).read(ctx)


@pytest.mark.parametrize("metric", sorted(STAGE_MEANS))
def test_stage_mean_readers(metric):
    stage = STAGE_MEANS[metric]
    moved = {"delta": {f"stage.{stage}.sum": 90.0, f"stage.{stage}.count": 60.0}}
    assert read(metric, moved) == pytest.approx(1.5)
    still = {"delta": {f"stage.{stage}.sum": 0.0, f"stage.{stage}.count": 0.0}}
    assert read(metric, still) is None
    assert read(metric, {"delta": {}}) is None  # a program without the span


def test_per_document_and_per_scan_readers():
    delta = {"index.live_rows": 1920, "stage.connector.scan.count": 160.0,
             "stage.engine.flush.sum": 30720.0, "stage.engine.flush.count": 4000.0}
    assert read("connector.files_per_scan", {"delta": delta}) == pytest.approx(12.0)
    assert read("engine.flush_ms_per_doc", {"delta": delta}) == pytest.approx(16.0)
    for quiet in ({}, {"index.live_rows": 0, "stage.connector.scan.count": 0.0,
                       "stage.engine.flush.sum": 0.0, "stage.engine.flush.count": 0.0}):
        assert read("connector.files_per_scan", {"delta": quiet}) is None
        assert read("engine.flush_ms_per_doc", {"delta": quiet}) is None
    # flushes ran (an idle engine still steps) but nothing became queryable
    assert read("engine.flush_ms_per_doc", {"delta": {
        "index.live_rows": 0, "stage.engine.flush.sum": 3.0,
        "stage.engine.flush.count": 9.0}}) is None


def test_encoder_device_time_reads_the_named_programs_only():
    trace = {"programs": {"jit_pw_encoder_forward": 0.012, "jit_pw_encoder_forward_ragged": 0.004,
                          "jit__pallas_fused_dense": 2.0},
             "launches": {"jit_pw_encoder_forward": 190.0, "jit_pw_encoder_forward_ragged": 10.0,
                          "jit__pallas_fused_dense": 100.0}}
    assert read("encoder.device_ms_per_launch", {"trace": trace}) == pytest.approx(0.08)
    parent = {"programs": {"jit__forward": 0.012}, "launches": {"jit__forward": 190.0}}
    assert read("encoder.device_ms_per_launch", {"trace": parent}) is None
    assert read("encoder.device_ms_per_launch", {}) is None  # a run without a trace


def test_idle_attributed_on_made_up_gaps():
    gaps = [["pw.runtime.tick.idle [pw-tick]", 3.0], ["np.asarray(jax.Array) [pw-tick]", 0.5],
            ["pw.connector.connector.sleep [pw-conn-0]", 0.25], ["shard_args [python3]", 0.25]]
    assert read("idle.attributed", {"trace": {"idle_gaps": gaps}}) == pytest.approx(93.75)
    unnamed = [["np.asarray(jax.Array) [python3]", 3.68], ["no host span", 0.02]]
    assert read("idle.attributed", {"trace": {"idle_gaps": unnamed}}) is None
    assert read("idle.attributed", {"trace": {"idle_gaps": []}}) is None
    assert read("idle.attributed", {}) is None


def test_idle_attributed_on_the_recorded_trace():
    """A cut of a traced ingest-live run on a TPU v5 lite (PR 26): the
    program's threads carry their names and its spans cover the gaps."""
    with open(os.path.join(HERE, "trace_small_pw.json")) as f:
        recorded = json.load(f)
    reduced = trace_reduce.reduce(recorded["planes"])
    label, seconds = reduced["idle_gaps"][0]
    assert label.startswith("pw.") and "[pw-" in label
    assert seconds > 0.9 * (reduced["window_s"] - reduced["busy_s"])
    share = read("idle.attributed", {"trace": reduced})
    assert share == pytest.approx(recorded["expect"]["idle.attributed"], rel=1e-9)
    assert 99.0 < share <= 100.0  # the rest: microsecond gaps inside a launch
    assert read("encoder.device_ms_per_launch", {"trace": reduced}) == pytest.approx(
        recorded["expect"]["encoder.device_ms_per_launch"], rel=1e-9)
    threads = {t for t in recorded["planes"]["host"] if t.startswith("pw-")}
    assert len(threads) >= 3
    for thread in threads:
        assert any(name.startswith("pw.") for name, _s, _d in recorded["planes"]["host"][thread])
