"""CPU tests of the seven readers of a batch's segments (ISSUE 38): each
divides its stage's sum by its count, as ``test_span_metrics.py``'s
``STAGE_MEANS`` readers do (that file is the accepted benchmark's, so the
seven are parametrised here), and together they add up to
``engine.read_to_indexed_ms``."""

import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))

import run  # noqa: E402

#: reader -> (stage whose sum and count it divides)
SEGMENT_MEANS = {
    "ingest.read_to_commit_ms": "ingest.read_to_commit",
    "ingest.commit_to_step_ms": "ingest.commit_to_step",
    "ingest.step_to_index_ms": "ingest.step_to_index",
    "ingest.index_to_tick_ms": "ingest.index_to_tick",
    "ingest.tick_ms": "ingest.tick",
    "ingest.tick_to_embedded_ms": "ingest.tick_to_embedded",
    "ingest.embedded_to_indexed_ms": "ingest.embedded_to_indexed",
}


def read(metric: str, ctx: dict):
    return run.load_module("layer_metrics", metric).read(ctx)


@pytest.mark.parametrize("metric", sorted(SEGMENT_MEANS))
def test_segment_mean_readers(metric):
    stage = SEGMENT_MEANS[metric]
    moved = {"delta": {f"stage.{stage}.sum": 90.0, f"stage.{stage}.count": 60.0}}
    assert read(metric, moved) == pytest.approx(1.5)
    still = {"delta": {f"stage.{stage}.sum": 0.0, f"stage.{stage}.count": 0.0}}
    assert read(metric, still) is None
    assert read(metric, {"delta": {}}) is None  # the parent: no such stage


def test_segments_add_up_to_read_to_indexed():
    sums = [3.0, 1.0, 210.0, 60.0, 150.0, 5.0, 2.0]
    delta = {"stage.ingest.read_to_indexed.sum": sum(sums),
             "stage.ingest.read_to_indexed.count": 10.0}
    for (metric, stage), s in zip(SEGMENT_MEANS.items(), sums):
        delta[f"stage.{stage}.sum"] = s
        delta[f"stage.{stage}.count"] = 10.0
    ctx = {"delta": delta}
    parts = sum(read(metric, ctx) for metric in SEGMENT_MEANS)
    assert parts == pytest.approx(read("engine.read_to_indexed_ms", ctx))
