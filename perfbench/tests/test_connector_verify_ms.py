"""CPU test of ``connector.verify_ms`` (PR 35) on a made-up ``ctx``, as
``test_span_metrics.py`` tests the other readers of a stage's mean."""

import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))

import run  # noqa: E402


def read(ctx: dict):
    return run.load_module("layer_metrics", "connector.verify_ms").read(ctx)


def test_verify_ms_is_the_stage_mean():
    # 134 polls of 0.3 s in a 40-s window, each with a second pass
    moved = {"delta": {"stage.connector.verify.sum": 12060.0,
                       "stage.connector.verify.count": 134.0,
                       "stage.connector.scan.sum": 900.0,
                       "stage.connector.scan.count": 134.0}}
    assert read(moved) == pytest.approx(90.0)


@pytest.mark.parametrize("delta", [
    {},  # a program without the span: the parent commit
    {"stage.connector.verify.sum": 0.0, "stage.connector.verify.count": 0.0},
    {"stage.connector.scan.sum": 900.0, "stage.connector.scan.count": 134.0},
], ids=["no_span", "no_poll", "first_pass_only"])
def test_verify_ms_reads_nothing_where_no_second_pass_ran(delta):
    assert read({"delta": delta}) is None
