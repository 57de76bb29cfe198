"""CPU test of ``connector.period_ms`` (PR 37) on a made-up ``ctx``, as
``test_connector_verify_ms.py`` tests PR 35's reader, and of the metric's
entry in ``BENCHMARK.json``."""

import json
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))

import run  # noqa: E402


def read(ctx: dict):
    return run.load_module("layer_metrics", "connector.period_ms").read(ctx)


def test_period_ms_is_the_stage_mean():
    # 187 listing passes begun 214 ms apart in a 40-s window, 133 rounds beside them
    moved = {"delta": {"stage.connector.period.sum": 40018.0,
                       "stage.connector.period.count": 187.0,
                       "stage.connector.verify.sum": 12901.0,
                       "stage.connector.verify.count": 133.0}}
    assert read(moved) == pytest.approx(214.0)


@pytest.mark.parametrize("delta", [
    {},  # a program that does not observe it: the parent commit
    {"stage.connector.period.sum": 0.0, "stage.connector.period.count": 0.0},
    {"stage.connector.verify.sum": 12060.0, "stage.connector.verify.count": 134.0},
], ids=["no_observation", "no_pass", "rounds_only"])
def test_period_ms_reads_nothing_where_no_listing_pass_was_observed(delta):
    assert read({"delta": delta}) is None


def test_period_ms_is_a_metric_of_every_ingest_cell():
    with open(os.path.join(os.path.dirname(os.path.dirname(HERE)), "BENCHMARK.json")) as f:
        bench = json.load(f)
    by_name = {m["name"]: m for m in bench["per_layer"]}
    entry, verify = by_name["connector.period_ms"], by_name["connector.verify_ms"]
    cells = [m["workloads"] for m in bench["end_to_end"] if m["name"] == "fresh_p95_ms"][0]
    assert entry == {"name": "connector.period_ms", "unit": "ms", "better": "lower",
                     "source": "program_span", "layer": "connector and engine",
                     "moves": "fresh_p95_ms", "workloads": cells}
    assert (verify["layer"], verify["workloads"]) == (entry["layer"], entry["workloads"])
    assert entry in run.metrics_of(bench, bench["workloads"][0], "per_layer")
