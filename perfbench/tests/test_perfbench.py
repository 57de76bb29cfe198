"""CPU tests of the benchmark's own code (``python -m pytest perfbench/tests -q``).

No TPU topology call at import; every run here is ``--rehearse`` at the tiny
sizes of the configuration's ``rehearse`` group, and prints the real platform.
"""

import http.server
import json
import os
import subprocess
import sys
import threading

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, BENCH)

import costs  # noqa: E402
import textgen  # noqa: E402
import trace_reduce  # noqa: E402

ENV = dict(os.environ, JAX_PLATFORMS="cpu")
RESULT_KEYS = ["correct", "attempted", "failed", "metrics", "device", "compared"]


def benches() -> list[dict]:
    """BENCHMARK.json and the cells that are built but not admitted."""
    out = []
    for path in (os.path.join(ROOT, "BENCHMARK.json"), os.path.join(BENCH, "staged.json")):
        with open(path) as f:
            out.append(json.load(f))
    return out


def cells() -> list[str]:
    return [w["name"] for b in benches() for w in b["workloads"]]


def bench_of(cell: str) -> dict:
    return next(b for b in benches() if any(w["name"] == cell for w in b["workloads"]))


def rehearse(cell: str, fault: str = "none", trace: int = 0) -> dict:
    out = subprocess.run(
        [sys.executable, os.path.join(HERE, "faulty_run.py"), fault, "--workload", cell,
         "--seed", "2147483659", "--seconds", "3", "--trace", str(trace), "--rehearse"],
        env=ENV, capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    return json.loads(out.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("cell", cells())
def test_rehearsal_prints_the_contracts_line_and_the_real_platform(cell):
    line = rehearse(cell)
    assert list(line) == RESULT_KEYS
    assert line["correct"] is True and line["failed"] == 0 and line["attempted"] > 0
    assert line["device"]["platform"] == "cpu"  # a CPU run cannot pass for a chip run
    assert set(line["device"]) == {"platform", "kind", "count", "memory_peak_bytes"}
    bench = bench_of(cell)
    wanted = {m["name"] for m in bench["end_to_end"]
              if "workloads" not in m or cell in m["workloads"]}
    assert set(line["metrics"]) == wanted
    assert all(v["value"] > 0 for v in line["metrics"].values())
    assert list(line)[-1] == "compared" and all(
        set(c) == {"value", "limit"} for c in line["compared"].values())


@pytest.mark.parametrize("fault", ["scores", "half"])
@pytest.mark.parametrize("cell", [c for c in cells() if c.startswith("retrieve")])
def test_a_broken_timed_path_comes_out_not_correct(cell, fault):
    line = rehearse(cell, fault)
    assert line["correct"] is False
    over = [n for n, c in line["compared"].items()
            if c["limit"] is not None and c["value"] > c["limit"]]
    assert over == (["score_gap"] if fault == "scores" else ["rank_shortfall"])


@pytest.mark.parametrize("fault,number", [("scores", "score_gap"), ("lose", "files_not_counted")])
@pytest.mark.parametrize("cell", [c for c in cells() if c.startswith("ingest")])
def test_a_broken_ingest_path_comes_out_not_correct(cell, fault, number):
    line = rehearse(cell, fault)
    assert line["correct"] is False
    assert line["compared"][number]["value"] > line["compared"][number]["limit"]


@pytest.mark.parametrize("fault,number", [("token", "logit_gap"), ("half", "context_shortfall")])
@pytest.mark.parametrize("cell", [c for c in cells() if c.startswith("answers")])
def test_a_broken_answer_path_comes_out_not_correct(cell, fault, number):
    line = rehearse(cell, fault)
    assert line["correct"] is False
    assert line["compared"][number]["value"] > line["compared"][number]["limit"]


def test_run_without_a_tpu_exits_nonzero_and_prints_no_result():
    out = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload", cells()[0],
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        env=ENV, capture_output=True, text=True, timeout=300)
    assert out.returncode != 0 and out.stdout.strip() == ""


@pytest.mark.parametrize("cell", cells())
def test_the_control_comes_out_not_correct_at_test_size(cell):
    out = subprocess.run(
        [sys.executable, os.path.join(BENCH, "control.py"), "--workload", cell,
         "--seeds", "5,6,7", "--rehearse"], env=ENV, capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]  # 1: some seed's control passed
    config = next(w["config"] for w in bench_of(cell)["workloads"] if w["name"] == cell)
    with open(os.path.join(BENCH, "configs", config + ".json")) as f:
        limits = json.load(f)["rehearse"]["limits_ingest" if cell.startswith("ingest") else "limits"]
    readings = [json.loads(l.split(" ", 1)[1]) for l in out.stdout.splitlines()
                if l.startswith("perfbench-control ")]
    assert len(readings) == 3
    for r in readings:  # the control has to fail one of the cell's numbers, by run.py's rule
        assert r["correct"] is False
        assert all(r["compared"][n]["limit"] == limits[n] for n in limits if n in r)
        assert any(r[n] > limits[n] for n in limits if n in r and limits[n] > 0)


def test_schedule_is_the_seeds_and_every_seed_has_the_same_work():
    a = textgen.poisson_due_times(50.0, 4.0, 11)
    assert a == textgen.poisson_due_times(50.0, 4.0, 11)
    b = textgen.poisson_due_times(50.0, 4.0, 2**31 + 12)
    assert a != b and len(a) == len(b) == 200 and a[0] == b[0] == 0.0
    gaps = lambda d: sorted(round(y - x, 9) for x, y in zip(d, d[1:] + [4.0]))  # noqa: E731
    assert gaps(a) == gaps(b)
    qa, qb = textgen.query_texts(70, 11, 4, 10), textgen.query_texts(70, 12, 4, 10)
    assert qa == textgen.query_texts(70, 11, 4, 10) and qa != qb
    assert sorted(len(t.split()) for t in qa) == sorted(len(t.split()) for t in qb)
    assert len(set(qa)) == 70


class _Serial(http.server.BaseHTTPRequestHandler):
    """Answers one request at a time, 50 ms each: a queue builds behind it."""

    protocol_version = "HTTP/1.1"
    lock = threading.Lock()

    def do_POST(self):
        import time

        self.rfile.read(int(self.headers["Content-Length"]))
        with self.lock:
            time.sleep(0.05)
        body = json.dumps([{"text": "x", "metadata": {"path": "prefill/1"}, "dist": -0.5}]).encode()
        self.send_response(200)
        self.send_header("Content-Length", str(len(body)))
        self.end_headers()
        self.wfile.write(body)

    def log_message(self, *a):
        pass


def test_generator_is_open_loop_and_times_from_due_time(tmp_path):
    srv = http.server.ThreadingHTTPServer(("127.0.0.1", 0), _Serial)
    threading.Thread(target=srv.serve_forever, daemon=True).start()
    spec = {"url": f"http://127.0.0.1:{srv.server_address[1]}/v1/retrieve", "seed": 3,
            "seconds": 1.0, "drain_s": 20, "out": str(tmp_path / "records.jsonl"),
            "traffic": {"rate_per_s": 40, "k": 1, "min_words": 4, "max_words": 10,
                        "connections": 40}}
    (tmp_path / "spec.json").write_text(json.dumps(spec))
    child = subprocess.Popen(
        [sys.executable, os.path.join(BENCH, "generators", "retrieve.py"),
         str(tmp_path / "spec.json")], stdin=subprocess.PIPE, stdout=subprocess.PIPE, text=True)
    assert child.stdout.readline().strip() == "READY"
    child.stdin.write("GO\n")
    child.stdin.flush()
    assert child.wait(timeout=60) == 0
    srv.shutdown()
    recs = [json.loads(l) for l in open(spec["out"])]
    assert len(recs) == 40 and not any(r["failed"] for r in recs)
    # 40 requests of 50 ms each were due within 1 s: sent on time (open loop),
    # served one after another, so the last waited about a second from its
    # due time though its own service took 50 ms
    assert max(r["late_ms"] for r in recs) < 25
    assert max(r["latency_ms"] for r in recs) > 700
    assert recs[-1]["answer"][0][0] == "prefill/1"


def test_trace_reduce_on_known_intervals():
    planes = {"devices": {"/device:TPU:0": {
        "XLA Ops": [["%fusion.1 = f32[8]", 0, 100], ["%fusion.2 = f32[8]", 50, 100],
                    ["%copy.3", 400, 100]],
        "XLA Modules": [["jit_dense_fused_search(123)", 0, 150], ["jit_scatter(9)", 400, 100]]}},
        "host": {"tick": [["serving.tick", 140, 270], ["outer", 0, 1000]]}}
    out = trace_reduce.reduce(planes)
    assert out["busy_s"] == pytest.approx(250e-9) and out["window_s"] == pytest.approx(500e-9)
    assert out["programs"] == {"jit_dense_fused_search": pytest.approx(150e-9),
                               "jit_scatter": pytest.approx(100e-9)}
    assert out["launches"] == {"jit_dense_fused_search": 1, "jit_scatter": 1}
    assert out["device_ops"][0] == ["fusion", pytest.approx(200e-9)]
    assert out["idle_gaps"] == [["serving.tick [tick]", pytest.approx(250e-9)]]


def test_trace_reduce_on_the_recorded_trace():
    """A 60 ms cut of a traced retrieve-steady run on a TPU v5 lite (PR 25)."""
    with open(os.path.join(HERE, "trace_small.json")) as f:
        recorded = json.load(f)
    out = trace_reduce.reduce(recorded["planes"])
    for key, value in recorded["expect"].items():
        assert out[key] == pytest.approx(value, rel=1e-9), key
    assert 0 < out["busy_s"] <= out["window_s"]


def test_costs_match_hand_reckoned_numbers():
    # 3,145,728 slots x 384 x 4 B + one mask byte a slot
    assert costs.search_bytes(3145728, 384, 4) == 4831838208 + 3145728
    assert costs.search_flops(256, 3000000, 384) == 589824000000
    # MiniLM-L6 layer: 2*(4*384^2 + 2*384*1536) + 4*8*384 = 3,551,232 FLOPs a token at seq 8
    assert costs.encoder_flops(8, 8, hidden=384, layers=6, ffn=1536) == 8 * 6 * 3551232
    # GPT-2 124M: 12 * (2*(4*768^2+2*768*3072) + 4*100*768) + 2*768*50257 a token at context 100
    assert costs.decoder_flops(1, 100, hidden=768, layers=12, ffn=3072, vocab=50257) == \
        12 * (2 * (4 * 768 * 768 + 2 * 768 * 3072) + 4 * 100 * 768) + 2 * 768 * 50257


def test_peaks_raise_on_an_unknown_device():
    assert costs.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        costs.peaks("TPU v9")
    with pytest.raises(KeyError):
        costs.peaks("source")
