"""Run the declarative RAG app and evaluate it over the labeled dataset.

reference: integration_tests/rag_evals/ — spins the full QA REST app,
queries a labeled TSV, scores answer correctness (RAGAS-style there;
retrieval-grounded substring scoring here so the eval runs offline).

Usage::

    python examples/rag_app/run.py [--mock-embedder] [--serve]

``--mock-embedder`` swaps the JAX encoder for the deterministic fake
(fast, no device); ``--serve`` keeps the server running after the eval.
"""

from __future__ import annotations

import argparse
import json
import pathlib
import sys
import time

HERE = pathlib.Path(__file__).resolve().parent
sys.path.insert(0, str(HERE.parent.parent))

import os  # noqa: E402

# app.yaml's document path is repo-root-relative; make launching from any
# cwd work
os.chdir(HERE.parent.parent)

import pathway_tpu as pw  # noqa: E402
from pathway_tpu.xpacks.llm.question_answering import RAGClient  # noqa: E402


def build_app(mock_embedder: bool):
    text = (HERE / "app.yaml").read_text()
    if mock_embedder:
        text = text.replace(
            "!pw.xpacks.llm.embedders.SentenceTransformerEmbedder\n"
            "  model: all-MiniLM-L6-v2",
            "!pw.xpacks.llm.mocks.FakeEmbedder\n  dim: 16",
        )
    return pw.load_yaml(text)


def main() -> int:
    parser = argparse.ArgumentParser()
    parser.add_argument("--mock-embedder", action="store_true")
    parser.add_argument("--serve", action="store_true")
    parser.add_argument("--port", type=int, default=None)
    args = parser.parse_args()

    app = build_app(args.mock_embedder)
    qa = app["question_answerer"]
    host, port = app["host"], args.port or app["port"]
    qa.build_server(host=host, port=port)
    qa.server.run(threaded=True, with_cache=True)

    client = RAGClient(host=host, port=port)
    deadline = time.monotonic() + 60
    while True:
        try:
            stats = client.statistics()
            if stats.get("file_count", 0) >= 3:
                break
        except Exception:
            pass
        if time.monotonic() > deadline:
            raise TimeoutError("server did not index the documents in time")
        time.sleep(0.5)

    # answer-correctness harness (xpacks.llm.rag_evals — the reference's
    # integration_tests/rag_evals flow): query the labeled dataset through
    # the served app, grade each answer with the judge.  Offline runs use
    # the deterministic MockJudgeChat; swap in any chat UDF (e.g.
    # OpenAIChat) for a model-graded score.
    from pathway_tpu.xpacks.llm.rag_evals import (
        MockJudgeChat,
        run_eval_experiment,
    )

    metrics = run_eval_experiment(
        client, HERE / "labeled.tsv", judge_chat=MockJudgeChat()
    )
    result = {
        "metric": "rag_eval_answer_correctness",
        "value": metrics["answer_correctness"],
        "unit": "fraction",
        **metrics,
    }
    print(json.dumps(result))

    if args.serve:
        print(f"serving on http://{host}:{port} — ctrl-c to stop", file=sys.stderr)
        while True:
            time.sleep(60)
    return 0 if metrics["n_correct"] == metrics["n_questions"] else 1


if __name__ == "__main__":
    sys.exit(main())
