"""Serve the fully-local adaptive-RAG app and ask it one question.

Everything runs in-process on local JAX models: the MiniLM-class
encoder embeds documents and queries, the GPT-2-class causal LM
generates, and AdaptiveRAG widens the context geometrically
(reference: question_answering.py:620 + BASELINE config #4).

Usage::

    JAX_PLATFORMS=cpu python examples/local_qa/run.py [--serve]
"""

from __future__ import annotations

import argparse
import json
import os
import pathlib
import sys
import time

HERE = pathlib.Path(__file__).resolve().parent
sys.path.insert(0, str(HERE.parent.parent))
os.chdir(HERE.parent.parent)

import pathway_tpu as pw  # noqa: E402
from pathway_tpu.xpacks.llm.question_answering import RAGClient  # noqa: E402


def main() -> int:
    parser = argparse.ArgumentParser()
    parser.add_argument("--serve", action="store_true")
    parser.add_argument("--port", type=int, default=None)
    args = parser.parse_args()

    app = pw.load_yaml((HERE / "app.yaml").read_text())
    qa = app["question_answerer"]
    host, port = app["host"], args.port or app["port"]
    qa.build_server(host=host, port=port)
    qa.server.run(threaded=True, with_cache=False)

    client = RAGClient(host=host, port=port)
    deadline = time.monotonic() + 120
    while True:
        try:
            if client.statistics().get("file_count", 0) >= 3:
                break
        except Exception:
            pass
        if time.monotonic() > deadline:
            raise TimeoutError("documents were not indexed in time")
        time.sleep(0.5)

    t0 = time.perf_counter()
    answer = client.answer("How does adaptive retrieval save tokens?")
    dt = time.perf_counter() - t0
    lm = getattr(qa.llm, "_lm", None)
    print(json.dumps({
        "answer": str(answer)[:200],
        "latency_s": round(dt, 2),
        "pretrained": bool(getattr(lm, "pretrained", False)),
    }))

    if args.serve:
        print(f"serving on http://{host}:{port}", file=sys.stderr)
        while True:
            time.sleep(60)
    return 0


if __name__ == "__main__":
    sys.exit(main())
