"""Causal-LM generation throughput (models/decoder.py).

Measures steady-state decode tokens/sec for the gpt2 (124M) geometry at
a few batch sizes — prefill excluded, scan decode only — on whatever
backend JAX brings up.  The reference's counterpart is HFPipelineChat's
torch pipeline on CPU.  Prints one JSON line and appends to
``benchmarks/decoder_results.jsonl``.

Run: ``JAX_PLATFORMS=cpu python benchmarks/decoder_bench.py [geometry]``
(geometry: "gpt2" | "tiny")
"""

from __future__ import annotations

import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(HERE)
sys.path.insert(0, REPO)


def run(geometry: str = "gpt2") -> dict:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from pathway_tpu.models.decoder import CausalLM, DecoderConfig

    platform = jax.devices()[0].platform
    if geometry == "tiny":
        cfg = DecoderConfig(
            vocab_size=512, hidden_dim=128, num_layers=4, num_heads=4,
            mlp_dim=512, max_len=512,
            dtype=jnp.float32 if platform == "cpu" else jnp.bfloat16,
        )
    else:
        cfg = DecoderConfig(
            dtype=jnp.float32 if platform == "cpu" else jnp.bfloat16
        )
    lm = CausalLM(cfg=cfg)
    rng = np.random.default_rng(0)
    max_new = 64
    results = {}
    budget = float(os.environ.get("DECODER_BENCH_BUDGET_S", "240"))
    deadline = time.monotonic() + budget
    for batch in (1, 8, 32):
        prompts = [
            rng.integers(1, cfg.vocab_size, size=24).tolist()
            for _ in range(batch)
        ]
        lm.generate_ids(prompts, max_new_tokens=max_new)  # compile + warm
        if time.monotonic() > deadline:
            break
        reps = 0
        t0 = time.perf_counter()
        while time.perf_counter() - t0 < 4.0:
            lm.generate_ids(prompts, max_new_tokens=max_new)
            reps += 1
        elapsed = time.perf_counter() - t0
        results[f"tokens_per_sec_b{batch}"] = round(
            reps * batch * max_new / elapsed, 1
        )
    return {
        "metric": "causal_lm_decode_tokens_per_sec",
        "geometry": geometry,
        "platform": platform,
        "max_new_tokens": max_new,
        **results,
    }


if __name__ == "__main__":
    out = run(sys.argv[1] if len(sys.argv) > 1 else "gpt2")
    out["ts"] = time.strftime("%Y-%m-%dT%H:%M:%S")
    line = json.dumps(out)
    print(line)
    with open(os.path.join(HERE, "decoder_results.jsonl"), "a") as f:
        f.write(line + "\n")
