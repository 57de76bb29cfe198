"""Headline benchmark: document embedding throughput on one TPU chip.

BASELINE.json config #1 — ``SentenceTransformerEmbedder(all-MiniLM-L6-v2)``
over a static corpus.  The reference runs the torch model inside an async
UDF one string per call (xpacks/llm/embedders.py:270); here the same
geometry runs as a jit-compiled flax encoder with bucketed batching
(models/encoder.py), bf16 on the MXU.

Measures on the chip, in this one process, or exits non-zero naming the
platform JAX found: there is no CPU fallback and no carried-forward
number.  (ROADMAP S1 replaces this script with the benchmark proper.)

Prints one JSON line per banked measurement as it goes; the LAST line is
the result: {"metric", "value", "unit", "platform", "device_kind", ...}.
"""

from __future__ import annotations

import json
import os
import sys
import time

METRIC = "embedding_throughput_minilm_seq128"
UNIT = "docs/sec/chip"

# all-MiniLM-L6-v2 geometry (models/encoder.py EncoderConfig defaults)
_L, _H, _I, _S = 6, 384, 1536, 128

#: forward FLOPs per doc at seq 128: per layer QKV+O projections
#: (8*S*H^2), attention QK^T+AV (4*S^2*H), FFN (4*S*H*I)
FLOPS_PER_DOC = _L * (8 * _S * _H * _H + 4 * _S * _S * _H + 4 * _S * _H * _I)

#: peak dense bf16 FLOP/s per chip by ``device_kind`` (public spec
#: sheets); a device that is not here is an error, not a default
_PEAK_BF16 = {
    "TPU v4": 275e12,
    "TPU v5 lite": 197e12,
    "TPU v5p": 459e12,
    "TPU v6 lite": 918e12,
}


#: per-doc word counts, cycle of 4 (tokens ≈ words + CLS/SEP): two short
#: docs (seq bucket 32), one medium (64), one long (128) — the
#: mixed-length shape real ingest corpora have (chunked documents +
#: titles + queries), and the case whole-batch padding pays 2-4x extra
#: FLOPs on.  Counts per bucket land on exact batch buckets so the
#: packed path compiles few shapes.
_MIXED_WORDS = (24, 24, 56, 120)


def _corpus(n_docs: int = 2048, mixed: bool = True) -> list[str]:
    import numpy as np

    rng = np.random.default_rng(0)
    words = [f"w{i:04d}" for i in range(2000)]
    if not mixed:
        return [
            " ".join(rng.choice(words, size=96))  # ~128 tokens after wordpiece
            for _ in range(n_docs)
        ]
    return [
        " ".join(rng.choice(words, size=_MIXED_WORDS[i % len(_MIXED_WORDS)]))
        for i in range(n_docs)
    ]


# ---------------------------------------------------------------------------
# the device measurement
# ---------------------------------------------------------------------------


def measure_device(dev, seconds: float = 10.0) -> None:
    from pathway_tpu.models.encoder import (
        EncoderConfig,
        SentenceEncoder,
        bucketed_dispatch,
    )

    # fused attention (jax.nn.dot_product_attention) keeps the S×S
    # intermediates out of HBM; numerically equal to the flax chain
    # (1e-7 fp32, tests/test_models.py) and measured faster on both
    # backends.  BENCH_ATTN=flax|fused|pallas overrides for A/B runs.
    attn = os.environ.get("BENCH_ATTN", "fused")
    enc = SentenceEncoder(
        max_length=128, cfg=EncoderConfig(attention_impl=attn)
    )
    docs = _corpus()
    budget = float(os.environ.get("BENCH_BUDGET_S", "840"))
    deadline = time.monotonic() + budget

    # tokenize ONCE, outside every timed window: the metric is forward +
    # pooling over pre-built ids
    ids_all, mask_all = enc.tokenizer.encode_batch(docs, max_length=enc.max_length)
    fwd = lambda i, m: enc._apply(enc.params, i, m)  # noqa: E731
    vocab = enc.cfg.vocab_size

    def measure(batch: int, ragged_enc=None) -> float:
        """Steady-state forward throughput at one chunk size (already
        warm).  ``ragged_enc`` (or a ragged headline, BENCH_ATTN=ragged)
        routes through that encoder's own ragged dispatch —
        bucketed_dispatch has no packed-token path.  ONE timing loop for
        headline and every A/B variant, so the measurement protocol
        can't drift between them."""
        if ragged_enc is None and attn == "ragged":
            ragged_enc = enc
        n_docs = 0
        t0 = time.perf_counter()
        while True:
            for start in range(0, len(docs), batch):
                stop = min(start + batch, len(docs))
                if ragged_enc is not None:
                    ragged_enc.encode_tokenized(
                        ids_all[start:stop], mask_all[start:stop]
                    )
                else:
                    bucketed_dispatch(
                        fwd,
                        ids_all[start:stop],
                        mask_all[start:stop],
                        enc.max_length,
                        vocab_size=vocab,
                    )
                n_docs += stop - start
            if time.perf_counter() - t0 > seconds:
                break
        return n_docs / (time.perf_counter() - t0)

    # padding accounting for the headline path: one packed_prepare pass
    # over the measurement slices tells how many padded tokens the device
    # actually computes per real token
    from pathway_tpu.models.encoder import packed_prepare

    def _padding_eff(batch: int) -> float:
        real = padded = 0
        for start in range(0, len(docs), batch):
            _, st = packed_prepare(
                ids_all[start : start + batch],
                mask_all[start : start + batch],
                enc.max_length,
                vocab_size=vocab,
            )
            real += st["real_tokens"]
            padded += st["padded_tokens"]
        return round(real / padded, 4) if padded else 1.0

    extra: dict = {
        "corpus": "mixed_seq32/64/128",
        # every measured variant labels the attention impl it ran
        "attn_impl_by_variant": {"headline": attn},
    }

    def _ragged_ab(enc_ragged, batch: int, baseline_dps: float) -> None:
        """In-run ragged-vs-packed A/B over the same mixed corpus: docs/s
        ratio, the intra-bucket padding decomposition (ragged pins ~1.0
        where the packed-bucket path sits ~0.906), and XLA compile-count
        flatness across the measured reps."""
        from pathway_tpu.internals.flight_recorder import compile_stats

        enc_ragged.encode_tokenized(ids_all[:batch], mask_all[:batch])  # warm
        before = compile_stats().get("encoder.forward_ragged", 0)
        ragged_dps = max(
            measure(batch, ragged_enc=enc_ragged),
            measure(batch, ragged_enc=enc_ragged),
        )
        flat = compile_stats().get("encoder.forward_ragged", 0) == before
        real = row = padded = 0
        for start in range(0, len(docs), batch):
            _, rst = enc_ragged.prepare_chunks(
                ids_all[start : start + batch], mask_all[start : start + batch]
            )
            real += rst["real_tokens"]
            row += rst["row_tokens"]
            padded += rst["padded_tokens"]
        extra["ragged_docs_per_sec"] = round(ragged_dps, 1)
        extra["ragged_intra_bucket_efficiency"] = (
            round(real / row, 4) if row else 1.0
        )
        extra["ragged_padding_efficiency"] = (
            round(real / padded, 4) if padded else 1.0
        )
        extra["ragged_compile_flat"] = flat
        if baseline_dps:
            extra["ragged_vs_packed"] = round(ragged_dps / baseline_dps, 3)
        extra["attn_impl_by_variant"]["ragged"] = "ragged"

    def _quant_ab(n_rows: int, reps: int = 5) -> None:
        """In-run f32-vs-int8 brute-force search A/B (ISSUE 11): the
        same seeded corpus resident both ways, the same query batches,
        docs/s (= corpus rows scored per second), recall@10 of the
        quantized path against the f32 oracle, and HBM bytes/vector."""
        import numpy as np

        import jax as _jax
        from pathway_tpu.ops.knn import DeviceKnnIndex

        import jax.numpy as jnp

        rng = np.random.default_rng(7)
        dim, n_q, k = 384, 8, 10
        corpus = rng.standard_normal((n_rows, dim)).astype(np.float32)
        queries = rng.standard_normal((16, n_q, dim)).astype(np.float32)
        keys = list(range(n_rows))
        corpus_dev = jnp.asarray(corpus)  # device staging: the ingest plane
        results = {}
        recall_base = None
        for label, kwargs in (
            ("f32", {}),
            ("int8", {"index_dtype": "int8"}),
        ):
            idx = DeviceKnnIndex(dim=dim, capacity=n_rows, **kwargs)
            idx.upsert_batch(keys, corpus_dev)
            idx.search(queries[0], k)  # apply + compile warm
            t0 = time.perf_counter()
            total_q = 0
            all_res = []
            for rep in range(reps):
                for qb in queries:
                    res = idx.search(qb, k)
                    total_q += n_q
                    if rep == reps - 1:  # recall over EVERY query batch
                        all_res.extend(res)
            elapsed = time.perf_counter() - t0
            results[label] = {
                "search_docs_per_sec": round(n_rows * total_q / elapsed, 1),
                "ms_per_query_batch": round(
                    elapsed / (reps * len(queries)) * 1000, 3
                ),
                "hbm_bytes_per_vector": round(idx.hbm_bytes() / n_rows, 2),
            }
            if label == "f32":
                recall_base = [{key for key, _s in row} for row in all_res]
            else:
                hits = sum(
                    len(truth & {key for key, _s in row})
                    for truth, row in zip(recall_base, all_res)
                )
                results["recall_at_10"] = round(
                    hits / (len(all_res) * k), 4
                )
        results["int8_vs_f32"] = round(
            results["int8"]["search_docs_per_sec"]
            / results["f32"]["search_docs_per_sec"],
            3,
        )
        results["corpus_rows"] = n_rows
        results["platform"] = _jax.devices()[0].platform
        extra["quant_ab"] = results

    # escalating warmup: a small bucket compiles fast; the big bucket
    # (fewer, fuller launches) upgrades the number only if the budget
    # still allows its compile + a timed window.  Every improvement is
    # printed immediately.
    small = 256
    if attn == "ragged":
        enc.encode_tokenized(ids_all[:small], mask_all[:small])
    else:
        bucketed_dispatch(fwd, ids_all[:small], mask_all[:small], enc.max_length, vocab_size=vocab)
    if attn != "ragged":
        extra["padding_efficiency"] = _padding_eff(small)
    docs_per_sec = _emit_device_result(measure(small), dev, attn, **extra)
    big = min(1024, len(docs))
    big_warm = False
    if big > small and time.monotonic() + 180 + seconds < deadline:
        if attn == "ragged":
            enc.encode_tokenized(ids_all[:big], mask_all[:big])
        else:
            bucketed_dispatch(fwd, ids_all[:big], mask_all[:big], enc.max_length, vocab_size=vocab)
        big_warm = True
        if attn != "ragged":
            extra["padding_efficiency"] = _padding_eff(big)
        docs_per_sec = max(docs_per_sec, measure(big))
        docs_per_sec = _emit_device_result(docs_per_sec, dev, attn, **extra)
        # steady chip + budget to spare: take a second same-length sample
        # (keeps the best of the two against scheduler noise)
        if time.monotonic() + 3 * seconds < deadline:
            docs_per_sec = max(docs_per_sec, measure(big))

    _emit_device_result(docs_per_sec, dev, attn, **extra)
    best_attn = attn

    # A/B the pallas kernel only after a banked fused measurement — a
    # crash here cannot cost the number already printed above
    fused_fwd = fwd
    if (
        attn == "fused"
        and time.monotonic() + 180 + seconds < deadline
    ):
        try:
            enc2 = SentenceEncoder(
                max_length=128, cfg=EncoderConfig(attention_impl="pallas")
            )
            fwd2 = lambda i, m: enc2._apply(enc2.params, i, m)  # noqa: E731
            fwd = fwd2
            bucketed_dispatch(fwd, ids_all[:big], mask_all[:big], enc.max_length, vocab_size=vocab)
            pallas_dps = measure(big)
            extra["pallas_docs_per_sec"] = round(pallas_dps, 1)
            extra["attn_impl_by_variant"]["pallas"] = "pallas"
            if pallas_dps > docs_per_sec:
                docs_per_sec, best_attn = pallas_dps, "pallas"
        except Exception as exc:  # a pallas lowering failure must never
            # cost the fused number already printed above — but it must
            # be VISIBLE: it lands in the result's warnings
            msg = f"pallas A/B failed: {exc!r}"[:300]
            extra["ab_warning"] = (
                f"{extra['ab_warning']}; {msg}" if "ab_warning" in extra else msg
            )
        _emit_device_result(docs_per_sec, dev, best_attn, **extra)

    # ragged packed-batch A/B on the chip: the REAL Pallas ragged kernel
    # (one launch per budget window, block-aligned ragged masks) vs the
    # banked packed number — the MFU headline this PR is about
    if (
        attn != "ragged"
        and time.monotonic() + 180 + 2 * seconds < deadline
    ):
        try:
            enc_r = SentenceEncoder(
                max_length=128, cfg=EncoderConfig(attention_impl="ragged")
            )
            enc_r.params = enc.params
            _ragged_ab(enc_r, big, docs_per_sec)
            if extra["ragged_docs_per_sec"] > docs_per_sec:
                docs_per_sec, best_attn = extra["ragged_docs_per_sec"], "ragged"
        except Exception as exc:
            msg = f"ragged A/B failed: {exc!r}"[:300]
            extra["ab_warning"] = (
                f"{extra['ab_warning']}; {msg}" if "ab_warning" in extra else msg
            )
        _emit_device_result(docs_per_sec, dev, best_attn, **extra)

    # bf16-wire A/B: casting the normalized embedding to bf16 ON DEVICE
    # halves the device→host bytes; the forward is unchanged.  Not the
    # headline — reported alongside.  The cast composes OUTSIDE the
    # forward's jit (the cached executable is reused), so warmup compiles
    # only a trivial convert kernel.
    if (
        attn != "ragged"  # ragged has no dense fwd warmed to cast through
        and time.monotonic() + 60 + 3 * seconds < deadline
    ):
        try:
            import jax.numpy as jnp

            fwd = lambda i, m: fused_fwd(i, m).astype(jnp.bfloat16)  # noqa: E731
            bucketed_dispatch(fwd, ids_all[:big], mask_all[:big], enc.max_length, vocab_size=vocab)
            extra["wire_bf16_docs_per_sec"] = round(measure(big), 1)
            # fused_fwd is the HEADLINE-impl encoder (bound before the
            # pallas/ragged A/Bs reassign fwd) — label it as such
            extra["attn_impl_by_variant"]["wire_bf16"] = attn
        except Exception as exc:
            msg = f"bf16-wire A/B failed: {exc!r}"[:300]
            extra["ab_warning"] = (
                f"{extra['ab_warning']}; {msg}" if "ab_warning" in extra else msg
            )
        _emit_device_result(docs_per_sec, dev, best_attn, **extra)

    # compute-only: device-resident inputs, no per-dispatch transfer —
    # what the chip itself sustains once host↔device copies are out of
    # the loop (published accelerator figures are data-resident).  Reuses
    # the dispatch path's own padding protocol (pad_chunk) so the cached
    # executable is hit — a fresh big-bucket compile is only paid when
    # the escalation never warmed it, and then only with compile budget.
    margin = 30 if big_warm else 180
    if (
        attn != "ragged"  # dense-executable probe; a ragged headline
        # never warmed it and the fallback would mislabel the number
        and time.monotonic() + margin + seconds < deadline
    ):
        try:
            import jax

            from pathway_tpu.models.encoder import (
                BATCH_BUCKETS,
                SEQ_BUCKETS,
                _bucket,
                dispatch_dtype,
                pad_chunk,
            )

            longest = int(mask_all[:big].sum(axis=1).max())
            seq_b = min(_bucket(longest, SEQ_BUCKETS), enc.max_length)
            bb = _bucket(big, BATCH_BUCKETS)
            ids_np, mask_np, _ = pad_chunk(
                ids_all[:big],
                mask_all[:big],
                bb,
                seq_b,
                ids_dtype=dispatch_dtype(vocab),
            )
            di, dm = jax.device_put(ids_np), jax.device_put(mask_np)
            fused_fwd(di, dm).block_until_ready()  # cached-executable warm
            n = 0
            t0 = time.perf_counter()
            out = None
            while time.perf_counter() - t0 < seconds:
                # sync every 32 dispatches: async dispatch would otherwise
                # enqueue unbounded device work the trailing drain pays for
                for _ in range(32):
                    out = fused_fwd(di, dm)
                    n += bb
                out.block_until_ready()
            co = n / (time.perf_counter() - t0)
            extra["compute_only_docs_per_sec"] = round(co, 1)
            extra["mfu_compute_only"] = _mfu(co, dev)
            extra["attn_impl_by_variant"]["compute_only"] = attn
        except Exception as exc:
            msg = f"compute-only probe failed: {exc!r}"[:300]
            extra["ab_warning"] = (
                f"{extra['ab_warning']}; {msg}" if "ab_warning" in extra else msg
            )
        _emit_device_result(docs_per_sec, dev, best_attn, **extra)

    # quantized-index search A/B on the REAL chip: the Pallas asymmetric
    # kernel streaming int8 codes from HBM vs the f32 tiled path — the
    # memory-bandwidth headline of ISSUE 11 (4x fewer bytes/vector)
    if time.monotonic() + 180 + seconds < deadline:
        try:
            _quant_ab(131072)
        except Exception as exc:
            msg = f"quant A/B failed: {exc!r}"[:300]
            extra["ab_warning"] = (
                f"{extra['ab_warning']}; {msg}" if "ab_warning" in extra else msg
            )
        _emit_device_result(docs_per_sec, dev, best_attn, **extra)


def _mfu(docs_per_sec: float, dev) -> float:
    peak = _PEAK_BF16[dev.device_kind]  # KeyError: add the device's peak
    return round(docs_per_sec * FLOPS_PER_DOC / peak, 4)


#: the newest banked measurement (what the last printed line says)
_LATEST: dict = {}


def _emit_device_result(
    docs_per_sec: float, dev, attn: str = "fused", **extra
) -> float:
    """Print one measurement JSON line and keep it as the newest."""
    rec = {
        "docs_per_sec": round(docs_per_sec, 1),
        "platform": dev.platform,
        "device_kind": dev.device_kind,
        "flops_per_doc": FLOPS_PER_DOC,
        "mfu": _mfu(docs_per_sec, dev),
        "attn_impl": attn,
    }
    rec.update(extra)
    print(json.dumps(rec), flush=True)
    _LATEST.clear()
    _LATEST.update(rec)
    return docs_per_sec


_printed = False


def _emit(out: dict) -> None:
    global _printed
    if not _printed:
        _printed = True
        line = json.dumps(out)
        print(line, flush=True)
        # bank the headline like the other benches do, so its history is
        # a repo artifact
        try:
            out = dict(out)
            out.setdefault("ts", time.strftime("%Y-%m-%dT%H:%M:%S"))
            path = os.path.join(
                os.path.dirname(os.path.abspath(__file__)),
                "benchmarks",
                "bench_results.jsonl",
            )
            with open(path, "a") as f:
                f.write(json.dumps(out) + "\n")
        except OSError:
            pass


def _install_last_resort() -> None:
    """Even if the harness SIGTERMs us mid-run, ship a valid JSON line."""
    import signal

    def handler(signum, frame):
        _emit(
            {
                "metric": METRIC,
                "value": 0.0,
                "unit": UNIT,
                "error": f"killed by signal {signum} before measurement finished",
            }
        )
        os._exit(1)

    for sig in (signal.SIGTERM, signal.SIGINT):
        signal.signal(sig, handler)


def main() -> None:
    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        sys.exit(
            f"bench.py measures on a TPU; JAX found platform "
            f"{dev.platform!r} ({dev.device_kind!r})"
        )
    _install_last_resort()
    measure_device(dev)
    result = _LATEST
    out: dict = {
        "metric": METRIC,
        "unit": UNIT,
        "value": result["docs_per_sec"],
        "platform": result["platform"],
        "device_kind": result["device_kind"],
        "mfu": result["mfu"],
        "attn_impl": result["attn_impl"],
    }
    for opt in (
        "corpus",
        "padding_efficiency",
        "pallas_docs_per_sec",
        "ragged_docs_per_sec",
        "ragged_vs_packed",
        "ragged_intra_bucket_efficiency",
        "ragged_padding_efficiency",
        "ragged_compile_flat",
        "wire_bf16_docs_per_sec",
        "compute_only_docs_per_sec",
        "mfu_compute_only",
        "quant_ab",
        "attn_impl_by_variant",
    ):
        if result.get(opt) is not None:
            out[opt] = result[opt]
    if result.get("ab_warning"):
        out["warnings"] = [f"A/B: {result['ab_warning']}"]
    _emit(out)


if __name__ == "__main__":
    main()
