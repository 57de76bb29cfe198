"""Packed, pipelined ingest: tokenize → pack → encode → upsert.

The ingest plane (connector → splitter → embedder → index upsert) is
where the live-RAG loop's freshness budget goes.  This module rebuilds
its embedding hot path as a producer/consumer pipeline:

* a **host worker** tokenizes and packs (the encoder's
  ``prepare_chunks``) one batch
  AHEAD of the device — the double-buffered hand-off queue (depth
  ``PATHWAY_INGEST_PIPELINE_DEPTH``, default 2) means tokenize(N+1)
  overlaps encode(N) instead of serializing on the embedder thread (the
  WindVE queue-decoupling argument, arXiv:2504.14941, applied to
  ingest);
* the **device worker** transfers, encodes, and — when an index is
  attached — hands the encoder's DEVICE output straight to the staged
  scatter (``DeviceKnnIndex.upsert_batch``): the per-micro-batch
  D2H(embeddings)+H2D(same bytes) round trip disappears, only keys and
  metadata stay host-side.

Every stage records flight-recorder spans (``tokenize`` / ``encode`` /
``upsert``, category ``ingest``) and documents count into
``pathway_ingest_docs_total``; packing efficiency feeds
``pathway_embed_padding_efficiency``.  Under ``PATHWAY_FAULTS`` chaos
the device stage honors the ``embedder`` site: an injected failure
fails THAT batch's future and the pipeline keeps draining.

The device worker does not touch the device itself — each prepared
chunk (one bounded ``bb×seq`` launch) is submitted to the unified
device-tick runtime as a ``BULK_INGEST``-class work item whose token
estimate is the chunk's padded token mass.  Interactive
serving ticks preempt the backlog at tick granularity (a query never
waits behind more than the chunk already on the device) while the
runtime's starvation bound guarantees ingest forward progress under
sustained query load.  Upsert staging (host-side bookkeeping; the
scatter itself runs at the next search) stays on the worker thread so a
failed chunk still fails its whole batch before anything is staged.
"""

from __future__ import annotations

import os
import queue
import threading
from concurrent.futures import Future
from typing import Any, Sequence

import numpy as np

__all__ = ["IngestPipeline", "ingest_pipeline_depth"]

_SENTINEL = object()


def ingest_pipeline_depth() -> int:
    """Prepared-batch hand-off depth (``PATHWAY_INGEST_PIPELINE_DEPTH``,
    default 2 = double-buffered: host stays exactly one batch ahead)."""
    try:
        depth = int(os.environ.get("PATHWAY_INGEST_PIPELINE_DEPTH", "2"))
    except ValueError:
        depth = 2
    return max(depth, 1)


class _Batch:
    __slots__ = ("texts", "keys", "metas", "future", "prepared", "stats")

    def __init__(self, texts, keys, metas, future):
        self.texts = texts
        self.keys = keys
        self.metas = metas
        self.future = future
        self.prepared = None
        self.stats = None


class IngestPipeline:
    """Two-stage tokenize/pack → encode/upsert pipeline over a
    :class:`~pathway_tpu.models.encoder.SentenceEncoder`.

    ``index`` (optional) is an inner index with ``add_batch`` (e.g.
    :class:`~pathway_tpu.stdlib.indexing.retrievers.BruteForceKnnIndex`)
    or a bare :class:`~pathway_tpu.ops.knn.DeviceKnnIndex`; with one
    attached, futures resolve to the number of documents upserted and
    embeddings never leave the device.  Without one, futures resolve to
    the ``[B, dim]`` float32 embeddings in submission order.
    """

    def __init__(
        self,
        encoder: Any,
        index: Any = None,
        *,
        depth: int | None = None,
        max_tokens: int | None = None,
    ):
        from ...models.encoder import embed_max_tokens
        from ...runtime import WorkGroup

        self.encoder = encoder
        self.index = index
        self.depth = depth if depth is not None else ingest_pipeline_depth()
        self.max_tokens = (
            max_tokens if max_tokens is not None else embed_max_tokens()
        )
        # max_batch=1: every prepared chunk is its own device dispatch
        # AND its own failure domain — one poisoned chunk must not fail
        # another pipeline batch sharing the tick
        self._encode_group = WorkGroup(
            "ingest-encode", self._encode_chunk, max_batch=1
        )
        self._in: queue.Queue = queue.Queue()
        # the hand-off: host worker blocks here once it is `depth`
        # batches ahead — bounded lookahead IS the backpressure
        self._ready: queue.Queue = queue.Queue(maxsize=self.depth)
        self._closed = False
        self._lock = threading.Lock()
        self._tok_thread: threading.Thread | None = None
        self._dev_thread: threading.Thread | None = None

    # -- lifecycle -------------------------------------------------------
    def _ensure_threads_locked(self) -> None:
        if self._tok_thread is None:
            self._tok_thread = threading.Thread(
                target=self._tokenize_loop, daemon=True,
                name="pw-ingest-tok",
            )
            self._dev_thread = threading.Thread(
                target=self._device_loop, daemon=True,
                name="pw-ingest-dev",
            )
            self._tok_thread.start()
            self._dev_thread.start()

    def close(self) -> None:
        """Drain both stages and join the workers (idempotent)."""
        with self._lock:
            if self._closed:
                return
            self._closed = True
            started = self._tok_thread is not None
        if started:
            self._in.put(_SENTINEL)
            self._tok_thread.join()
            self._dev_thread.join()

    def __enter__(self) -> "IngestPipeline":
        return self

    def __exit__(self, *exc) -> None:
        self.close()

    # -- submission ------------------------------------------------------
    def submit(
        self,
        texts: Sequence[str],
        keys: Sequence[Any] | None = None,
        metas: Sequence[Any] | None = None,
    ) -> Future:
        """Enqueue one document batch.  With an index attached ``keys``
        is required (metadata optional); the future resolves once the
        batch is encoded and staged into the index."""
        if self.index is not None and keys is None:
            raise ValueError("keys are required when upserting into an index")
        if keys is not None and len(keys) != len(texts):
            raise ValueError(f"{len(keys)} keys for {len(texts)} texts")
        fut: Future = Future()
        if not texts:
            fut.set_result(
                0 if self.index is not None else np.zeros(
                    (0, self.encoder.dim), dtype=np.float32
                )
            )
            return fut
        # closed-check and enqueue under the same lock close() flips the
        # flag under — a batch can never slip in BEHIND the shutdown
        # sentinel (its future would hang forever)
        with self._lock:
            if self._closed:
                raise RuntimeError("ingest pipeline is closed")
            self._ensure_threads_locked()
            self._in.put(_Batch(list(texts), keys, metas, fut))
        return fut

    def encode(self, texts: Sequence[str]) -> Any:
        """Synchronous convenience: submit one batch and wait."""
        return self.submit(texts).result()

    # -- stage 1: host tokenize + pack ----------------------------------
    def _tokenize_loop(self) -> None:
        from ...internals.flight_recorder import name_thread, span

        name_thread("pw-ingest-tok")
        enc = self.encoder
        while True:
            item = self._in.get()
            if item is _SENTINEL:
                self._ready.put(_SENTINEL)
                return
            try:
                with span("tokenize", "ingest", docs=len(item.texts)):
                    ids_all, mask_all = enc.tokenizer.encode_batch(
                        item.texts, max_length=enc.max_length
                    )
                # host half of the dispatch in the ENCODER's layout
                # (packed (bb, seq) buckets or the ragged
                # concatenated-token layout, per ``attention_impl``);
                # every entry is ``(payload, rows, tokens)``
                item.prepared, item.stats = enc.prepare_chunks(
                    ids_all, mask_all, max_tokens=self.max_tokens
                )
            except BaseException as exc:  # noqa: BLE001 — fail THIS batch only
                if not item.future.done():
                    item.future.set_exception(exc)
                continue
            self._ready.put(item)  # blocks at `depth` batches ahead

    # -- stage 2: device transfer + encode + upsert ---------------------
    def _encode_chunk(self, payloads: list) -> list:
        """BULK_INGEST batch handler (runtime executor thread): one
        prepared chunk per call (``max_batch=1``) — the encoder's own
        device half (packed (bb, seq) launch or ONE ragged
        concatenated-token launch, H2D + mesh placement included), the
        DEVICE output returned as-is so upsert staging keeps the
        embed→upsert path device-resident.

        The chunk's device work is SYNCHRONIZED before the tick ends:
        jax dispatches are async, so returning unfinished work would
        let a bulk backlog pile into the device queue and the next
        tick's interactive dispatch would wait behind every queued
        chunk anyway — priority inversion at the device-queue level
        (observed as 300+ ms serving `search` stages behind a 64-chunk
        async backlog).  One tick in flight at a time is the executor's
        whole contract with the device."""
        import jax

        from ...internals.flight_recorder import span

        (payload,) = payloads
        tokens = int(
            np.asarray(payload[0]).size if isinstance(payload, tuple)
            else np.asarray(payload.ids).size
        )
        with span("encode", "ingest", tokens=tokens):
            out = self.encoder.encode_prepared(payload)
        jax.block_until_ready(out)
        return [out]

    def _device_loop(self) -> None:
        from ...internals.flight_recorder import (
            name_thread,
            record_ingest_docs,
            record_padding,
            span,
        )

        name_thread("pw-ingest-dev")
        while True:
            item = self._ready.get()
            if item is _SENTINEL:
                return
            try:
                from ...testing import faults

                if faults.enabled:
                    # chaos site "embedder": a failed encode fails this
                    # batch's future; the pipeline keeps draining
                    faults.perturb("embedder")
                record_padding(
                    item.stats["real_tokens"],
                    item.stats["padded_tokens"],
                    item.stats.get("row_tokens"),
                )
                # every prepared chunk is one BULK_INGEST work item:
                # tokens = its padded token mass (one ragged launch ==
                # one item too), coalesce 0 (a backlog never waits for
                # tick-mates).  Interactive ticks slot in between
                # chunks; the min-share bound keeps this batch
                # progressing under query floods.
                from ...runtime import QoS, get_runtime

                rt = get_runtime()
                futs = [
                    (
                        rt.submit(
                            self._encode_group,
                            payload,
                            qos=QoS.BULK_INGEST,
                            tokens=int(tokens),
                            coalesce_s=0.0,
                        ),
                        rows,
                    )
                    for payload, rows, tokens in item.prepared
                ]
                # all chunks must encode before anything stages: a
                # failed chunk fails the WHOLE batch pre-upsert
                outs = [(f.result(), rows) for f, rows in futs]
                if self.index is not None:
                    with span("upsert", "ingest", docs=len(item.texts)):
                        for out, rows in outs:
                            keys = [item.keys[i] for i in rows]
                            metas = (
                                [item.metas[i] for i in rows]
                                if item.metas is not None
                                else [None] * len(rows)
                            )
                            if hasattr(self.index, "add_batch"):
                                self.index.add_batch(keys, out, metas)
                            else:
                                self.index.upsert_batch(keys, out)
                    record_ingest_docs(len(item.texts))
                    result: Any = len(item.texts)
                else:
                    emb = np.empty(
                        (len(item.texts), self.encoder.dim), dtype=np.float32
                    )
                    for out, rows in outs:
                        emb[rows] = np.asarray(out, dtype=np.float32)[: len(rows)]
                    result = emb
            except BaseException as exc:  # noqa: BLE001 — fail THIS batch only
                if not item.future.done():
                    item.future.set_exception(exc)
                continue
            if not item.future.done():
                item.future.set_result(result)
