"""StreamingDataLoader — the device-facing edge of the ingestion fabric.

Pulls FlowFile documents from a topic of the durable log (as a consumer-group
member), tokenizes, packs, and assembles fixed-shape global batches, with:

  * bounded host→device prefetch (reuses ``core.Connection`` backpressure —
    the paper's object-threshold semantics extended to the accelerator hop);
  * multiple reader threads with work-stealing over assigned partitions
    (straggler mitigation: a slow partition/disk never stalls the batch
    assembly as long as any partition has data);
  * exactly-once state: (consumer positions, packer carry, row buffer) are
    checkpointable and restored byte-identically (poll is deterministic);
  * elasticity: the loader is one member of a consumer group — adding
    training jobs (or data-parallel reader hosts) rebalances partitions
    without touching the ingestion pipeline (paper's headline property).

In a multi-host deployment each host runs one loader member producing the
host-local rows of the global batch, and the runtime assembles them with
``jax.make_array_from_process_local_data``; in this single-process container
the loader produces the full global batch and the runtime shards it by
``jax.device_put`` with a NamedSharding.
"""
from __future__ import annotations

import itertools
import threading
import time
from collections import deque
from typing import Callable

import numpy as np

from ..core.connection import Connection
from ..core.delivery import Consumer
from ..core.flowfile import FlowFile
from ..core.telemetry import count, span, tracer
from .packing import SequencePacker
from .tokenizer import ByteTokenizer

_LOADER_IDS = itertools.count()


class StreamingDataLoader:
    def __init__(self, consumer: Consumer, *, batch_size: int, seq_len: int,
                 tokenizer: ByteTokenizer | None = None,
                 text_fn: Callable[[FlowFile], str] | None = None,
                 prefetch_batches: int = 4,
                 prefetch_chunk: int | None = None,
                 prefetch_linger_sec: float = 0.05,
                 reader_threads: int = 2,
                 poll_records: int = 64) -> None:
        self.consumer = consumer
        self.batch_size = batch_size
        self.seq_len = seq_len
        self.tokenizer = tokenizer or ByteTokenizer()
        self.text_fn = text_fn or (lambda ff: ff.text())
        self.packer = SequencePacker(seq_len, self.tokenizer.PAD)
        self._rows: list[np.ndarray] = []
        self._batches_emitted = 0
        self.poll_records = poll_records
        #: label of this loader's ``loader_*`` counters on the process tracer
        self.loader_id = str(next(_LOADER_IDS))
        # host→device prefetch queue with backpressure. The assembler ships
        # *chunks* of up to ``prefetch_chunk`` batches per queue envelope:
        # the CPU-bound assembler thread only yields the GIL every switch
        # interval, so each queue handoff costs the consumer a scheduling
        # quantum — amortize it over many batches. ``prefetch_batches`` still
        # bounds the number of *batches* buffered: the queue's object
        # threshold counts envelopes, sized so envelopes × chunk ≈
        # prefetch_batches. ``prefetch_linger_sec`` bounds the latency a
        # partial chunk may wait.
        prefetch_batches = max(1, prefetch_batches)
        self._chunk_batches = (min(prefetch_batches, 8) if prefetch_chunk
                               is None else max(1, prefetch_chunk))
        depth = -(-prefetch_batches // self._chunk_batches)  # ceil div
        self._prefetch = Connection("loader-prefetch", object_threshold=depth)
        self._chunk_linger = prefetch_linger_sec
        self._drained: deque[np.ndarray] = deque()
        self._reader_threads = reader_threads
        self._stop = threading.Event()
        self._threads: list[threading.Thread] = []
        self._state_lock = threading.Lock()

    # ------------------------------------------------------------------
    # Synchronous path (used by tests, dry runs, and the exactly-once
    # restore story — deterministic single-threaded batch assembly).
    # ------------------------------------------------------------------
    def _ingest_records(self, records) -> None:
        """Tokenize + pack a whole poll batch at once: one ``encode_batch``
        over all documents and one reshape in the packer, instead of
        per-document Python token loops. Falls back to the per-document path
        for pluggable tokenizers without ``encode_batch``. Row output is
        byte-identical to the sequential path (same concatenation order)."""
        if not records:
            return
        texts = [self.text_fn(FlowFile.from_record(rec.key, rec.value))
                 for rec in records]
        encode_batch = getattr(self.tokenizer, "encode_batch", None)
        n_rows = len(self._rows)
        if encode_batch is None:
            n_tokens = 0
            for text in texts:
                ids = self.tokenizer.encode(text)
                n_tokens += len(ids)
                self._rows.extend(self.packer.add_document(ids))
        else:
            ids = encode_batch(texts)
            n_tokens = len(ids)
            rows = self.packer.add_tokens(ids)
            if len(rows):
                self._rows.extend(rows)
        count("loader_tokens", n_tokens, loader=self.loader_id)
        count("loader_rows", len(self._rows) - n_rows, loader=self.loader_id)

    def next_batch(self, timeout_polls: int = 10_000) -> np.ndarray | None:
        """Assemble one (batch_size, seq_len+1) batch synchronously.
        Returns None when the stream is exhausted before a full batch.

        Spans: ``loader/next_batch``, holding a ``loader/poll`` per
        ``consumer.poll`` and ``loader/pack`` around tokenizing, packing
        and the final stack; counters ``loader_records``,
        ``loader_bytes``, ``loader_starved_polls``, ``loader_tokens``,
        ``loader_rows``, ``loader_batches``, each labelled
        ``loader=<loader_id>``."""
        with span("loader/next_batch"), self._state_lock:
            polls = 0
            while len(self._rows) < self.batch_size:
                with span("loader/poll"):
                    recs = self.consumer.poll(self.poll_records)
                    if recs:
                        count("loader_records", len(recs),
                              loader=self.loader_id)
                        count("loader_bytes", sum(len(r.value) for r in recs),
                              loader=self.loader_id)
                    else:
                        count("loader_starved_polls", loader=self.loader_id)
                if not recs:
                    polls += 1
                    if polls >= timeout_polls:
                        return None
                    continue
                with span("loader/pack"):
                    self._ingest_records(recs)
            with span("loader/pack"):
                batch = np.stack(self._rows[:self.batch_size])
                del self._rows[:self.batch_size]
                self._batches_emitted += 1
                count("loader_batches", loader=self.loader_id)
            return batch

    # ------------------------------------------------------------------
    # Asynchronous path: background readers + bounded prefetch queue.
    # ------------------------------------------------------------------
    def start(self) -> None:
        if self._threads:
            return
        self._stop.clear()
        t = threading.Thread(target=self._assembler, name="loader-assembler",
                             daemon=True)
        self._threads.append(t)
        t.start()

    def _assembler(self) -> None:
        chunk: list[np.ndarray] = []
        chunk_t0 = 0.0
        while not self._stop.is_set():
            batch = self.next_batch(timeout_polls=50)
            now = time.monotonic()
            if batch is not None:
                if not chunk:
                    chunk_t0 = now
                chunk.append(batch)
            if chunk and (batch is None
                          or len(chunk) >= self._chunk_batches
                          or now - chunk_t0 >= self._chunk_linger):
                self._prefetch.offer(_BatchEnvelope(chunk), block=True)
                chunk = []

    def get_prefetched(self, timeout: float = 30.0) -> np.ndarray | None:
        """Pop the next ready batch, unpacking whole prefetched chunks into a
        caller-local buffer — one queue round-trip amortized over up to
        ``prefetch_chunk`` batches."""
        if not self._drained:
            for env in self._prefetch.poll_batch(
                    self._prefetch.object_threshold, timeout=timeout):
                self._drained.extend(env.batches)
        return self._drained.popleft() if self._drained else None

    def stop(self) -> None:
        self._stop.set()
        for t in self._threads:
            t.join(timeout=5)
        self._threads.clear()

    # ------------------------------------------------------------------
    # Exactly-once checkpoint state
    # ------------------------------------------------------------------
    def state(self) -> dict:
        with self._state_lock:
            return {
                "positions": {str(k): int(v)
                              for k, v in self.consumer.positions().items()},
                "packer": self.packer.state(),
                "pending_rows": [r.tolist() for r in self._rows],
                "batches_emitted": self._batches_emitted,
            }

    def restore(self, state: dict) -> None:
        with self._state_lock:
            self.consumer.restore({int(k): int(v)
                                   for k, v in state["positions"].items()})
            self.packer.restore(state["packer"])
            self._rows = [np.asarray(r, dtype=np.int32)
                          for r in state.get("pending_rows", [])]
            self._batches_emitted = int(state.get("batches_emitted", 0))

    def commit(self) -> None:
        """At-least-once boundary for non-checkpoint consumers."""
        self.consumer.commit()

    @property
    def batches_emitted(self) -> int:
        return self._batches_emitted

    @property
    def starved_polls(self) -> int:
        """Times this loader polled an empty stream (its
        ``loader_starved_polls`` counter) — the 'ingestion is the
        bottleneck' signal surfaced to the trainer's metrics."""
        return tracer().value("loader_starved_polls", loader=self.loader_id)


class _BatchEnvelope:
    """Duck-typed FlowFile stand-in so a chunk of assembled batches rides the
    backpressured Connection without serialization (zero-copy)."""

    __slots__ = ("batches",)

    def __init__(self, batches: list[np.ndarray]) -> None:
        self.batches = batches

    @property
    def size(self) -> int:
        return sum(int(b.nbytes) for b in self.batches)
