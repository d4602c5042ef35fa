"""Packed-token batches for the sequence models, with the detection
loader's contract: ``batches(n)`` yields host batches from a producer
thread through a bounded queue, ``health`` is a ``LoaderHealth``, every
build is a ``batch_build`` span (``seq``, ``rows``), and the batches go
to the device through the same ``DevicePrefetcher``.

The stream is synthetic and seeded: documents of log-normal length,
each ended by the end-of-document id, their ids Zipf-distributed over
the held slice of the vocabulary, packed back to back into rows with no
mask between documents (as DeepSeek-V3 pre-trains).  A row is
``SEQ_LEN + 1`` ids: the inputs, the next token and the one after all
come from the same row.  One ``RandomState`` per loader, drawn only on
the producer's path: the same seed gives the same stream.
"""

from __future__ import annotations

import queue
import threading
import time
from typing import Dict, Iterator, Optional

import numpy as np

from eksml_tpu import telemetry
from eksml_tpu.data.robust import LoaderHealth


class TokenLoader:
    def __init__(self, batch_size: int, seq_len: int, vocab: int,
                 seed: int = 0, doc_len_median: float = 600.0,
                 doc_len_sigma: float = 1.2, doc_len_clip=(16, 16384),
                 zipf_exponent: float = 1.0, eod_id: int = 1,
                 prefetch: int = 4, host_id: int = 0):
        self.batch_size = int(batch_size)
        self.row_len = int(seq_len) + 1
        self.vocab = int(vocab)
        self.eod = int(eod_id)
        self.median = float(doc_len_median)
        self.sigma = float(doc_len_sigma)
        self.clip = tuple(int(x) for x in doc_len_clip)
        self.prefetch = int(prefetch)
        if not 0 <= self.eod < self.vocab:
            raise ValueError(f"end-of-document id {self.eod} is outside "
                             f"the {self.vocab} held rows of the "
                             "vocabulary")
        # every host packs a stream of its own
        self._stream_rng = np.random.RandomState(
            (int(seed) * 1000003 + int(host_id)) % (2 ** 32))
        # Zipf over the ids that are not the end-of-document id: rank r
        # (from 1) with weight r ** -a; low ids are the frequent ones
        ranks = np.arange(1, self.vocab, dtype=np.float64)
        weights = ranks ** -float(zipf_exponent)
        self._cdf = np.cumsum(weights / weights.sum())
        self._left = np.zeros((0,), np.int32)    # ids not yet in a row
        self.health = LoaderHealth()

    @classmethod
    def from_config(cls, cfg, batch_size: int, host_id: int = 0):
        """The stream ``LM.DATA`` sizes (the other parameters at their
        defaults above), seeded by ``TRAIN.SEED``."""
        data = cfg.LM.DATA
        return cls(batch_size, cfg.LM.SEQ_LEN, cfg.LM.VOCAB_ROWS,
                   seed=cfg.TRAIN.SEED,
                   doc_len_median=data.DOC_LEN_MEDIAN,
                   doc_len_clip=data.DOC_LEN_CLIP, host_id=host_id)

    def _document(self) -> np.ndarray:
        rng = self._stream_rng
        n = int(np.clip(round(rng.lognormal(np.log(self.median),
                                            self.sigma)), *self.clip))
        rank = np.searchsorted(self._cdf, rng.random_sample(n - 1))
        rank = np.minimum(rank, self.vocab - 2)
        ids = rank + (rank >= self.eod)          # skip the eod id
        return np.append(ids, self.eod).astype(np.int32)

    def _next_batch(self) -> Dict[str, np.ndarray]:
        need = self.batch_size * self.row_len
        parts, have = [self._left], len(self._left)
        while have < need:
            doc = self._document()
            parts.append(doc)
            have += len(doc)
        ids = np.concatenate(parts)
        self._left = ids[need:]
        return {"tokens": ids[:need].reshape(self.batch_size,
                                             self.row_len)}

    def batches(self, num_steps: Optional[int] = None
                ) -> Iterator[Dict[str, np.ndarray]]:
        """Yield ``num_steps`` batches (endless if None) through a
        background producer thread; the stream goes on where the last
        call left it."""
        q: "queue.Queue" = queue.Queue(maxsize=self.prefetch)
        stop = threading.Event()
        error = []

        def put_or_stop(item) -> bool:
            while not stop.is_set():
                try:
                    q.put(item, timeout=0.1)
                    return True
                except queue.Full:
                    continue
            return False

        def producer():
            produced = 0
            try:
                while not stop.is_set() and (num_steps is None
                                             or produced < num_steps):
                    # ends before the queue put: waiting on a full
                    # queue is back-pressure, not build time
                    with telemetry.span("batch_build", attrs={
                            "seq": produced, "rows": self.batch_size}):
                        t_build = time.monotonic()
                        batch = self._next_batch()
                        self.health.record_batch(
                            (time.monotonic() - t_build) * 1000)
                    if not put_or_stop(batch):
                        return
                    produced += 1
            except Exception as e:  # surfaced to the consumer below
                error.append(e)
            finally:
                put_or_stop(None)

        t = threading.Thread(target=producer, daemon=True,
                             name="loader-producer")
        self.health.queue_depth = q.qsize
        self.health.producer_alive = t.is_alive
        t.start()
        try:
            while True:
                batch = q.get()
                if batch is None:
                    if error:
                        raise error[0]
                    return
                yield batch
        finally:
            stop.set()
            t.join(timeout=5.0)
            self.health.queue_depth = lambda: 0
            self.health.producer_alive = lambda: False
