"""Batch loaders over tokenized corpora with a C hot path and
background prefetch.

Design (TPU-first): the device step is the bottleneck resource, so the
loader's job is to make batch assembly invisible — a daemon thread
builds the next ``prefetch`` batches into fresh numpy buffers while the
accelerator runs, and the iterator hands them over without copies. All
randomness is derived from ``(seed, epoch)`` / ``(seed, batch_index)``
pairs, so a run is reproducible regardless of prefetch timing.
"""

from __future__ import annotations

import ctypes
import os
import queue
import threading
from typing import Optional, Sequence

import numpy as np

from apex_tpu import profiler
from apex_tpu._native import build_ctypes_lib

_LIB = None
_TRIED = False
_SRC = os.path.join(os.path.dirname(__file__), os.pardir, os.pardir,
                    "csrc", "dataloader.c")


def _build_and_load() -> Optional[ctypes.CDLL]:
    global _LIB, _TRIED
    if _TRIED:
        return _LIB
    _TRIED = True
    lib = build_ctypes_lib(_SRC, "dataloader")
    if lib is not None:
        lib.apex_shuffle_indices.argtypes = [
            ctypes.c_void_p, ctypes.c_size_t, ctypes.c_uint64]
        lib.apex_gather_rows.argtypes = [
            ctypes.c_void_p, ctypes.c_size_t, ctypes.c_void_p,
            ctypes.c_size_t, ctypes.c_void_p]
        lib.apex_mlm_mask.argtypes = [
            ctypes.c_void_p, ctypes.c_void_p, ctypes.c_void_p,
            ctypes.c_size_t, ctypes.c_int32, ctypes.c_int32,
            ctypes.c_void_p, ctypes.c_size_t, ctypes.c_uint32,
            ctypes.c_uint64]
    _LIB = lib
    return _LIB


def native_available() -> bool:
    return _build_and_load() is not None


def _shuffled_indices(n: int, seed: int) -> np.ndarray:
    lib = _build_and_load()
    idx = np.empty(n, np.uint64)
    if lib is not None:
        lib.apex_shuffle_indices(idx.ctypes.data_as(ctypes.c_void_p), n,
                                 ctypes.c_uint64(seed))
        return idx
    rng = np.random.RandomState(seed & 0x7FFFFFFF)
    return rng.permutation(n).astype(np.uint64)


def _gather_rows(corpus: np.ndarray, idx: np.ndarray) -> np.ndarray:
    lib = _build_and_load()
    out = np.empty((len(idx), corpus.shape[1]), np.int32)
    if lib is not None:
        lib.apex_gather_rows(
            corpus.ctypes.data_as(ctypes.c_void_p), corpus.shape[1],
            np.ascontiguousarray(idx).ctypes.data_as(ctypes.c_void_p),
            len(idx), out.ctypes.data_as(ctypes.c_void_p))
        return out
    np.take(corpus, idx.astype(np.int64), axis=0, out=out)
    return out


def _mlm_mask(tokens: np.ndarray, vocab_size: int, mask_id: int,
              special_ids: np.ndarray, prob: float, seed: int):
    lib = _build_and_load()
    ids = np.empty_like(tokens)
    labels = np.empty_like(tokens)
    q16 = min(65535, max(0, int(prob * 65536)))
    if lib is not None:
        lib.apex_mlm_mask(
            tokens.ctypes.data_as(ctypes.c_void_p),
            ids.ctypes.data_as(ctypes.c_void_p),
            labels.ctypes.data_as(ctypes.c_void_p),
            tokens.size, vocab_size, mask_id,
            special_ids.ctypes.data_as(ctypes.c_void_p), special_ids.size,
            q16, ctypes.c_uint64(seed))
        return ids, labels
    # numpy fallback: same contract, different RNG stream
    rng = np.random.RandomState(seed & 0x7FFFFFFF)
    flat = tokens.reshape(-1)
    ids_f = flat.copy()
    labels_f = np.full_like(flat, -1)
    eligible = ~np.isin(flat, special_ids)
    sel = eligible & (rng.rand(flat.size) < prob)
    labels_f[sel] = flat[sel]
    kind = rng.rand(flat.size)
    mask_pos = sel & (kind < 0.8)
    rand_pos = sel & (kind >= 0.8) & (kind < 0.9)
    ids_f[mask_pos] = mask_id
    ids_f[rand_pos] = rng.randint(0, vocab_size, rand_pos.sum())
    return ids_f.reshape(tokens.shape), labels_f.reshape(tokens.shape)


class _PrefetchIterator:
    """Daemon-thread prefetcher: builds up to ``depth`` batches ahead.

    Worker exceptions are enqueued and re-raised in the consumer (a
    batch-assembly error crashes the training loop, never hangs it), and
    abandoning the iterator early (``break``) releases the worker via
    :meth:`close` — the bounded ``put`` polls a stop event instead of
    blocking forever."""

    _DONE = object()

    def __init__(self, make_batch, n_batches: int, depth: int):
        self._q = queue.Queue(maxsize=max(depth, 1))
        self._stop = threading.Event()

        def put(item) -> bool:
            while not self._stop.is_set():
                try:
                    self._q.put(item, timeout=0.1)
                    return True
                except queue.Full:
                    continue
            return False

        def work():
            try:
                for i in range(n_batches):
                    if not put(make_batch(i)):
                        return
            except BaseException as e:  # noqa: BLE001 — re-raised consumer-side
                put(e)
                return
            put(self._DONE)

        self._thread = threading.Thread(target=work, daemon=True)
        self._thread.start()

    def close(self):
        """Release the worker thread (called on early abandonment)."""
        self._stop.set()
        while True:  # drain so a blocked put wakes promptly
            try:
                self._q.get_nowait()
            except queue.Empty:
                break

    def __del__(self):
        self.close()

    def __iter__(self):
        return self

    def __next__(self):
        with profiler.annotate(profiler.DATA_WAIT):
            item = self._q.get()
        if item is self._DONE:
            raise StopIteration
        if isinstance(item, BaseException):
            raise item
        return item


class _BaseLoader:
    """Shared epoch/shuffle/prefetch machinery.

    corpus: (N, S) int32 array of tokenized sequences (memmap works).

    ``drop_last=False`` (torch-DataLoader parity) keeps the epoch tail
    when the corpus is not batch-divisible — but with STATIC shapes:
    the final batch is padded to ``batch_size`` by repeating its last
    valid row, and every yielded batch gains a trailing
    ``sample_weights`` float32 (batch,) array (1.0 valid / 0.0 pad) so
    losses mask the padding without any per-tail recompile. (A
    torch-style smaller tail batch would change the jit input shape and
    force an XLA recompile each epoch.)
    """

    def __init__(self, corpus, batch_size: int, *, seed: int = 0,
                 shuffle: bool = True, drop_last: bool = True,
                 prefetch: int = 2):
        self.corpus = np.ascontiguousarray(np.asarray(corpus, np.int32))
        if self.corpus.ndim != 2:
            raise ValueError(
                f"corpus must be (num_sequences, seq_len), got "
                f"{self.corpus.shape}")
        self.batch_size = int(batch_size)
        self.seed = int(seed)
        self.shuffle = shuffle
        self.drop_last = drop_last
        self.prefetch = int(prefetch)
        self.epoch = 0

    def __len__(self):
        n, b = len(self.corpus), self.batch_size
        return n // b if self.drop_last else -(-n // b)

    def valid_rows(self, b: int) -> int:
        """Number of non-padding rows in batch ``b`` (== batch_size for
        all but a ``drop_last=False`` epoch tail)."""
        if b < 0 or b >= len(self):
            raise IndexError(f"batch {b} out of range [0, {len(self)})")
        if b < len(self.corpus) // self.batch_size:
            return self.batch_size
        return len(self.corpus) - b * self.batch_size

    def _batch_rows(self, order: np.ndarray, b: int):
        """(row indices padded to batch_size, sample weights)."""
        rows = order[b * self.batch_size:(b + 1) * self.batch_size]
        valid = len(rows)
        if valid < self.batch_size:  # pad-and-mask the epoch tail
            rows = np.concatenate(
                [rows, np.repeat(rows[-1:], self.batch_size - valid)])
        weights = np.zeros(self.batch_size, np.float32)
        weights[:valid] = 1.0
        return rows, weights

    def set_epoch(self, epoch: int):
        """Reshuffle for a new epoch (distributed-sampler analog)."""
        self.epoch = int(epoch)

    def _epoch_indices(self) -> np.ndarray:
        if not self.shuffle:
            return np.arange(len(self.corpus), dtype=np.uint64)
        return _shuffled_indices(len(self.corpus),
                                 (self.seed << 20) ^ self.epoch)

    def _make_batch(self, order: np.ndarray, b: int):
        raise NotImplementedError

    def __iter__(self):
        order = self._epoch_indices()
        return _PrefetchIterator(
            lambda b: self._make_batch(order, b), len(self), self.prefetch)


class MLMBatchLoader(_BaseLoader):
    """BERT masked-LM batches: yields ``(input_ids, mlm_labels)`` int32
    numpy arrays of shape (batch, seq); labels are -1 on unmasked
    positions (the convention ``models.bert.pretraining_loss`` expects).

    With ``drop_last=False`` every batch is
    ``(input_ids, mlm_labels, sample_weights)``; padding rows of the
    epoch tail carry all ``-1`` labels (zero MLM loss) and weight 0.
    """

    def __init__(self, corpus, batch_size: int, vocab_size: int,
                 mask_id: int, special_ids: Sequence[int] = (),
                 mask_prob: float = 0.15, **kw):
        super().__init__(corpus, batch_size, **kw)
        self.vocab_size = int(vocab_size)
        self.mask_id = int(mask_id)
        self.special_ids = np.asarray(sorted(set(special_ids)), np.int32)
        self.mask_prob = float(mask_prob)

    def _make_batch(self, order: np.ndarray, b: int):
        rows, weights = self._batch_rows(order, b)
        tokens = _gather_rows(self.corpus, rows)
        ids, labels = _mlm_mask(
            tokens, self.vocab_size, self.mask_id, self.special_ids,
            self.mask_prob,
            (self.seed << 40) ^ (self.epoch << 20) ^ (b + 1))
        if self.drop_last:
            return ids, labels
        labels[weights == 0.0] = -1  # padding rows: no loss positions
        return ids, labels, weights


class CausalLMBatchLoader(_BaseLoader):
    """GPT-style batches: yields ``input_ids`` (batch, seq) int32; the
    next-token shift lives in ``models.gpt.lm_loss``. With
    ``drop_last=False`` every batch is ``(input_ids, sample_weights)``
    (see :class:`_BaseLoader`)."""

    def _make_batch(self, order: np.ndarray, b: int):
        rows, weights = self._batch_rows(order, b)
        ids = _gather_rows(self.corpus, rows)
        if self.drop_last:
            return ids
        return ids, weights
