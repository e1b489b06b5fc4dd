"""Batching: dense minibatches and packed ragged sequence batches.

Dense path = reference's v2 minibatch (reference: python/paddle/v2/
minibatch.py). Ragged path replaces the reference's LoD/Argument
sequenceStartPositions representation (reference: parameter/Argument.h:84,
framework/lod_tensor.h:57) with fixed-shape *packed segment batches*:
sequences concatenated on one time axis plus a segment-id vector — the
XLA-friendly equivalent of padding-free variable-length batching. Capacity
is static (required by XLA); overflow positions are masked out.
"""

from __future__ import annotations

import dataclasses
import os
import threading
from concurrent import futures
from typing import Any, Iterator, List, Optional, Sequence, Tuple

import numpy as np


def batch(reader, batch_size: int, drop_last: bool = True):
    """Group samples into lists of batch_size (reference: v2/minibatch.py)."""

    def batch_reader():
        buf = []
        for sample in reader():
            buf.append(sample)
            if len(buf) == batch_size:
                yield buf
                buf = []
        if buf and not drop_last:
            yield buf

    return batch_reader


# Which columns `stack_columns` copies on several threads: measured on the
# chip's host (TPU v5e VM, 13 cores, no transparent hugepages; PERF.md
# section 6, PR 27). `np.stack` of 256 float32 images (154 MB) took 165.5
# ms there; 155 ms of it is the first touch of a fresh array's 37,632
# pages (4.1 us each), and the same copy into touched memory takes 15 ms.
# The faults parallelise: 2 / 4 / 8 / 13 threads, each filling its slice
# of one fresh array, took 93.8 / 63.9 / 47.6 / 42.8 ms.
# - SLICED_MIN_BYTES: glibc serves an array under 32 MiB from memory the
#   process has touched before (M_MMAP_THRESHOLD grows to that and no
#   further), where `np.stack` runs at 10-17 GB/s: 16 MiB in 0.9 ms
#   against 3.5 ms sliced. From 32 MiB on every array is a fresh mmap:
#   37.9 ms against 11.1 ms in 8 slices.
# - SLICED_MIN_SAMPLE_BYTES: every `out[i] = x` gives the GIL away and
#   takes it back, tens of us under contention, and a sample's copy must
#   outlast that. 64 MiB in samples of 32 / 64 / 128 KiB: `np.stack` 79 /
#   76 / 74 ms, 8 slices 62 / 37 / 23 ms; in samples of 4 KiB 91 against
#   394 ms.
# - STACK_SLICES: `resnet50.train_bs256` with 2 / 4 / 6 / 8 / 12 slices
#   read `feeder.convert` 108.6 / 75.1 / 61.7 / 56.9 / 54.7 ms against a
#   device step of 96.6 ms: from 4 on the device sets the pace, 8 leaves
#   room for a shorter step, and past 8 only `trainer.dispatch` grows.
SLICED_MIN_BYTES = 32 << 20
SLICED_MIN_SAMPLE_BYTES = 64 << 10
STACK_SLICES = min(8, os.cpu_count() or 1)

_pool: Optional[futures.ThreadPoolExecutor] = None
_pool_lock = threading.Lock()


def _stack_pool() -> futures.ThreadPoolExecutor:
    """The process's one pool of copying threads, made on first use and
    shared by every `DataFeeder`. An executor's threads start as work
    arrives and are joined, idle, at interpreter exit."""
    global _pool
    with _pool_lock:
        if _pool is None:
            _pool = futures.ThreadPoolExecutor(
                max_workers=STACK_SLICES - 1,
                thread_name_prefix="paddle_tpu-stack")
        return _pool


def _copy_rows(out: np.ndarray, col: Sequence[np.ndarray],
               lo: int, hi: int) -> None:
    for i in range(lo, hi):
        out[i] = col[i]     # numpy copies plain dtypes without the GIL


def _sliceable(col: Sequence[Any]) -> bool:
    """Whether `np.stack(col)` is nothing but a large copy: every sample
    a C-contiguous `ndarray` (no subclass) of one shape and one native,
    non-object dtype, so that the result is C-contiguous of that dtype
    whichever way it is filled."""
    first = col[0]
    if STACK_SLICES < 2 or type(first) is not np.ndarray \
            or first.nbytes < SLICED_MIN_SAMPLE_BYTES \
            or len(col) * first.nbytes < SLICED_MIN_BYTES:
        return False
    shape, dtype = first.shape, first.dtype
    if dtype.hasobject or not dtype.isnative:
        return False
    return all(type(x) is np.ndarray and x.shape == shape
               and x.dtype == dtype and x.flags.c_contiguous for x in col)


def _stack_sliced(col: Sequence[np.ndarray]) -> np.ndarray:
    n, k = len(col), min(STACK_SLICES, len(col))
    out = np.empty((n,) + col[0].shape, col[0].dtype)
    cuts = [n * i // k for i in range(k + 1)]
    pool = _stack_pool()
    rest = [pool.submit(_copy_rows, out, col, cuts[i], cuts[i + 1])
            for i in range(1, k)]
    try:
        _copy_rows(out, col, cuts[0], cuts[1])
    finally:
        futures.wait(rest)      # nobody writes `out` once this returns
    for f in rest:
        f.result()
    return out


def stack_columns_counted(samples: Sequence[tuple]) -> Tuple[tuple, int]:
    """`stack_columns(samples)` and how many of its columns were copied
    in parallel slices: what `DataFeeder` counts as
    `feeder.parallel_stacks`."""
    cols, sliced = [], 0
    for col in zip(*samples):
        if _sliceable(col):
            cols.append(_stack_sliced(col))
            sliced += 1
        else:
            cols.append(np.stack([np.asarray(x) for x in col]))
    return tuple(cols), sliced


def stack_columns(samples: Sequence[tuple]) -> tuple:
    """Turn a list of tuple-samples into a tuple of stacked np arrays:
    `np.stack` column by column, every column a fresh array. A large
    dense column (`_sliceable`) is copied by several threads at once;
    the result is the same, bit for bit."""
    return stack_columns_counted(samples)[0]


@dataclasses.dataclass
class SequenceBatch:
    """A packed ragged batch: the LoD-equivalent, in fixed shapes.

    tokens:     [capacity, ...] concatenated timesteps of all sequences
    segment_ids:[capacity] int32, which sequence each position belongs to
                (== max_seqs for padding slots; ops treat ids >= max_seqs
                as invalid)
    positions:  [capacity] int32, timestep index within the sequence
    lengths:    [max_seqs] int32 per-sequence lengths (0 for empty slots)
    num_seqs:   int, actual number of sequences
    mask:       [capacity] bool, True for real positions

    Nested (2-level) sequences (reference: Argument.h:90
    subSequenceStartPositions) are expressed with an extra outer_segment_ids
    field mapping each position to its outer sequence.
    """

    tokens: Any
    segment_ids: np.ndarray
    positions: np.ndarray
    lengths: np.ndarray
    num_seqs: int
    mask: np.ndarray
    outer_segment_ids: Optional[np.ndarray] = None

    @property
    def capacity(self) -> int:
        return self.segment_ids.shape[0]

    @property
    def max_seqs(self) -> int:
        return self.lengths.shape[0]


def pack_sequences(
    seqs: Sequence[np.ndarray],
    capacity: Optional[int] = None,
    max_seqs: Optional[int] = None,
    outer_ids: Optional[Sequence[int]] = None,
) -> SequenceBatch:
    """Pack a list of variable-length sequences into one SequenceBatch.

    seqs: list of [len_i, ...] arrays. capacity defaults to total length
    rounded up to a multiple of 8 (TPU sublane); max_seqs to len(seqs).
    """
    seqs = [np.asarray(s) for s in seqs]
    lengths = [len(s) for s in seqs]
    total = sum(lengths)
    if capacity is None:
        capacity = max(8, -(-total // 8) * 8)
    if max_seqs is None:
        max_seqs = len(seqs)
    if total > capacity:
        raise ValueError(f"total length {total} exceeds capacity {capacity}")
    if len(seqs) > max_seqs:
        raise ValueError(f"{len(seqs)} sequences exceed max_seqs {max_seqs}")

    feat_shape = seqs[0].shape[1:] if seqs else ()
    dtype = seqs[0].dtype if seqs else np.float32
    tokens = np.zeros((capacity,) + feat_shape, dtype=dtype)
    # padding slots carry segment id == max_seqs, which every segment op
    # treats as invalid (ids are valid iff < num_segments == max_seqs)
    segment_ids = np.full((capacity,), max_seqs, np.int32)
    positions = np.zeros((capacity,), np.int32)
    mask = np.zeros((capacity,), bool)
    out_lengths = np.zeros((max_seqs,), np.int32)
    outer_seg = None
    if outer_ids is not None:
        outer_seg = np.full((capacity,), max(list(outer_ids) or [0]) + 1, np.int32)

    offset = 0
    for i, s in enumerate(seqs):
        n = len(s)
        tokens[offset : offset + n] = s
        segment_ids[offset : offset + n] = i
        positions[offset : offset + n] = np.arange(n)
        mask[offset : offset + n] = True
        out_lengths[i] = n
        if outer_seg is not None:
            outer_seg[offset : offset + n] = outer_ids[i]
        offset += n

    return SequenceBatch(
        tokens=tokens,
        segment_ids=segment_ids,
        positions=positions,
        lengths=out_lengths,
        num_seqs=len(seqs),
        mask=mask,
        outer_segment_ids=outer_seg,
    )


def pad_sequences(seqs: Sequence[np.ndarray], max_len: Optional[int] = None,
                  pad_value=0):
    """Dense [B, T, ...] padded batch + lengths, for scan-based RNNs.

    The packed representation (pack_sequences) is for position-wise ops;
    time-recurrent layers consume this time-major-able dense layout, the
    analogue of the reference's SequenceToBatch reordering
    (reference: gserver/layers/SequenceToBatch.h:41).
    """
    seqs = [np.asarray(s) for s in seqs]
    lengths = np.asarray([len(s) for s in seqs], np.int32)
    t = int(max_len or (max(lengths) if len(seqs) else 1))
    feat = seqs[0].shape[1:] if seqs else ()
    out = np.full((len(seqs), t) + feat, pad_value, dtype=seqs[0].dtype if seqs else np.float32)
    for i, s in enumerate(seqs):
        n = min(len(s), t)
        out[i, :n] = s[:n]
    return out, lengths


def bucket_by_length(reader, batch_size: int, bucket_bounds: Sequence[int],
                     len_fn=len, drop_last: bool = False):
    """Bucketed batching to bound padding waste under static shapes."""
    bounds = sorted(bucket_bounds)

    def bucket_of(n):
        for i, b in enumerate(bounds):
            if n <= b:
                return i
        return len(bounds)

    def new_reader():
        buckets: List[List[Any]] = [[] for _ in range(len(bounds) + 1)]
        for sample in reader():
            i = bucket_of(len_fn(sample))
            buckets[i].append(sample)
            if len(buckets[i]) == batch_size:
                yield buckets[i]
                buckets[i] = []
        if not drop_last:
            for b in buckets:
                if b:
                    yield b

    return new_reader
