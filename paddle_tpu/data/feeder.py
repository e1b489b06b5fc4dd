"""Host→device feeding with async prefetch.

Replaces the reference's DataFeeder/dataprovider_converter (reference:
paddle/py_paddle/dataprovider_converter.py) and the DoubleBuffer prefetch
thread (reference: gserver/dataproviders/DataProvider.h:249): batches are
converted to stacked numpy columns on a worker thread while the device
computes, then transferred with jax.device_put (optionally sharded over the
mesh's data axis).
"""

from __future__ import annotations

import collections
import itertools
import queue as queue_mod
import threading
from typing import Any, Callable, Iterator, Optional

import jax

from paddle_tpu.data.batch import stack_columns_counted
from paddle_tpu.obs.trace import Timeline, default_timeline


def _put(batch, sharding, timeline: Timeline, seq: Optional[int]):
    """device_put every leaf of one batch (sharded or not) under the
    `feeder.device_put` span, counting the host leaves' bytes from
    their shapes. A leaf already on the device is a no-op and counts
    nothing."""
    timeline.count("feeder.h2d_bytes", sum(
        x.nbytes for x in jax.tree.leaves(batch)
        if hasattr(x, "nbytes") and not isinstance(x, jax.Array)))
    with timeline.span("feeder.device_put", seq):
        if sharding is not None:
            return jax.tree.map(lambda x: jax.device_put(x, sharding),
                                batch)
        return jax.tree.map(jax.device_put, batch)


class DataFeeder:
    """Iterate device-ready batches from a batch-reader.

    convert_fn: list-of-samples -> pytree of np arrays (default:
    `stack_columns`, which copies a large dense column on several
    threads; the batches where it did are counted as
    `feeder.parallel_stacks`). sharding: optional jax.sharding.Sharding
    applied on device_put (the data-parallel split, replacing
    MultiGradientMachine's per-thread batch slicing, reference:
    MultiGradientMachine.h:73).
    timeline: where the feed times itself (obs.trace.Timeline; the
    process default unless given): `feeder.read` / `feeder.convert` /
    `feeder.queue_put` on the worker thread, `feeder.queue_wait` /
    `feeder.device_put` on the consumer's, all of one batch under the
    batch's ordinal (docs/OBSERVABILITY.md § Training timeline).
    """

    def __init__(
        self,
        convert_fn: Optional[Callable] = None,
        sharding=None,
        prefetch: int = 2,
        timeline: Optional[Timeline] = None,
    ):
        self.convert_fn = convert_fn
        self.sharding = sharding
        self.prefetch = prefetch
        self.timeline = timeline if timeline is not None \
            else default_timeline()

    def _stack_columns(self, samples):
        cols, sliced = stack_columns_counted(samples)
        self.timeline.count("feeder.parallel_stacks", 1 if sliced else 0)
        return cols

    def __call__(self, batch_reader) -> Iterator[Any]:
        end = object()
        q: queue_mod.Queue = queue_mod.Queue(maxsize=self.prefetch)
        errors = []
        tl = self.timeline
        convert = self.convert_fn or self._stack_columns
        # set when the consumer stops, early or not: the worker then
        # puts nothing more (the consumer drains what a put may be
        # blocked on), so it ends instead of holding its batches
        stopped = threading.Event()

        def worker():
            try:
                raw_batches = iter(batch_reader())
                seq = 0
                while not stopped.is_set():
                    with tl.span("feeder.read", seq) as read:
                        raw = next(raw_batches, end)
                        if raw is end:
                            read.discard()
                    if raw is end:
                        break
                    with tl.span("feeder.convert", seq):
                        host_batch = convert(raw)
                    if stopped.is_set():
                        break
                    with tl.span("feeder.queue_put", seq):
                        q.put(host_batch)
                    seq += 1
            except BaseException as e:
                errors.append(e)
            finally:
                if not stopped.is_set():
                    q.put(end)

        threading.Thread(target=worker, daemon=True,
                         name="paddle_tpu-feeder").start()
        seq = 0
        try:
            while True:
                with tl.span("feeder.queue_wait", seq) as wait:
                    depth = q.qsize()
                    host_batch = q.get()
                    if host_batch is end:
                        wait.discard()
                if host_batch is end:
                    if errors:
                        raise errors[0]
                    return
                # the reading before the sentinel's get belongs to no
                # batch: the sum stays within batches x prefetch
                tl.count("feeder.queue_depth_sum", depth)
                tl.count("feeder.batches")
                yield _put(host_batch, self.sharding, tl, seq)
                seq += 1
        finally:
            stopped.set()
            try:
                while True:
                    q.get_nowait()
            except queue_mod.Empty:
                pass


def prefetch_to_device(iterator: Iterator, size: int = 2,
                       sharding=None,
                       timeline: Optional[Timeline] = None) -> Iterator:
    """Keep `size` batches already transferred ahead of the consumer.

    Each buffered batch is device_put here (async — the transfer runs in
    the background), so the NEXT batch's H2D DMA overlaps the current
    step's compute — the device-side half of the reference's
    DoubleBuffer (reference: gserver/dataproviders/DataProvider.h:249;
    its GPU path staged into pinned memory the same way). Re-putting an
    already-device-resident batch (e.g. from DataFeeder) is a no-op.
    Each put is a `feeder.device_put` span of `timeline` (the process
    default unless given) and adds its host bytes to `feeder.h2d_bytes`.
    """
    tl = timeline if timeline is not None else default_timeline()
    buf = collections.deque()
    it = iter(iterator)
    seqs = itertools.count()
    try:
        for _ in range(size):
            buf.append(_put(next(it), sharding, tl, next(seqs)))
    except StopIteration:
        pass
    while buf:
        nxt = buf.popleft()
        try:
            buf.append(_put(next(it), sharding, tl, next(seqs)))
        except StopIteration:
            pass
        yield nxt
