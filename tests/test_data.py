"""Data pipeline tests (reference: python/paddle/v2/reader/tests/)."""

import os
import subprocess
import sys
import threading
import time

import numpy as np
import pytest

from paddle_tpu.data import batch as B
from paddle_tpu.data import datasets, reader as R


def _dense(n, nbytes_each, dtype=np.float32, seed=0):
    """`n` distinct C-contiguous samples of `nbytes_each` bytes, views
    of one pool as a reader over a decoded file hands them out."""
    m, rest = divmod(nbytes_each, 4 * np.dtype(dtype).itemsize)
    assert rest == 0
    pool = np.random.RandomState(seed).rand(n, m, 4).astype(dtype)
    return [pool[i] for i in range(n)]


_S = B.SLICED_MIN_SAMPLE_BYTES


@pytest.fixture
def column_of_16(monkeypatch):
    """The column's threshold brought down to 16 samples of the least
    size, so that the cases stay small; the sample's threshold is the
    measured one."""
    monkeypatch.setattr(B, "SLICED_MIN_BYTES", 16 * _S)


# name -> () -> (samples, columns that take the sliced copy), under
# `column_of_16`
STACK_CASES = {
    "image_and_label": lambda: (
        [(x, i % 7) for i, x in enumerate(_dense(16, 2 * _S))], 1),
    "slices_do_not_divide_13": lambda: (
        [(x,) for x in _dense(13, 2 * _S)], 1),
    "fewer_samples_than_slices": lambda: (
        [(x, np.int32(1)) for x in _dense(1, 32 * _S)], 1),
    "two_large_columns": lambda: (
        list(zip(_dense(8, 4 * _S), _dense(8, 8 * _S, np.float64, 1))), 2),
    "column_just_under_its_threshold": lambda: (
        [(x,) for x in _dense(15, _S + _S // 16)], 0),
    "column_just_at_its_threshold": lambda: (
        [(x,) for x in _dense(16, _S)], 1),
    "samples_just_under_their_threshold": lambda: (
        [(x,) for x in _dense(32, _S - 16)], 0),
    "many_small_samples": lambda: (
        [(x,) for x in _dense(512, _S // 16)], 0),
    "transposed_views": lambda: (
        [(x.T,) for x in _dense(8, 4 * _S)], 0),
    "strided_and_flipped_views": lambda: (
        [(x[::2, ::-1],) for x in _dense(8, 16 * _S)], 0),
    "one_sample_not_contiguous": lambda: (
        [(x,) for x in _dense(7, 4 * _S)]
        + [(np.asfortranarray(_dense(1, 4 * _S)[0]),)], 0),
    "mixed_dtypes": lambda: (
        [(x,) for x in _dense(4, 4 * _S)]
        + [(x,) for x in _dense(4, 8 * _S, np.float64)], 0),
    "mismatched_shapes": lambda: (
        [(x,) for x in _dense(4, 4 * _S)]
        + [(x,) for x in _dense(4, 8 * _S)], 0),
    "big_endian": lambda: (
        [(x.astype(">f4"),) for x in _dense(8, 4 * _S)], 0),
    "ndarray_subclass": lambda: (
        [(np.ma.masked_array(x),) for x in _dense(8, 4 * _S)], 0),
    "python_scalars_and_lists": lambda: (
        [(1.5, [i, i + 1], "a") for i in range(16)], 0),
}


def test_thresholds_as_measured_route_the_batches_users_feed():
    """With the constants as they stand: an image batch of 256 is
    sliced; MNIST rows, token ids and a small image batch are not."""
    image = np.zeros((224, 224, 3), np.float32)
    assert B._sliceable([image] * 256) == (B.STACK_SLICES > 1)
    assert not B._sliceable([image] * 32)                   # 19 MB
    assert not B._sliceable([np.zeros(784, np.float32)] * 128)
    assert not B._sliceable([np.zeros(2048, np.int32)] * 8192)  # 64 MiB
    assert not B._sliceable([3] * 256)


def counting_reader(n=10):
    def r():
        return iter(range(n))

    return r


class TestReaders:
    def test_map_readers(self):
        r = R.map_readers(lambda a, b: a + b, counting_reader(3), counting_reader(3))
        assert list(r()) == [0, 2, 4]

    def test_shuffle_preserves_items(self):
        r = R.shuffle(counting_reader(20), 5, seed=0)
        assert sorted(r()) == list(range(20))

    def test_chain(self):
        r = R.chain(counting_reader(2), counting_reader(3))
        assert list(r()) == [0, 1, 0, 1, 2]

    def test_compose(self):
        r = R.compose(counting_reader(3), counting_reader(3))
        assert list(r()) == [(0, 0), (1, 1), (2, 2)]

    def test_compose_misaligned_raises(self):
        r = R.compose(counting_reader(2), counting_reader(3))
        with pytest.raises(R.ComposeNotAligned):
            list(r())

    def test_buffered(self):
        r = R.buffered(counting_reader(50), 8)
        assert list(r()) == list(range(50))

    def test_buffered_propagates_error(self):
        def bad():
            yield 1
            raise RuntimeError("boom")

        with pytest.raises(RuntimeError):
            list(R.buffered(lambda: bad(), 2)())

    def test_firstn(self):
        assert list(R.firstn(counting_reader(10), 3)()) == [0, 1, 2]

    def test_xmap_unordered(self):
        r = R.xmap_readers(lambda x: x * 2, counting_reader(20), 4, 8)
        assert sorted(r()) == [2 * i for i in range(20)]

    def test_xmap_ordered(self):
        r = R.xmap_readers(lambda x: x * 2, counting_reader(20), 4, 8, order=True)
        assert list(r()) == [2 * i for i in range(20)]

    def test_cache(self):
        calls = []

        def src():
            calls.append(1)
            return iter(range(3))

        r = R.cache(src)
        assert list(r()) == [0, 1, 2]
        assert list(r()) == [0, 1, 2]
        assert len(calls) == 1


class TestBatch:
    def test_batch_drop_last(self):
        b = B.batch(counting_reader(10), 4)
        batches = list(b())
        assert [len(x) for x in batches] == [4, 4]

    def test_batch_keep_last(self):
        b = B.batch(counting_reader(10), 4, drop_last=False)
        assert [len(x) for x in b()] == [4, 4, 2]

    def test_stack_columns(self):
        samples = [(np.zeros((2,)), 1), (np.ones((2,)), 0)]
        x, y = B.stack_columns(samples)
        assert x.shape == (2, 2) and y.shape == (2,)

    @pytest.mark.parametrize("case", sorted(STACK_CASES))
    def test_stack_columns_is_np_stack_column_by_column(
            self, case, column_of_16):
        samples, sliced = STACK_CASES[case]()
        try:
            want = [np.stack([np.asarray(x) for x in col])
                    for col in zip(*samples)]
        except ValueError as e:
            with pytest.raises(ValueError) as got:
                B.stack_columns(samples)
            assert str(got.value) == str(e)
            return
        cols, n_sliced = B.stack_columns_counted(samples)
        assert n_sliced == (sliced if B.STACK_SLICES > 1 else 0)
        assert len(cols) == len(want)
        for got, ref in zip(cols, want):
            assert type(got) is type(ref)
            assert got.dtype == ref.dtype and got.shape == ref.shape
            assert got.flags.c_contiguous == ref.flags.c_contiguous
            assert got.flags.owndata and got.flags.writeable
            np.testing.assert_array_equal(got, ref)
        same = B.stack_columns(samples)
        assert all(np.array_equal(a, b) for a, b in zip(same, cols))

    def test_successive_large_batches_share_no_memory(self, column_of_16):
        samples, _ = STACK_CASES["image_and_label"]()
        first = B.stack_columns(samples)
        second = B.stack_columns(samples)
        for a, b, col in zip(first, second, zip(*samples)):
            assert not np.shares_memory(a, b)
            assert not any(np.shares_memory(a, x) for x in col
                           if isinstance(x, np.ndarray))
        first[0][...] = -1.0        # the user's batch is the user's
        np.testing.assert_array_equal(second[0][0], samples[0][0])

    @pytest.mark.parametrize("where", ["calling_thread", "pool_thread"])
    def test_error_in_a_slice_reaches_the_caller(
            self, monkeypatch, where, column_of_16):
        copy_rows = B._copy_rows
        seen = []

        def failing(out, col, lo, hi):
            seen.append(lo)
            if (lo == 0) == (where == "calling_thread"):
                raise RuntimeError(f"slice at {lo}")
            copy_rows(out, col, lo, hi)

        samples, _ = STACK_CASES["image_and_label"]()
        with monkeypatch.context() as patched:
            patched.setattr(B, "_copy_rows", failing)
            patched.setattr(B, "STACK_SLICES", 3)
            with pytest.raises(RuntimeError, match="slice at"):
                B.stack_columns(samples)
        assert len(seen) == 3       # every slice ran before the raise
        x, _ = B.stack_columns(samples)     # and the pool still works
        np.testing.assert_array_equal(x[-1], samples[-1][0])

    def test_pool_threads_do_not_hold_the_interpreter(self):
        """A process that stacked a large batch, and whose feeder is
        still mid-pass, exits when its main thread ends."""
        code = (
            "import threading, numpy as np\n"
            "from paddle_tpu import data\n"
            "from paddle_tpu.data import batch as B\n"
            "x = np.ones((B.SLICED_MIN_BYTES // 32,), np.float32)\n"
            "read = lambda: ((x, 0) for _ in range(10**9))\n"
            "it = data.DataFeeder()(data.batch_reader(read, 8))\n"
            "assert np.asarray(next(it)[0]).shape == (8,) + x.shape\n"
            "print(sorted(t.name for t in threading.enumerate()))\n")
        env = dict(os.environ, JAX_PLATFORMS="cpu")
        done = subprocess.run([sys.executable, "-c", code], env=env,
                              capture_output=True, text=True, timeout=120)
        assert done.returncode == 0, done.stderr
        assert "paddle_tpu-stack_0" in done.stdout
        assert "paddle_tpu-feeder" in done.stdout

    def test_many_callers_share_one_small_pool(self, column_of_16):
        """More callers than cores, one pool: every result whole, and no
        more copying threads than the slices need."""
        # an executor's threads take the daemon flag of the thread that
        # first gave them work, and in this process that may have been a
        # feeder's worker (another test file's): start from no pool
        with B._pool_lock:
            old, B._pool = B._pool, None
        if old is not None:
            old.shutdown(wait=True)
        samples, _ = STACK_CASES["image_and_label"]()
        want = np.stack([s[0] for s in samples])
        bad, deadline = [], time.monotonic() + 20.0

        def caller():
            for _ in range(5):
                if time.monotonic() > deadline:
                    bad.append("too slow")
                x, _ = B.stack_columns(samples)
                if not np.array_equal(x, want):
                    bad.append("torn batch")

        prev = sys.getswitchinterval()
        sys.setswitchinterval(1e-5)
        try:
            callers = [threading.Thread(target=caller)
                       for _ in range(2 * (os.cpu_count() or 1) + 1)]
            for t in callers:
                t.start()
            for t in callers:
                t.join(60.0)
        finally:
            sys.setswitchinterval(prev)
        assert not any(t.is_alive() for t in callers) and not bad
        pool = [t for t in threading.enumerate()
                if t.name.startswith("paddle_tpu-stack")]
        assert len(pool) <= max(B.STACK_SLICES - 1, 0)
        assert not any(t.daemon for t in pool)  # joined at exit, not cut

    def test_pack_sequences(self):
        seqs = [np.arange(3), np.arange(5), np.arange(2)]
        sb = B.pack_sequences(seqs, capacity=16, max_seqs=4)
        assert sb.tokens.shape == (16,)
        assert sb.num_seqs == 3
        np.testing.assert_array_equal(sb.lengths, [3, 5, 2, 0])
        np.testing.assert_array_equal(sb.segment_ids[:3], [0, 0, 0])
        np.testing.assert_array_equal(sb.segment_ids[3:8], [1] * 5)
        np.testing.assert_array_equal(sb.positions[3:8], np.arange(5))
        assert sb.mask[:10].all() and not sb.mask[10:].any()

    def test_pack_overflow_raises(self):
        with pytest.raises(ValueError):
            B.pack_sequences([np.arange(10)], capacity=8)

    def test_pad_sequences(self):
        x, lens = B.pad_sequences([np.arange(3), np.arange(1)])
        assert x.shape == (2, 3)
        np.testing.assert_array_equal(lens, [3, 1])
        np.testing.assert_array_equal(x[1], [0, 0, 0])

    def test_bucket_by_length(self):
        data = [np.zeros(n) for n in [2, 9, 3, 8, 2, 9]]
        r = B.bucket_by_length(lambda: iter(data), 2, [4])
        batches = list(r())
        for b in batches:
            lens = [len(s) for s in b]
            assert all(l <= 4 for l in lens) or all(l > 4 for l in lens)


class TestDatasets:
    def test_mnist_schema(self):
        it = datasets.mnist("train", synthetic_n=8)()
        img, lbl = next(it)
        assert img.shape == (28, 28, 1)
        assert img.dtype == np.float32
        assert 0 <= int(lbl) < 10

    def test_text_classification_schema(self):
        it = datasets.synthetic_text_classification(n=5)()
        tokens, label = next(it)
        assert tokens.ndim == 1 and tokens.dtype == np.int32

    def test_tagging_schema(self):
        it = datasets.synthetic_tagging(n=3)()
        tokens, tags = next(it)
        assert tokens.shape == tags.shape

    def test_translation_schema(self):
        it = datasets.synthetic_translation(n=3)()
        src, tgt = next(it)
        assert len(src) == len(tgt)

    def test_ctr_schema(self):
        it = datasets.synthetic_ctr(n=3)()
        ids, dense, click = next(it)
        assert ids.shape == (3,) and dense.shape == (8,)
