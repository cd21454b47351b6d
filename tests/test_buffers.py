"""Shuffling buffer tests (reference: ``tests/test_shuffling_buffer.py``)."""

import numpy as np
import pytest

from petastorm_tpu.buffers import (
    BatchedNoopShufflingBuffer, BatchedRandomShufflingBuffer,
    NoopShufflingBuffer, RandomShufflingBuffer,
)
from petastorm_tpu.codecs import CompressedImageCodec
from petastorm_tpu.fused import EncodedImageColumn
from petastorm_tpu.unischema import UnischemaField


class TestNoop:
    def test_fifo(self):
        buf = NoopShufflingBuffer()
        buf.add_many([1, 2, 3])
        assert buf.size == 3
        assert [buf.retrieve() for _ in range(3)] == [1, 2, 3]
        assert not buf.can_retrieve
        buf.finish()
        assert not buf.can_add


class TestRandom:
    def test_holds_until_min_after_retrieve(self):
        buf = RandomShufflingBuffer(10, min_after_retrieve=3, seed=0)
        buf.add_many([1, 2])
        assert not buf.can_retrieve  # below the decorrelation floor
        buf.add_many([3])
        assert buf.can_retrieve

    def test_floor_equal_to_capacity_does_not_deadlock(self):
        buf = RandomShufflingBuffer(3, min_after_retrieve=3, seed=0)
        buf.add_many([1, 2, 3])
        assert not buf.can_add
        assert buf.can_retrieve

    def test_floor_above_capacity_rejected(self):
        with pytest.raises(ValueError, match='capacity'):
            RandomShufflingBuffer(3, min_after_retrieve=4)

    def test_finish_drains_fully(self):
        buf = RandomShufflingBuffer(10, min_after_retrieve=5, seed=0)
        buf.add_many([1, 2, 3])
        buf.finish()
        out = []
        while buf.can_retrieve:
            out.append(buf.retrieve())
        assert sorted(out) == [1, 2, 3]

    def test_capacity_gates_can_add(self):
        buf = RandomShufflingBuffer(3, min_after_retrieve=0, seed=0)
        buf.add_many([1, 2])
        assert buf.can_add
        buf.add_many([3, 4])  # single add may overshoot capacity
        assert not buf.can_add
        with pytest.raises(RuntimeError):
            buf.add_many([5])

    def test_all_items_come_out_exactly_once(self):
        buf = RandomShufflingBuffer(1000, min_after_retrieve=10, seed=1)
        buf.add_many(list(range(500)))
        out = []
        while buf.can_retrieve:
            out.append(buf.retrieve())
        buf.finish()
        while buf.can_retrieve:
            out.append(buf.retrieve())
        assert sorted(out) == list(range(500))

    def test_output_is_shuffled(self):
        buf = RandomShufflingBuffer(1000, min_after_retrieve=0, seed=2)
        buf.add_many(list(range(200)))
        buf.finish()
        out = [buf.retrieve() for _ in range(200)]
        assert out != list(range(200))


def _chunk(start, n):
    return {'id': np.arange(start, start + n),
            'vec': np.arange(start, start + n, dtype=np.float32).reshape(-1, 1)
            * np.ones((1, 4), np.float32)}


class TestBatchedNoop:
    def test_rebatches_preserving_order(self):
        buf = BatchedNoopShufflingBuffer(batch_size=7)
        buf.add_many(_chunk(0, 10))
        buf.add_many(_chunk(10, 10))
        batches = []
        while buf.can_retrieve:
            batches.append(buf.retrieve())
        buf.finish()
        while buf.can_retrieve:
            batches.append(buf.retrieve())
        assert [len(b['id']) for b in batches] == [7, 7, 6]
        np.testing.assert_array_equal(
            np.concatenate([b['id'] for b in batches]), np.arange(20))
        last = batches[-1]
        np.testing.assert_array_equal(last['vec'][:, 0], last['id'])

    def test_empty_chunk_ignored(self):
        buf = BatchedNoopShufflingBuffer(batch_size=2)
        buf.add_many(_chunk(0, 0))
        assert buf.size == 0


class TestBatchedRandom:
    def test_exactly_once_and_row_alignment(self):
        buf = BatchedRandomShufflingBuffer(
            shuffling_buffer_capacity=64, min_after_retrieve=16,
            batch_size=8, extra_capacity=32, seed=0)
        seen = []
        start = 0
        for _ in range(6):
            buf.add_many(_chunk(start, 16))
            start += 16
            while buf.can_retrieve:
                b = buf.retrieve()
                # rows must stay internally consistent across columns
                np.testing.assert_array_equal(b['vec'][:, 2], b['id'])
                seen.extend(b['id'].tolist())
        buf.finish()
        while buf.can_retrieve:
            b = buf.retrieve()
            np.testing.assert_array_equal(b['vec'][:, 2], b['id'])
            seen.extend(b['id'].tolist())
        assert sorted(seen) == list(range(start))

    def test_shuffles_across_chunks(self):
        buf = BatchedRandomShufflingBuffer(
            shuffling_buffer_capacity=100, min_after_retrieve=50,
            batch_size=10, extra_capacity=100, seed=3)
        buf.add_many(_chunk(0, 100))
        first = buf.retrieve()['id']
        assert not np.array_equal(first, np.arange(10))

    def test_chunk_overflow_raises(self):
        buf = BatchedRandomShufflingBuffer(
            shuffling_buffer_capacity=4, min_after_retrieve=0, batch_size=2,
            extra_capacity=0, seed=0)
        with pytest.raises(RuntimeError, match='extra_capacity'):
            buf.add_many(_chunk(0, 10))

    def test_min_after_retrieve_floor(self):
        buf = BatchedRandomShufflingBuffer(
            shuffling_buffer_capacity=100, min_after_retrieve=20,
            batch_size=5, extra_capacity=0, seed=0)
        buf.add_many(_chunk(0, 15))
        assert not buf.can_retrieve
        buf.add_many(_chunk(15, 5))
        assert buf.can_retrieve

    def test_batch_size_above_capacity_rejected(self):
        with pytest.raises(ValueError, match='capacity'):
            BatchedRandomShufflingBuffer(
                shuffling_buffer_capacity=4, min_after_retrieve=0,
                batch_size=8)

    def test_dtype_widening_no_truncation(self):
        buf = BatchedRandomShufflingBuffer(
            shuffling_buffer_capacity=10, min_after_retrieve=0, batch_size=10,
            extra_capacity=10, seed=0)
        buf.add_many({'s': np.array(['abc', 'de'])})
        buf.add_many({'s': np.array(['abcdefghij'])})
        buf.finish()
        out = []
        while buf.can_retrieve:
            out.extend(buf.retrieve()['s'].tolist())
        assert sorted(out) == ['abc', 'abcdefghij', 'de']

    def test_object_dtype_columns(self):
        buf = BatchedRandomShufflingBuffer(
            shuffling_buffer_capacity=10, min_after_retrieve=0, batch_size=4,
            extra_capacity=10, seed=0)
        ragged = np.empty(6, dtype=object)
        for i in range(6):
            ragged[i] = np.arange(i + 1)
        buf.add_many({'id': np.arange(6), 'ragged': ragged})
        buf.finish()
        rows = 0
        while buf.can_retrieve:
            b = buf.retrieve()
            for rid, arr in zip(b['id'], b['ragged']):
                assert len(arr) == rid + 1
            rows += len(b['id'])
        assert rows == 6


_IMG = (4, 4, 3)
_IMG_FIELD = UnischemaField('image', np.uint8, _IMG,
                            CompressedImageCodec('png'), False)


def _image_chunk(start, n, encoded):
    """``n`` rows of ids and png images whose pixels encode the id; the
    image column still encoded or decoded."""
    import cv2
    ids = np.arange(start, start + n)
    images = np.stack([np.full(_IMG, i % 251, np.uint8) for i in ids])
    if not encoded:
        return {'id': ids, 'image': images}
    cells = [np.frombuffer(cv2.imencode('.png', im)[1].tobytes(), np.uint8)
             for im in images]
    return {'id': ids, 'image': EncodedImageColumn(_IMG_FIELD, cells)}


def _drain_shuffled(chunk_forms, seed=4):
    """Batches of a random buffer fed one chunk per entry of
    ``chunk_forms`` (True: image column encoded), encoded columns of the
    batches decoded; plus the buffer."""
    buf = BatchedRandomShufflingBuffer(
        shuffling_buffer_capacity=24, min_after_retrieve=12, batch_size=6,
        extra_capacity=12, seed=seed)
    batches = []

    def take():
        batch = buf.retrieve()
        image = batch['image']
        if isinstance(image, EncodedImageColumn):
            batch['image'] = image.materialize()
        batches.append(batch)

    for i, encoded in enumerate(chunk_forms):
        buf.add_many(_image_chunk(10 * i, 10, encoded))
        while buf.can_retrieve:
            take()
    buf.finish()
    while buf.can_retrieve:
        take()
    return batches, buf


class TestBatchedRandomEncoded:
    """The fused-decode carrier in the random buffer: cells are gathered
    in place of pixel rows, with the draws of the decoded column."""

    def test_encoded_column_draws_the_rows_of_the_decoded_column(self):
        forms = [True] * 7
        encoded, buf = _drain_shuffled(forms)
        decoded, _ = _drain_shuffled([False] * 7)
        assert len(encoded) == len(decoded)
        for a, b in zip(encoded, decoded):
            np.testing.assert_array_equal(a['id'], b['id'])
            np.testing.assert_array_equal(a['image'], b['image'])
            np.testing.assert_array_equal(a['image'][:, 0, 0, 0],
                                          a['id'] % 251)
        assert sorted(np.concatenate([b['id'] for b in encoded]).tolist()) \
            == list(range(70))
        # drained: no slot still holds a cell (it would pin its frame)
        assert all(c is None for c in buf._buffers['image'].cells)

    def test_retrieve_hands_back_encoded_cells(self):
        buf = BatchedRandomShufflingBuffer(
            shuffling_buffer_capacity=10, min_after_retrieve=0,
            batch_size=4, extra_capacity=10, seed=0)
        buf.add_many(_image_chunk(0, 10, True))
        batch = buf.retrieve()
        assert isinstance(batch['image'], EncodedImageColumn)
        assert batch['image'].shape == (4,) + _IMG
        assert batch['image'].field is _IMG_FIELD
        # the cells left behind the live rows are cleared
        assert all(c is None for c in buf._buffers['image'].cells[buf.size:])

    @pytest.mark.parametrize('forms', [(True, False, True, True),
                                       (False, True, False, True)])
    def test_mixed_chunks_keep_the_decoded_draws(self, forms):
        """A row-group the worker decoded (or one it deferred after a
        decoded one) joins the buffer without changing a draw, and each
        decode the mix forces is counted as a fused-decode decline."""
        from petastorm_tpu import telemetry as T
        from petastorm_tpu.fused import FUSED_FALLBACKS
        T.reset_for_tests()
        try:
            mixed, _ = _drain_shuffled(forms)
            declines = T.get_registry().counter_value(
                FUSED_FALLBACKS, reason='mixed-chunks')
        finally:
            T.reset_for_tests()
        # the first decoded chunk after encoded ones turns the column
        # decoded; every encoded chunk after that decodes on its add
        first = forms.index(False)
        assert declines == (first > 0) + sum(forms[first + 1:])
        decoded, _ = _drain_shuffled([False] * len(forms))
        assert len(mixed) == len(decoded)
        for a, b in zip(mixed, decoded):
            np.testing.assert_array_equal(a['id'], b['id'])
            np.testing.assert_array_equal(a['image'], b['image'])


class TestRandomizedOpSequences:
    """Long random interleavings of add/retrieve/finish must preserve the
    exactly-once invariant (reference: ``test_shuffling_buffer.py:223`` —
    test_longer_random_sequence_of_queue_ops)."""

    @pytest.mark.parametrize('capacity,min_after', [(20, 10), (64, 1),
                                                    (7, 7)])
    def test_row_buffer_invariants(self, capacity, min_after):
        rng = np.random.RandomState(capacity)
        buf = RandomShufflingBuffer(capacity, min_after_retrieve=min_after,
                                    seed=1)
        fed, got = [], []
        next_item = 0
        for _ in range(2000):
            if buf.can_add and rng.rand() < 0.55:
                chunk = [next_item + i for i in range(int(rng.randint(1, 4)))]
                next_item += len(chunk)
                buf.add_many(chunk)
                fed.extend(chunk)
            elif buf.can_retrieve:
                got.append(buf.retrieve())
            assert buf.size <= capacity + 3  # bounded by capacity + chunk
        buf.finish()
        while buf.can_retrieve:
            got.append(buf.retrieve())
        assert sorted(got) == fed

    @pytest.mark.parametrize('batch_size', [1, 5, 16])
    def test_batched_buffer_invariants(self, batch_size):
        rng = np.random.RandomState(batch_size)
        buf = BatchedRandomShufflingBuffer(
            64, min_after_retrieve=8, batch_size=batch_size,
            extra_capacity=64, seed=2)
        next_row = 0
        fed = 0
        out_ids = []
        for _ in range(500):
            if buf.can_add and rng.rand() < 0.55:
                n = int(rng.randint(1, 20))
                ids = np.arange(next_row, next_row + n)
                buf.add_many({'id': ids, 'sq': ids ** 2})
                next_row += n
                fed += n
            elif buf.can_retrieve:
                batch = buf.retrieve()
                assert len(batch['id']) == batch_size
                # row alignment: columns must stay paired under shuffling
                np.testing.assert_array_equal(batch['sq'],
                                              batch['id'] ** 2)
                out_ids.extend(batch['id'].tolist())
        buf.finish()
        while buf.can_retrieve:
            batch = buf.retrieve()
            np.testing.assert_array_equal(batch['sq'], batch['id'] ** 2)
            out_ids.extend(batch['id'].tolist())
        # exactly-once, in full: finish() + drain must emit every fed row
        assert sorted(out_ids) == list(range(fed))
