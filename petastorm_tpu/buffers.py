"""Shuffling buffers: decorrelation stage between row-group reads and batches.

Re-design of ``petastorm/reader_impl/shuffling_buffer.py`` (row-level) and
``pytorch_shuffling_buffer.py`` (batched tensors). The TPU-first change: the
**batched, column-major buffers are the primary implementation** — contiguous
preallocated numpy column buffers with vectorized random retrieval — because
they feed the JAX device stage and the Torch bridge directly; the row-level
buffers remain for the row-at-a-time API.

Contract (shared by all flavors, reference ``shuffling_buffer.py:22-72``):
``can_add`` → ``add_many(items)``, ``can_retrieve`` → ``retrieve()``,
``finish()`` when upstream is exhausted, then drain until ``size == 0``.
"""

from abc import ABCMeta, abstractmethod
from collections import deque

import numpy as np

from petastorm_tpu.fused import count_fallback


class ShufflingBufferBase(metaclass=ABCMeta):
    """Row-level buffer contract."""

    @abstractmethod
    def add_many(self, items):
        """Store items; only legal while ``can_add``."""

    @abstractmethod
    def retrieve(self):
        """Return one item; only legal while ``can_retrieve``."""

    @abstractmethod
    def finish(self):
        """Upstream exhausted: everything buffered becomes retrievable."""

    @property
    @abstractmethod
    def can_add(self):
        """True when the buffer will accept more items."""

    @property
    @abstractmethod
    def can_retrieve(self):
        """True when retrieve() would return an item."""

    @property
    @abstractmethod
    def size(self):
        """Number of buffered items."""


class NoopShufflingBuffer(ShufflingBufferBase):
    """FIFO pass-through (reference: ``shuffling_buffer.py:75-100``)."""

    def __init__(self):
        self._items = deque()
        self._done = False

    def add_many(self, items):
        if not self.can_add:
            raise RuntimeError('add_many called on a finished buffer')
        self._items.extend(items)

    def retrieve(self):
        return self._items.popleft()

    def finish(self):
        self._done = True

    @property
    def can_add(self):
        return not self._done

    @property
    def can_retrieve(self):
        return len(self._items) > 0

    @property
    def size(self):
        return len(self._items)


class RandomShufflingBuffer(ShufflingBufferBase):
    """Uniform-random retrieval with swap-remove
    (reference: ``shuffling_buffer.py:103-180``).

    :param shuffling_buffer_capacity: soft fill target; ``can_add`` turns
        False at this size, but one ``add_many`` may overshoot up to
        ``extra_capacity`` (callers add whole row-groups at once).
    :param min_after_retrieve: retrieval blocks until this many items are
        buffered (decorrelation floor), except after :meth:`finish`.
    """

    def __init__(self, shuffling_buffer_capacity, min_after_retrieve=0,
                 extra_capacity=0, seed=None):
        if min_after_retrieve > shuffling_buffer_capacity:
            raise ValueError('min_after_retrieve (%d) must not exceed the '
                             'buffer capacity (%d)'
                             % (min_after_retrieve, shuffling_buffer_capacity))
        self._capacity = shuffling_buffer_capacity
        self._min_after_retrieve = min_after_retrieve
        self._extra_capacity = extra_capacity
        self._items = []
        self._done = False
        self._rng = np.random.RandomState(seed)

    def add_many(self, items):
        if not self.can_add:
            raise RuntimeError('add_many called on a full or finished buffer')
        self._items.extend(items)

    def retrieve(self):
        if not self.can_retrieve:
            raise RuntimeError('retrieve called but can_retrieve is False')
        idx = self._rng.randint(len(self._items))
        # swap-remove: O(1), order irrelevant in a shuffling buffer
        self._items[idx], self._items[-1] = self._items[-1], self._items[idx]
        return self._items.pop()

    def finish(self):
        self._done = True

    @property
    def can_add(self):
        return not self._done and len(self._items) < self._capacity

    @property
    def can_retrieve(self):
        if self._done:
            return len(self._items) > 0
        # >= (not >): capacity == min_after_retrieve is a legal config and
        # must not deadlock the add-while-can_add/retrieve-while-can_retrieve
        # driving loop.
        return len(self._items) >= max(1, self._min_after_retrieve)

    @property
    def size(self):
        return len(self._items)


class BatchedShufflingBufferBase(metaclass=ABCMeta):
    """Column-major buffer contract: items are ``{name: ndarray}`` dicts of
    equal leading dimension; retrieval returns fixed-size batches.

    Reference: ``pytorch_shuffling_buffer.py:22-84`` — but numpy column
    buffers instead of torch tensors, so the same implementation feeds JAX
    staging, the Torch bridge (via ``torch.from_numpy`` zero-copy), and TF.
    """

    def __init__(self, batch_size):
        self.batch_size = batch_size

    @abstractmethod
    def add_many(self, columns):
        """Append a column-dict chunk."""

    @abstractmethod
    def retrieve(self):
        """Return a ``{name: ndarray}`` batch with ``batch_size`` rows."""

    @abstractmethod
    def finish(self):
        """Upstream exhausted; remaining rows become retrievable (the final
        batch may be short)."""

    @property
    @abstractmethod
    def can_add(self):
        """True when the buffer will accept more chunks."""

    @property
    @abstractmethod
    def can_retrieve(self):
        """True when retrieve() would return a batch."""

    @property
    @abstractmethod
    def size(self):
        """Buffered row count."""


class BatchedNoopShufflingBuffer(BatchedShufflingBufferBase):
    """Order-preserving re-batcher: chunks in, fixed batches out
    (reference: ``pytorch_shuffling_buffer.py:111-159``)."""

    def __init__(self, batch_size):
        super().__init__(batch_size)
        self._chunks = deque()
        self._size = 0
        self._done = False

    def add_many(self, columns):
        if not self.can_add:
            raise RuntimeError('add_many called on a finished buffer')
        n = _leading_dim(columns)
        if n == 0:
            return
        self._chunks.append(columns)
        self._size += n

    def retrieve_parts(self):
        """One batch as a LIST of column-dict parts (views/whole chunks,
        no concatenation): consumers that copy into a preallocated
        destination — the JAX staging arena — skip the intermediate
        concatenated batch allocation entirely."""
        if not self.can_retrieve:
            raise RuntimeError('retrieve called but can_retrieve is False')
        want = min(self.batch_size, self._size)
        parts = []
        got = 0
        while got < want:
            chunk = self._chunks[0]
            n = _leading_dim(chunk)
            take = min(n, want - got)
            if take == n:
                parts.append(self._chunks.popleft())
            else:
                parts.append({k: v[:take] for k, v in chunk.items()})
                self._chunks[0] = {k: v[take:] for k, v in chunk.items()}
            got += take
        self._size -= want
        return parts

    def retrieve(self):
        parts = self.retrieve_parts()
        if len(parts) == 1:
            return parts[0]
        return {k: _concat([p[k] for p in parts]) for k in parts[0]}

    def finish(self):
        self._done = True

    @property
    def can_add(self):
        return not self._done

    @property
    def can_retrieve(self):
        return self._size >= self.batch_size or (self._done and self._size > 0)

    @property
    def size(self):
        return self._size


class BatchedRandomShufflingBuffer(BatchedShufflingBufferBase):
    """Uniform-random fixed-size batches out of a contiguous column buffer.

    Columns are preallocated to ``capacity + extra_capacity`` rows on first
    add; retrieval gathers ``batch_size`` random rows and compacts the holes
    with tail rows — all vectorized (reference keeps torch tensors and slices
    a randperm, ``pytorch_shuffling_buffer.py:162-291``).

    A still-encoded image column (:class:`~petastorm_tpu.fused.
    EncodedImageColumn`, the fused-decode carrier) allocates its own
    storage (``empty``), which gathers and assigns cell views with the
    ndarray indexing this class uses: adds, gathers and compaction move
    pointers, not decoded pixel rows, and :meth:`retrieve` hands back an
    ``EncodedImageColumn`` over the drawn cells for the staging arena to
    decode straight into its slot. The draws are the same either way, so
    a seed selects the same rows whether the column arrives encoded or
    decoded. A column whose chunks arrive some encoded and some decoded
    (the worker declines to defer a row-group with null cells) goes on
    decoded, counted as the fused decode's ``mixed-chunks`` decline.
    """

    def __init__(self, shuffling_buffer_capacity, min_after_retrieve,
                 batch_size, extra_capacity=0, seed=None):
        super().__init__(batch_size)
        if min_after_retrieve > shuffling_buffer_capacity:
            raise ValueError('min_after_retrieve (%d) must not exceed the '
                             'buffer capacity (%d)'
                             % (min_after_retrieve, shuffling_buffer_capacity))
        if batch_size > shuffling_buffer_capacity:
            raise ValueError('batch_size (%d) must not exceed the buffer '
                             'capacity (%d)'
                             % (batch_size, shuffling_buffer_capacity))
        self._capacity = shuffling_buffer_capacity
        self._min_after_retrieve = min_after_retrieve
        self._extra_capacity = extra_capacity
        self._buffers = None
        self._size = 0
        self._done = False
        self._rng = np.random.RandomState(seed)

    def _ensure_buffers(self, columns):
        if self._buffers is not None:
            return
        cap = self._capacity + self._extra_capacity
        self._buffers = {}
        for name, arr in columns.items():
            self._buffers[name] = (
                np.empty((cap,) + arr.shape[1:], dtype=arr.dtype)
                if isinstance(arr, np.ndarray) else arr.empty(cap))

    def add_many(self, columns):
        if not self.can_add:
            raise RuntimeError('add_many called on a full or finished buffer')
        columns = {k: _as_column(v) for k, v in columns.items()}
        n = _leading_dim(columns)
        if n == 0:
            return
        self._ensure_buffers(columns)
        if self._size + n > next(iter(self._buffers.values())).shape[0]:
            raise RuntimeError(
                'Chunk of %d rows overflows the shuffling buffer (capacity %d '
                '+ extra %d, size %d); raise extra_capacity to at least the '
                'row-group size' % (n, self._capacity, self._extra_capacity,
                                    self._size))
        for name, arr in columns.items():
            buf = self._buffers[name]
            if isinstance(buf, np.ndarray) != isinstance(arr, np.ndarray):
                buf, arr = self._go_decoded(name, arr)
            # Widen the buffer when a later chunk needs a wider dtype (e.g.
            # '<U3' → '<U10', int32 → int64): plain assignment would silently
            # truncate/wrap instead.
            promoted = np.promote_types(buf.dtype, arr.dtype) \
                if buf.dtype != arr.dtype else buf.dtype
            if promoted != buf.dtype:
                buf = buf.astype(promoted)
                self._buffers[name] = buf
            buf[self._size:self._size + n] = arr
        self._size += n

    def _go_decoded(self, name, chunk):
        """One side of column ``name`` is encoded and the other decoded:
        the buffered column or the ``chunk`` being added. The column goes
        on decoded — encoded buffered cells decode once, here, into a
        dense buffer of the field's shape and dtype; an encoded chunk
        decodes on its add. Returns the buffer and the chunk to assign.
        """
        count_fallback('mixed-chunks')
        buf = self._buffers[name]
        if isinstance(chunk, np.ndarray):
            dense = np.empty(buf.shape, buf.dtype)
            buf[:self._size].decode_into(dense[:self._size])
            self._buffers[name] = dense
            return dense, chunk
        return buf, chunk.materialize()

    def retrieve(self):
        if not self.can_retrieve:
            raise RuntimeError('retrieve called but can_retrieve is False')
        k = min(self.batch_size, self._size)
        sel = self._rng.choice(self._size, size=k, replace=False)
        # fancy indexing already allocates fresh arrays — no copy needed
        batch = {name: buf[sel] for name, buf in self._buffers.items()}
        self._compact(sel, k)
        self._size -= k
        return batch

    def _compact(self, sel, k):
        """Backfill the vacated slots with surviving tail rows (vectorized
        swap-remove): holes below the new size get the non-selected rows
        living at or above it."""
        new_size = self._size - k
        sel_mask = np.zeros(self._size, dtype=bool)
        sel_mask[sel] = True
        holes = np.flatnonzero(sel_mask[:new_size])
        movers = np.flatnonzero(~sel_mask[new_size:]) + new_size
        for buf in self._buffers.values():
            buf[holes] = buf[movers]
            if not isinstance(buf, np.ndarray):
                # an encoded cell left past the end would pin its frame
                buf[new_size:self._size] = None

    def finish(self):
        self._done = True

    @property
    def can_add(self):
        return not self._done and self._size < self._capacity

    @property
    def can_retrieve(self):
        if self._done:
            return self._size > 0
        return self._size >= max(self.batch_size, self._min_after_retrieve)

    @property
    def size(self):
        return self._size


def _as_column(values):
    """``values`` as an ndarray, unless it is a column type that keeps
    its own storage (the still-encoded image column of
    :mod:`petastorm_tpu.fused`)."""
    return values if hasattr(values, 'decode_into') else np.asarray(values)


def _leading_dim(columns):
    return len(next(iter(columns.values())))


def _concat(arrays):
    if arrays[0].dtype == object:
        out = np.empty(sum(len(a) for a in arrays), dtype=object)
        pos = 0
        for a in arrays:
            out[pos:pos + len(a)] = a
            pos += len(a)
        return out
    return np.concatenate(arrays)
