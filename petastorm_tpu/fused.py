"""Fused batch-native decode: encoded cells ride to the staging arena.

The decode→collate→fill copy chain used to run in three passes: the
row-group worker decoded image cells into a fresh ``(n,)+shape`` batch,
the JAX loader's collate stage buffered/sliced that batch, and the
staging arena copied the slices into a slot before ``device_put``. This
module collapses the chain to ONE pass, the operator-fusion move of
tf.data (PAPERS.md, arxiv 2101.12127) applied to the decode tentpole
(ROADMAP "Batch-granularity native decode, fused into the staging
arena"):

* the worker, when the reader was built with ``defer_image_decode=True``
  (requested by :func:`petastorm_tpu.jax.make_jax_loader` whenever its
  own batch path can fuse), SKIPS decoding eligible image columns and
  publishes an :class:`EncodedImageColumn` — the still-encoded cells plus
  the field that knows how to decode them;
* the encoded column travels the exact route a decoded one would (noop
  re-batcher chunk views, provenance sidecars, part slicing) — a few KB
  of jpeg bytes per row instead of 150 KB of pixels, so every buffered
  hop is cheaper too; under a predicate it carries ONLY the surviving
  rows' cells (the worker's late-materialization path compacts survivor
  indices over the zero-copy cell views, ``arrow_worker.
  _decode_survivors``), so ``decode_fused`` decodes survivors straight
  into slot-ring rows and non-matching rows never cross the wire;
* the staging engine's fill pass (:mod:`petastorm_tpu.jax.staging`)
  decodes the cells **directly into the arena slot's rows** (or the
  fresh page-aligned assembly buffer on host-backed targets) through the
  codecs' ``decode_batch(..., out=)`` destination API, under the
  ``decode_fused`` stage span — decoded pixels are written exactly once,
  at their final host address, by the native batch decoders' internal
  C-level thread pool.

Shuffled rows take the same route: the loader's random shuffling buffer
(``buffers.BatchedRandomShufflingBuffer``) stores an encoded column as
an :class:`EncodedImageColumn` over an object array of cell views
(:meth:`EncodedImageColumn.empty`) and gathers and assigns cells, not
pixels, so only the rows a batch draws are decoded, once, into the slot.

Eligibility is decided at two gates and every decline falls back to the
classic worker-side batched decode, counted in
``petastorm_tpu_fused_decode_fallbacks_total{reason=…}`` (the
"decode is batched but not fused" runbook in docs/troubleshoot.md reads
these): the worker defers only fixed-shape, numeric, null-free image
columns on the no-transform/no-ngram/no-cache path; the loader
materializes early when batching is bucketed or a dtype cast retargets
the column; the random buffer decodes a column
whose chunks arrive some encoded and some decoded (``mixed-chunks``).

Ownership contract (pipesan): ``EncodedImageColumn.cells`` hold ZERO-COPY
views over the arrow column's data buffer — borrowed memory, registered
as a borrow source in ``analysis/contracts.py`` (``column.cells``). The
one lifetime rule is numpy's ``base`` chain: each cell view holds the
buffer it views (the arrow buffer in the worker, the received frame
after a pool hop), so a cell keeps its memory alive wherever it goes,
through slices, gathers and the shuffling buffer, and nothing else
needs to. Pickling across a process/service pool packs the cells into
one contiguous buffer that travels as a single pickle-5 out-of-band
frame (``PickleSerializer.serialize_frames``; in band under the
one-frame ``serialize``); the receiver's cells are views over that frame.
"""

import logging
import pickle

import numpy as np

from petastorm_tpu.codecs import decode_batch_with_nulls
from petastorm_tpu.telemetry import get_registry, metrics_disabled

logger = logging.getLogger(__name__)

#: registry counters (docs/telemetry.md metric reference)
FUSED_ROWS = 'petastorm_tpu_fused_decode_rows_total'
FUSED_BYTES = 'petastorm_tpu_fused_decode_bytes_total'
FUSED_FALLBACKS = 'petastorm_tpu_fused_decode_fallbacks_total'

#: column slabs align to page boundaries: XLA:CPU zero-copies suitably
#: aligned host arrays into device handles (measured, jax/staging.py),
#: and the native decoders' parallel row writes stay cache-line clean
SLAB_ALIGN = 4096


def alloc_column_slab(shape, dtype):
    """A writable ``np.empty(shape, dtype)`` equivalent whose data starts
    on a :data:`SLAB_ALIGN` (page) boundary — the row-group worker's
    decode destination (``decode_batch(out=)``) and the shape of buffer
    the staging engine's fresh-assembly path zero-copies from. The
    backing allocation rides the returned view's ``.base`` chain, so the
    slab owns its memory like any fresh ndarray."""
    dtype = np.dtype(dtype)
    nbytes = int(np.prod(shape, dtype=np.int64)) * dtype.itemsize
    if nbytes <= 0:
        return np.empty(shape, dtype)
    raw = np.empty(nbytes + SLAB_ALIGN, np.uint8)
    offset = (-raw.ctypes.data) % SLAB_ALIGN
    return raw[offset:offset + nbytes].view(dtype).reshape(shape)


def count_fallback(reason):
    """One fused-decode decline, attributed: the bench/runbook read these
    to explain a ``fused_decode_mode`` that is not ``fused-into-slot``."""
    if not metrics_disabled():
        get_registry().counter(FUSED_FALLBACKS, reason=reason).inc()


class EncodedImageColumn:
    """A column whose cells are STILL ENCODED: the deferred-decode
    carrier between the row-group worker and the staging arena.

    Mimics just enough of the decoded dense column's ndarray surface
    (``shape``/``dtype``/``len``/slicing) that the batch path between the
    two — provenance tagging, the noop re-batcher's chunk views, part
    slicing — needs no special cases; the first consumer that needs
    pixels calls :meth:`decode_into` (staging fill, zero extra copies) or
    :meth:`materialize` (fallback paths).

    ``cells`` is a sequence of encoded bytes-like objects (zero-copy
    ``np.uint8`` views over the arrow data buffer on the in-process
    path, over the received frame after a pool hop, an object array of
    such views after the shuffling buffer's gather), each keeping the
    memory it views alive through its numpy ``base``. Cells may not be
    None here — the worker's eligibility gate routes nullable
    row-groups to the classic decode so null semantics never change —
    but :meth:`decode_into` still zero-fills defensively via
    ``decode_batch_with_nulls``. A column made by :meth:`empty` holds
    None in the slots not yet assigned.
    """

    __slots__ = ('field', 'cells')

    def __init__(self, field, cells):
        self.field = field
        # Intentional transfer of the worker's borrowed cell views: each
        # view's numpy base chain holds the buffer it aliases, so the
        # views stay valid for this object's whole lifetime.  # pipesan: owns
        self.cells = cells

    # -- ndarray-like surface -------------------------------------------------

    @property
    def shape(self):
        return (len(self.cells),) + tuple(self.field.shape)

    @property
    def dtype(self):
        return np.dtype(self.field.numpy_dtype)

    @property
    def nbytes(self):
        """DECODED size (what the fused fill will write), not the encoded
        payload size — the surface downstream accounting expects."""
        return int(np.prod(self.shape, dtype=np.int64)) * self.dtype.itemsize

    def __len__(self):
        return len(self.cells)

    def __getitem__(self, index):
        """Slicing returns a VIEW column over the same cells (the noop
        re-batcher splits chunks with ``col[:take]`` / ``col[take:]``);
        an integer index array gathers those cells (the random shuffling
        buffer's draw). A scalar index is a contract error — per-row
        access means some consumer thinks this is decoded data."""
        if isinstance(index, slice):
            return EncodedImageColumn(self.field, self.cells[index])
        if isinstance(index, np.ndarray) and index.dtype.kind in 'iu':
            return EncodedImageColumn(self.field,
                                      _object_cells(self.cells)[index])
        raise TypeError(
            'EncodedImageColumn is encoded data; decode it '
            '(decode_into/materialize) before per-row indexing')

    def __setitem__(self, index, value):
        """Assign the cells of the column ``value`` to the slots
        ``index`` selects, or, with ``value`` None, empty them (a cell
        left in a slot would keep the buffer it views alive). Only a
        column made by :meth:`empty` is written to."""
        self.cells[index] = (None if value is None
                             else _object_cells(value.cells))

    def empty(self, n):
        """A column of this field with ``n`` empty slots, for
        :meth:`__setitem__` to fill: the random shuffling buffer's
        storage for an encoded column."""
        return EncodedImageColumn(self.field, np.full(n, None, object))

    # -- decode ---------------------------------------------------------------

    def decode_into(self, out):
        """Decode every cell into the caller's ``(n,) + field.shape``
        destination — a staging-arena slot slice or a fresh assembly
        buffer — in one vectorized pass (native batch decoders' internal
        thread pool; null positions zero-filled). Returns ``out``."""
        return decode_batch_with_nulls(self.field, self.cells, out=out)

    def materialize(self):
        """Decode to a fresh page-aligned owned batch — the fallback for
        consumers that cannot provide a destination (bucketed batching,
        dtype recast)."""
        out = alloc_column_slab(self.shape, self.dtype)
        return self.decode_into(out)

    # -- pickling (process/service pools) ------------------------------------

    def __reduce_ex__(self, protocol):
        # the cells travel as ONE contiguous buffer plus offsets: under
        # pickle 5 with a buffer callback (the process pool's
        # serialize_frames) the PickleBuffer ships as one out-of-band
        # frame, not one in-band bytes object per cell; older protocols,
        # which cannot carry a PickleBuffer, take the buffer as bytes.
        # The receiver's cells view the payload
        views = [None if c is None else np.frombuffer(c, np.uint8)
                 for c in self.cells]
        present = [v for v in views if v is not None]
        offsets = np.zeros(len(views) + 1, np.int64)
        offsets[1:] = np.cumsum([0 if v is None else len(v) for v in views])
        data = np.concatenate(present) if present else np.empty(0, np.uint8)
        payload = pickle.PickleBuffer(data) if protocol >= 5 \
            else data.tobytes()
        nulls = tuple(i for i, v in enumerate(views) if v is None)
        # offsets in band (bytes): only the cell payload earns a frame
        return _unpack_cells, (self.field, payload, offsets.tobytes(), nulls)

    def __repr__(self):
        return ('EncodedImageColumn(%r, n=%d, shape=%s)'
                % (self.field.name, len(self.cells), self.shape))


def _object_cells(cells):
    """``cells`` as a 1-d object array, one cell an element. Numpy would
    stack a list of equal-length cells into a 2-d array, so a list is
    copied element by element."""
    if isinstance(cells, np.ndarray) and cells.dtype == object:
        return cells
    return np.fromiter(cells, dtype=object, count=len(cells))


def _unpack_cells(field, data, offsets, nulls):
    """The receiving side of :meth:`EncodedImageColumn.__reduce_ex__`:
    cells as zero-copy views over ``data`` (the received frame, or the
    in-band bytes), which their numpy ``base`` chain keeps alive."""
    buf = np.frombuffer(data, np.uint8)
    offsets = np.frombuffer(offsets, np.int64)
    cells = [buf[offsets[i]:offsets[i + 1]] for i in range(len(offsets) - 1)]
    for i in nulls:
        cells[i] = None
    return EncodedImageColumn(field, cells)
