"""Fused batch-native decode (ISSUE 9 tentpole, petastorm_tpu/fused.py).

Covers the whole chain: the codecs' ``decode_batch(..., out=)``
destination API (incl. the nulls path's zero-fill + red-zone
no-overrun fixture), the worker's deferral gates, the
``EncodedImageColumn`` carrier, the staging arena's fused fill
(exact-value parity against the plain reference,
``petastorm_tpu/test_util/reference.py``, and through the cv2 fallback
decoder), every
fallback mode the troubleshoot runbook names, the sanitizer interplay
(canaries intact across fused refills), and the ``perf``-marked
zero-per-image-intermediates tracemalloc guard."""

import contextlib
import os
import pickle
import tracemalloc

import numpy as np
import pyarrow as pa
import pytest

from petastorm_tpu import sanitizer
from petastorm_tpu import telemetry as T
from petastorm_tpu.codecs import (
    CompressedImageCodec, NdarrayCodec, decode_batch_with_nulls,
)
from petastorm_tpu.fused import (
    EncodedImageColumn, SLAB_ALIGN, alloc_column_slab,
)
from petastorm_tpu.jax import make_jax_loader
from petastorm_tpu.jax import staging
from petastorm_tpu.reader import make_batch_reader
from petastorm_tpu.unischema import Unischema, UnischemaField
from petastorm_tpu.test_util import reference
from petastorm_tpu.test_util.parity import assert_same_rows_by_id


IMG_SHAPE = (32, 32, 3)


def _png_codec_field(name='image'):
    codec = CompressedImageCodec('png')
    return codec, UnischemaField(name, np.uint8, IMG_SHAPE, codec, False)


def _png_cells(n, seed=0):
    import cv2
    rng = np.random.RandomState(seed)
    cells, images = [], []
    for _ in range(n):
        img = rng.randint(0, 255, IMG_SHAPE, dtype=np.uint8)
        ok, enc = cv2.imencode('.png', cv2.cvtColor(img, cv2.COLOR_RGB2BGR))
        assert ok
        cells.append(enc.tobytes())
        images.append(img)
    return cells, images


@contextlib.contextmanager
def _env(**env):
    saved = {k: os.environ.get(k) for k in env}
    for k, v in env.items():
        if v is None:
            os.environ.pop(k, None)
        else:
            os.environ[k] = v
    T.refresh()
    try:
        yield
    finally:
        for k, v in saved.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v
        T.refresh()


@pytest.fixture(scope='module')
def image_dataset(tmp_path_factory):
    """96 png rows (lossless + decode-path-independent, so every decode
    route must produce bit-identical pixels), 16-row row-groups."""
    from petastorm_tpu.etl.dataset_metadata import write_dataset
    url = 'file://' + str(tmp_path_factory.mktemp('fused')) + '/ds'
    _, field = _png_codec_field()
    schema = Unischema('FusedImages', [
        UnischemaField('id', np.int32, (), None, False),
        field,
    ])
    rng = np.random.RandomState(5)
    rows = [{'id': np.int32(i),
             'image': rng.randint(0, 255, IMG_SHAPE, dtype=np.uint8)}
            for i in range(96)]
    write_dataset(url, schema, rows, rowgroup_size_rows=16, num_files=2)
    return url, rows


# -- alloc_column_slab --------------------------------------------------------


def test_column_slab_is_page_aligned_and_owned():
    slab = alloc_column_slab((7, 32, 32, 3), np.uint8)
    assert slab.shape == (7, 32, 32, 3) and slab.dtype == np.uint8
    assert slab.ctypes.data % SLAB_ALIGN == 0
    assert slab.flags.writeable
    # the backing allocation rides the base chain: the slab owns its
    # memory like any fresh ndarray (no borrowed lifetime)
    root = slab
    while root.base is not None:
        root = root.base
    assert isinstance(root, np.ndarray)
    slab[...] = 1  # writable end to end


# -- decode_batch(out=) -------------------------------------------------------


def test_image_decode_batch_out_matches_no_out():
    codec, field = _png_codec_field()
    cells, images = _png_cells(8, seed=1)
    out = alloc_column_slab((8,) + IMG_SHAPE, np.uint8)
    returned = codec.decode_batch(field, cells, out=out)
    assert returned is out
    np.testing.assert_array_equal(out, np.stack(images))
    np.testing.assert_array_equal(out, codec.decode_batch(field, cells))


def test_image_decode_batch_out_validates_destination():
    codec, field = _png_codec_field()
    cells, _ = _png_cells(4, seed=2)
    with pytest.raises(ValueError, match='does not match'):
        codec.decode_batch(field, cells,
                           out=np.empty((4, 16, 16, 3), np.uint8))
    with pytest.raises(ValueError, match='does not match'):
        codec.decode_batch(field, cells,
                           out=np.empty((4,) + IMG_SHAPE, np.float32))
    wild = UnischemaField('w', np.uint8, (None, None, 3), codec, False)
    with pytest.raises(ValueError, match='fixed-shape'):
        codec.decode_batch(wild, cells,
                           out=np.empty((4,) + IMG_SHAPE, np.uint8))


def test_ndarray_decode_batch_out_matches_no_out():
    codec = NdarrayCodec()
    field = UnischemaField('m', np.float32, (5, 7), codec, False)
    rng = np.random.RandomState(3)
    arrs = [rng.rand(5, 7).astype(np.float32) for _ in range(10)]
    cells = [codec.encode(field, a) for a in arrs]
    out = alloc_column_slab((10, 5, 7), np.float32)
    assert codec.decode_batch(field, cells, out=out) is out
    np.testing.assert_array_equal(out, np.stack(arrs))


def test_out_tail_rejects_broadcastable_shape_mismatch():
    """Review regression: the rejected-tail per-cell assignment must not
    numpy-BROADCAST a smaller cell across its destination row — a (3,)
    cell landing in a (2, 3) row would silently replicate data where the
    no-out path preserved the true shape."""
    from io import BytesIO
    codec = NdarrayCodec()
    field = UnischemaField('m', np.float32, (2, 3), codec, False)
    good = np.arange(6, dtype=np.float32).reshape(2, 3)
    buf = BytesIO()
    np.save(buf, np.arange(3, dtype=np.float32), allow_pickle=False)
    cells = [codec.encode(field, good), buf.getvalue()]
    out = np.empty((2, 2, 3), np.float32)
    with pytest.raises(ValueError, match='decoded to shape'):
        codec.decode_batch(field, cells, out=out)
    # the no-out path still degrades gracefully (true shape preserved)
    rows = codec.decode_batch(field, cells)
    assert rows[1].shape == (3,)


def test_nulls_out_path_zero_fills_inside_red_zones():
    """ISSUE 9 satellite: null positions in the destination slab must be
    ZERO-FILLED (not uninitialized / previous-slot bytes), and a ragged
    tail (out covering fewer rows than the slab) must not overrun — the
    pipesan red-zone fixture proves it byte-exactly."""
    codec, field = _png_codec_field()
    cells, images = _png_cells(4, seed=4)
    ragged = [cells[0], None, cells[1], None, None, cells[2]]
    # guarded slab: poisoned canaries on both sides, garbage in the middle
    slab = sanitizer.allocate_guarded((8,) + IMG_SHAPE, np.uint8)
    slab[...] = 0x77  # stale "previous slot" bytes a lazy path would leak
    out = slab[:6]    # the ragged tail: two slab rows stay out of bounds
    returned = decode_batch_with_nulls(field, ragged, out=out)
    assert returned is out
    np.testing.assert_array_equal(out[0], images[0])
    np.testing.assert_array_equal(out[2], images[1])
    np.testing.assert_array_equal(out[5], images[2])
    for null_row in (1, 3, 4):
        assert not out[null_row].any(), 'null row %d not zeroed' % null_row
    # rows past the destination window were never touched...
    assert (slab[6:] == 0x77).all()
    # ...and neither red zone was (no overrun on the ragged tail)
    assert sanitizer.check_canaries(slab)


def test_all_null_out_path_zero_fills():
    _, field = _png_codec_field()
    out = np.full((3,) + IMG_SHAPE, 0xAB, np.uint8)
    decode_batch_with_nulls(field, [None, None, None], out=out)
    assert not out.any()


# -- EncodedImageColumn -------------------------------------------------------


def test_encoded_column_surface_and_slicing():
    _, field = _png_codec_field()
    cells, images = _png_cells(6, seed=6)
    column = EncodedImageColumn(field, cells)
    assert len(column) == 6
    assert column.shape == (6,) + IMG_SHAPE
    assert column.dtype == np.uint8
    assert column.nbytes == 6 * int(np.prod(IMG_SHAPE))
    head = column[:2]
    assert isinstance(head, EncodedImageColumn) and len(head) == 2
    np.testing.assert_array_equal(head.materialize(), np.stack(images[:2]))
    with pytest.raises(TypeError, match='encoded'):
        column[0]
    np.testing.assert_array_equal(column.materialize(), np.stack(images))


def test_encoded_column_gathers_and_assigns_cells():
    """The random shuffling buffer's surface: ``empty`` storage, slot
    assignment from a list-backed column (equal-length cells are not
    stacked), a gather by index array, and slots emptied with None."""
    _, field = _png_codec_field()
    cells, images = _png_cells(4, seed=8)
    same = [cells[0]] * 3       # equal lengths: numpy would stack these
    store = EncodedImageColumn(field, cells).empty(8)
    assert store.shape == (8,) + IMG_SHAPE
    store[0:4] = EncodedImageColumn(field, cells)
    store[4:7] = EncodedImageColumn(field, same)
    picked = store[np.array([3, 0, 5])]
    assert isinstance(picked, EncodedImageColumn) and len(picked) == 3
    np.testing.assert_array_equal(
        picked.materialize(), np.stack([images[3], images[0], images[0]]))
    store[np.array([1])] = store[np.array([6])]
    assert store.cells[1] is store.cells[6]
    store[4:8] = None
    assert all(c is None for c in store.cells[4:])
    with pytest.raises(TypeError, match='encoded'):
        store[np.int64(0)]


def test_encoded_column_pickles_to_owned_cells():
    _, field = _png_codec_field()
    cells, images = _png_cells(3, seed=7)
    views = [np.frombuffer(c, np.uint8) for c in cells]
    column = EncodedImageColumn(field, views)
    clone = pickle.loads(pickle.dumps(column))
    # the clone's cells view its own copy of the payload
    assert not any(np.shares_memory(c, v) for c in clone.cells
                   for v in views)
    np.testing.assert_array_equal(clone.materialize(), np.stack(images))


def test_encoded_column_crosses_the_pool_as_one_frame():
    """serialize_frames ships the cells as ONE out-of-band frame (not a
    bytes object per cell in the pickle stream), and the receiver's
    cells are zero-copy views over that frame."""
    from petastorm_tpu.serializers import PickleSerializer
    _, field = _png_codec_field()
    cells, images = _png_cells(5, seed=9)
    column = EncodedImageColumn(field, [np.frombuffer(c, np.uint8)
                                        for c in cells])
    serializer = PickleSerializer()
    frames = serializer.serialize_frames({'image': column})
    assert len(frames) == 2
    assert memoryview(frames[1]).nbytes == sum(len(c) for c in cells)
    assert memoryview(frames[0]).nbytes < min(len(c) for c in cells)
    received = bytearray(frames[1])     # what the channel hands over
    clone = serializer.deserialize_frames([frames[0], received])['image']
    frame = np.frombuffer(received, np.uint8)
    assert all(np.shares_memory(c, frame) for c in clone.cells)
    assert [bytes(c) for c in clone.cells] == cells
    np.testing.assert_array_equal(clone.materialize(), np.stack(images))
    # the one-frame contract (service pool) carries it in band
    inband = serializer.deserialize(serializer.serialize(column))
    assert [bytes(c) for c in inband.cells] == cells


# -- worker deferral gates ----------------------------------------------------


def test_reader_defers_when_asked(image_dataset):
    url, rows = image_dataset
    with make_batch_reader(url, shuffle_row_groups=False,
                           defer_image_decode=True) as reader:
        columns, _, _ = reader.next_batch_info()
    assert isinstance(columns['image'], EncodedImageColumn)
    # scalar columns decode as always
    assert isinstance(columns['id'], np.ndarray)
    dense = columns['image'].materialize()
    assert dense.shape == (16,) + IMG_SHAPE


def test_reader_does_not_defer_by_default(image_dataset):
    url, _ = image_dataset
    with make_batch_reader(url, shuffle_row_groups=False) as reader:
        batch = next(reader)
    assert isinstance(batch.image, np.ndarray)
    assert batch.image.shape == (16,) + IMG_SHAPE


def test_transform_spec_declines_deferral(image_dataset):
    # a TransformSpec needs pixels at the worker: deferral must not
    # change what the transform sees
    from petastorm_tpu.transform import TransformSpec
    url, _ = image_dataset

    def brighten(frame):
        frame['image'] = [np.minimum(im.astype(np.int32) + 1, 255)
                          .astype(np.uint8) for im in frame['image']]
        return frame

    with make_batch_reader(url, shuffle_row_groups=False,
                           defer_image_decode=True,
                           transform_spec=TransformSpec(brighten)) as reader:
        batch = next(reader)
    assert isinstance(batch.image, np.ndarray)


# -- loader end-to-end: fused vs the plain reference --------------------------


def _collect(url, **kw):
    with make_jax_loader(url, shuffle_row_groups=False, **kw) as loader:
        batches = [{k: np.asarray(v).copy() for k, v in b.items()}
                   for b in loader]
        diag = loader.diagnostics
    return batches, diag


def _assert_same(batches_a, batches_b):
    # reader pools deliver row-groups in completion order: compare by id
    assert_same_rows_by_id(batches_a, batches_b)


def test_fused_loader_matches_pure_python_oracle(image_dataset):
    """The acceptance gate: decode fused into staging buffers must be
    value-identical to the rows written (png: lossless +
    path-independent), with the native decoders and with the native
    layer OFF — the cv2 fallback, the only decoder where the native
    build fails."""
    url, rows = image_dataset
    fused_batches, diag = _collect(url, batch_size=24)
    assert diag['fused_decode_mode'] in ('fused-into-slot',
                                         'fused-into-slab')
    assert diag['fused_decode_rows'] == 96
    with _env(PETASTORM_TPU_NATIVE='0'):
        cv2_batches, cv2_diag = _collect(url, batch_size=24)
    assert cv2_diag['fused_decode_rows'] == 96
    expected = reference.loader_batches(rows, 24)
    _assert_same(fused_batches, expected)
    _assert_same(cv2_batches, expected)
    # and against the source pixels themselves
    by_id = {}
    for b in fused_batches:
        for i in range(len(b['id'])):
            by_id[int(b['id'][i])] = b['image'][i]
    for row in rows:
        np.testing.assert_array_equal(by_id[int(row['id'])], row['image'])


def test_fused_pad_tail_zero_fills_and_masks(image_dataset):
    url, rows = image_dataset
    batches, diag = _collect(url, batch_size=36, last_batch='pad')
    assert diag['fused_decode_rows'] == 96
    tail = batches[-1]
    mask = tail['valid_mask']
    assert mask[:24].all() and not mask[24:].any()
    assert not tail['image'][24:].any()  # padded rows are zero, not stale
    assert all(b['valid_mask'].all() for b in batches[:-1])
    _assert_same(batches, reference.loader_batches(rows, 36,
                                                   last_batch='pad'))


def _assert_same_batches(batches_a, batches_b):
    assert len(batches_a) == len(batches_b)
    for a, b in zip(batches_a, batches_b):
        assert sorted(a) == sorted(b)
        for name in a:
            np.testing.assert_array_equal(a[name], b[name])


def _assert_shuffled_rows_fuse(url, rows, pool):
    """Shuffled rows, fused: the random buffer gathers encoded cells and
    the arena decodes the drawn rows into its buffers. One worker fixes
    the row-group order, so one seed must give the batches of the
    worker-decoded read (cv2 fallback decoder) exactly — the same rows,
    in the same batches, in the same order — and the rows are the rows
    written."""
    kw = dict(batch_size=24, shuffle_rows=True, seed=3, workers_count=1,
              reader_pool_type=pool)
    batches, diag = _collect(url, **kw)
    assert diag['fused_decode_mode'] in ('fused-into-slot',
                                         'fused-into-slab')
    assert diag['fused_decode_rows'] == 96
    assert 'fused_decode_fallback' not in diag
    with _env(PETASTORM_TPU_NATIVE='0'):
        decoded, decoded_diag = _collect(url, defer_image_decode=False,
                                         **kw)
    assert decoded_diag['fused_decode_mode'] == 'batched'
    _assert_same_batches(batches, decoded)
    _assert_same(batches, reference.loader_batches(rows, 24))


def test_shuffled_rows_fall_back_and_match_decoded_path(image_dataset):
    _assert_shuffled_rows_fuse(*image_dataset, 'thread')


def test_shuffled_rows_fuse_across_the_process_pool(image_dataset):
    _assert_shuffled_rows_fuse(*image_dataset, 'process')


def test_dtype_cast_materializes_and_matches(image_dataset):
    url, _ = image_dataset
    kw = dict(batch_size=24, dtypes={'image': np.float32})
    batches, diag = _collect(url, **kw)
    assert batches[0]['image'].dtype == np.float32
    assert diag['fused_decode_mode'] == 'batched'
    assert diag.get('fused_decode_fallback') == 'dtype-cast'
    _, rows = image_dataset
    _assert_same(batches, reference.loader_batches(rows, **kw))


def test_fused_records_decode_fused_stage(image_dataset):
    url, _ = image_dataset
    T.reset_for_tests()
    try:
        _, diag = _collect(url, batch_size=24)
        assert diag['fused_decode_rows'] > 0
        report = T.pipeline_report()
        assert 'decode_fused' in report['stages']
        from petastorm_tpu.fused import FUSED_BYTES, FUSED_ROWS
        registry = T.get_registry()
        assert registry.counter_value(FUSED_ROWS) == 96
        assert registry.counter_value(FUSED_BYTES) \
            == 96 * int(np.prod(IMG_SHAPE))
    finally:
        T.reset_for_tests()


# -- sanitizer interplay ------------------------------------------------------


class _AcceleratorLeaf:
    """Copies on construction + claims a non-host platform, pinning ring
    mode on the CPU test host (same stand-in as tests/test_staging.py)."""

    def __init__(self, arr):
        self.value = np.array(arr, copy=True)

    def devices(self):
        class _Dev:
            platform = 'tpu'
        return (_Dev(),)

    def block_until_ready(self):
        return self


def _accelerator_put(tree):
    return {name: _AcceleratorLeaf(arr) for name, arr in tree.items()}


def _encoded_parts(bs, n_parts=2, seed=8):
    _, field = _png_codec_field()
    per = bs // n_parts
    parts, images = [], []
    for p in range(n_parts):
        cells, imgs = _png_cells(per, seed=seed + p)
        parts.append({'image': EncodedImageColumn(field, cells)})
        images.extend(imgs)
    return parts, np.stack(images)


def test_fused_ring_mode_under_sanitizer_keeps_canaries_intact():
    """PETASTORM_TPU_SANITIZE=1 over the fused path: slot slabs recycle
    across fused refills with red zones verified each time — the native
    decoders never write past their destination rows."""
    with _env(PETASTORM_TPU_SANITIZE='1'):
        sanitizer.reset_for_tests()
        bs = 8
        eng = staging.StagingEngine(bs, {}, 'drop', _accelerator_put,
                                    num_slots=2)
        held = []
        expected = []
        for i in range(6):
            parts, images = _encoded_parts(bs, seed=20 + i)
            held.append(eng.stage(parts, bs))
            expected.append(images)
        assert eng._host_backed is False      # ring mode engaged
        assert eng.fused_mode == 'fused-into-slot'
        assert eng.fused_rows == 6 * bs
        for batch, images in zip(held, expected):
            np.testing.assert_array_equal(batch['image'].value, images)
        assert sanitizer.violations() == [], sanitizer.violations()
    sanitizer.reset_for_tests()


# -- perf marker: zero per-image intermediates --------------------------------


@pytest.mark.perf
def test_fused_fill_allocates_zero_per_image_intermediates():
    """ISSUE 9 acceptance: decode lands in staging slots with ZERO
    per-image intermediate allocations. After warmup, tracemalloc growth
    attributed to the decode/staging modules stays far below even ONE
    batch of pixels (a per-image Mat/ndarray regression would show ~N
    batches' worth). Same discipline as tests/test_staging.py."""
    from petastorm_tpu.native import get_png_module
    if get_png_module() is None:
        pytest.skip('native png extension unavailable (cv2 fallback '
                    'allocates per-image Mats by design)')
    bs = 16
    eng = staging.StagingEngine(bs, {}, 'drop', _accelerator_put,
                                num_slots=2)
    parts, images = _encoded_parts(bs, seed=40)
    batch_bytes = images.nbytes
    for _ in range(4):
        eng.stage(list(parts), bs)
    assert eng._host_backed is False and eng.fused_rows == 4 * bs
    watched = tuple(os.path.join('petastorm_tpu', tail) for tail in
                    ('fused.py', 'codecs.py', os.path.join('jax',
                                                           'staging.py')))
    n = 40
    tracemalloc.start()
    before = tracemalloc.take_snapshot()
    for _ in range(n):
        eng.stage(list(parts), bs)
    after = tracemalloc.take_snapshot()
    tracemalloc.stop()
    grown = sum(
        max(0, s.size_diff)
        for s in after.compare_to(before, 'filename')
        if s.traceback and s.traceback[0].filename.endswith(watched))
    assert grown < batch_bytes / 2, \
        'fused decode allocated %d bytes over %d steady-state batches ' \
        '(batch is %d bytes)' % (grown, n, batch_bytes)
    np.testing.assert_array_equal(
        eng.stage(list(parts), bs)['image'].value, images)
