"""Staging arena + double-buffered async H2D transfer (jax/staging.py).

Covers the arena's correctness: zero per-batch host allocations in
steady state, slot contents never mutated while the consumer holds the
corresponding device batch, exact-value round-trips of partial, cast,
shuffled, ragged and bucketed batches against the plain numpy reference
(``petastorm_tpu/test_util/reference.py``), the knob discipline, the
aliasing-probe safety valve and the ``h2d_overlap_share`` report
surface."""

import contextlib
import os
import tracemalloc

import numpy as np
import pytest

import jax

from petastorm_tpu import telemetry as T
from petastorm_tpu.benchmark.dummy_reader import DummyBatchReader
from petastorm_tpu.jax import MASK_FIELD, make_jax_loader
from petastorm_tpu.jax import staging
from petastorm_tpu.test_util import reference
from petastorm_tpu.test_util.parity import assert_same_rows_by_id


@contextlib.contextmanager
def _staging_env(**env):
    saved = {k: os.environ.get(k) for k in env}
    os.environ.update({k: v for k, v in env.items() if v is not None})
    for k, v in env.items():
        if v is None:
            os.environ.pop(k, None)
    staging.refresh_staging()
    try:
        yield
    finally:
        for k, v in saved.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v
        staging.refresh_staging()


@pytest.fixture(autouse=True)
def _fresh_knobs():
    staging.refresh_staging()
    yield
    staging.refresh_staging()


def _dummy_factory(fields, batch_size=100, num_batches=8):
    def factory(url, **kw):
        return DummyBatchReader(fields=fields, batch_size=batch_size,
                                num_batches=num_batches)
    return factory


@pytest.fixture(scope='module')
def ragged_dataset(tmp_path_factory):
    """Variable-length token rows (same shape family as
    tests/test_jax_loader.py's fixture) for the ragged/bucketed
    round-trips; returns the url and the rows written."""
    import pyarrow as pa

    from petastorm_tpu.codecs import NdarrayCodec, ScalarCodec
    from petastorm_tpu.etl.dataset_metadata import (
        DatasetWriter, materialize_dataset,
    )
    from petastorm_tpu.unischema import Unischema, UnischemaField
    url = 'file://' + str(tmp_path_factory.mktemp('staging_ragged')) + '/ds'
    schema = Unischema('Ragged', [
        UnischemaField('id', np.int32, (), ScalarCodec(pa.int32()), False),
        UnischemaField('tokens', np.int32, (None,), NdarrayCodec(), False),
    ])
    rng = np.random.RandomState(0)
    rows = [{
        'id': i,
        'tokens': rng.randint(0, 100, (3 + i % 9,), dtype=np.int32),
    } for i in range(40)]
    with materialize_dataset(url, schema):
        with DatasetWriter(url, schema, rowgroup_size_rows=8) as writer:
            writer.write_row_dicts(rows)
    return url, rows


# -- knobs --------------------------------------------------------------------


def test_knob_defaults_and_refresh():
    with _staging_env(PETASTORM_TPU_STAGING_SLOTS=None):
        assert staging.staging_slots() == 2
    with _staging_env(PETASTORM_TPU_STAGING_SLOTS='5'):
        assert staging.staging_slots() == 5
        assert staging.make_stager(8, {}, 'drop', lambda x: x).num_slots == 5
    # floor of 2 and unparseable values degrade safely
    with _staging_env(PETASTORM_TPU_STAGING_SLOTS='1'):
        assert staging.staging_slots() == 2
    with _staging_env(PETASTORM_TPU_STAGING_SLOTS='bogus'):
        assert staging.staging_slots() == 2


def test_shared_telemetry_refresh_covers_staging_knobs():
    """telemetry.refresh() is the documented one-stop knob re-read; the
    staging knobs must flip through it too, not only through the
    module-private refresh_staging()."""
    assert staging.staging_slots() == 2
    saved = os.environ.get('PETASTORM_TPU_STAGING_SLOTS')
    os.environ['PETASTORM_TPU_STAGING_SLOTS'] = '3'
    try:
        T.refresh()
        assert staging.staging_slots() == 3
    finally:
        if saved is None:
            os.environ.pop('PETASTORM_TPU_STAGING_SLOTS', None)
        else:
            os.environ['PETASTORM_TPU_STAGING_SLOTS'] = saved
        T.refresh()


# -- zero-allocation steady state --------------------------------------------


class _AcceleratorLeaf:
    """Device-array stand-in that copies on construction (what a real
    transfer does) and claims a non-host platform, pinning the engine's
    ring mode on the CPU test host."""

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


def test_steady_state_performs_no_per_batch_host_allocations():
    """The acceptance-gate test (ring mode, the accelerator regime):
    after warmup, staging N more batches allocates no new host batch
    buffers — tracemalloc growth attributed to staging.py stays far below
    even ONE batch's bytes (a per-batch allocation regression would show
    ~N batches' worth), and the slot slab count does not move."""
    bs = 64
    eng = staging.StagingEngine(bs, {'b': np.float32}, 'pad',
                                _accelerator_put, num_slots=2)
    rng = np.random.RandomState(0)
    cols = {'a': rng.rand(bs, 256).astype(np.float32),
            'b': rng.rand(bs, 16)}                      # f64 → f32 cast
    batch_bytes = cols['a'].nbytes + cols['b'].nbytes
    for _ in range(4):
        eng.stage(dict(cols), bs)
    assert eng._host_backed is False      # ring mode engaged
    slabs_after_warmup = eng.slabs_allocated
    n = 50
    tracemalloc.start()
    before = tracemalloc.take_snapshot()
    for _ in range(n):
        eng.stage(dict(cols), bs)
    after = tracemalloc.take_snapshot()
    tracemalloc.stop()
    grown = sum(
        max(0, s.size_diff)
        for s in after.compare_to(before, 'filename')
        if s.traceback and s.traceback[0].filename.endswith(
            os.path.join('petastorm_tpu', 'jax', 'staging.py')))
    assert eng.slabs_allocated == slabs_after_warmup == 2
    # bookkeeping (signature tuples, span objects) is KBs; n re-allocated
    # batches would be ~n * batch_bytes (13 MB here)
    assert grown < batch_bytes / 2, \
        'staging.py allocated %d bytes over %d steady-state batches' \
        % (grown, n)


def test_loader_slot_slabs_stop_growing_after_startup():
    # the dtype cast routes every batch through the slot path (a no-cast
    # single-chunk batch would take the even cheaper direct dispatch)
    fields = {'x': ((32,), np.float64)}
    with make_jax_loader('dummy://', batch_size=25,
                         dtypes={'x': np.float32},
                         reader_factory=_dummy_factory(fields,
                                                       num_batches=12)) \
            as loader:
        it = iter(loader)
        for _ in range(4):
            next(it)
        # the first assembled batch allocates one ring (2 slots); the CPU
        # target then retires it for fresh assembly — either way the slab
        # count must never grow with the batch count
        slabs = loader.diagnostics['staging_slots_allocated']
        assert slabs == 2
        for _ in range(20):
            next(it)
        assert loader.diagnostics['staging_slots_allocated'] == slabs


def test_single_chunk_uncast_batches_dispatch_direct():
    """A batch that is one chunk view with no cast/pad takes the direct
    (no-slot, no-copy) path: values round-trip and no slot is ever
    allocated."""
    fields = {'x': ((8,), np.float32)}
    with make_jax_loader('dummy://', batch_size=25,
                         reader_factory=_dummy_factory(fields,
                                                       num_batches=4)) \
            as loader:
        batches = list(loader)
    assert len(batches) == 16
    assert loader.diagnostics['staging_slots_allocated'] == 0


# -- slot stability under a live consumer ------------------------------------


class _AsyncLeaf:
    """Device-array stand-in with a DEFERRED transfer: it keeps a VIEW of
    the host buffer and materializes its value only at
    ``block_until_ready`` — exactly an in-flight DMA. If the engine ever
    refilled a slot before awaiting that slot's previous handoff, the
    late materialization would capture the NEXT batch's bytes."""

    def __init__(self, view):
        self._view = view
        self.value = None

    def devices(self):
        class _Dev:
            platform = 'tpu'
        return (_Dev(),)

    def block_until_ready(self):
        if self.value is None:
            self.value = np.array(self._view, copy=True)
        return self


def test_slot_never_refilled_while_its_transfer_is_in_flight():
    """Ring mode: recycling is gated on the slot's PREVIOUS handoff
    completing. The deferred-transfer mock proves the ordering: every
    delivered batch's eventual value matches its source even though the
    two slots are recycled ~4 times over."""
    bs = 16
    eng = staging.StagingEngine(bs, {'v': np.float32}, 'drop',
                                lambda tree: {k: _AsyncLeaf(v)
                                              for k, v in tree.items()},
                                num_slots=2)
    rng = np.random.RandomState(1)
    sources, held = [], []
    for i in range(9):
        cols = {'v': rng.rand(bs, 8) + i}              # f64 → f32 cast
        sources.append(cols['v'].astype(np.float32))
        held.append(eng.stage(cols, bs))
    assert eng._host_backed is False and eng.slabs_allocated == 2
    for src, batch in zip(sources, held):
        np.testing.assert_array_equal(
            batch['v'].block_until_ready().value, src)


def test_loader_holds_all_batches_values_intact(scalar_dataset):
    with make_jax_loader(scalar_dataset.url, batch_size=16,
                         fields=['^id$', '^float64$'],
                         shuffle_row_groups=False) as loader:
        batches = list(loader)           # consumer holds ALL handoffs
        copies = [{k: np.asarray(v).copy() for k, v in b.items()}
                  for b in batches]
    assert len(batches) == 6
    seen_ids = sorted(np.concatenate([c['id'] for c in copies]).tolist())
    assert len(set(seen_ids)) == 96
    # re-read the still-held device arrays: recycling never touched them
    for b, c in zip(batches, copies):
        for name in b:
            np.testing.assert_array_equal(np.asarray(b[name]), c[name])


# -- exact-value round-trips vs the plain reference --------------------------


def _collect(url, **kw):
    with make_jax_loader(url, shuffle_row_groups=False, **kw) as loader:
        return [{k: np.asarray(v).copy() for k, v in b.items()}
                for b in loader]


def _assert_same(batches_a, batches_b, dropped_tail=False):
    # reader pools deliver row-groups in completion order (and a bucket's
    # batches depend on arrival): compare by id
    assert_same_rows_by_id(batches_a, batches_b, dropped_tail=dropped_tail)


@pytest.mark.parametrize('kw', [
    dict(batch_size=16, last_batch='pad'),
    dict(batch_size=16, last_batch='short'),
    dict(batch_size=16, last_batch='drop',
         dtypes={'float64': np.float32, 'id': np.int16}),
    dict(batch_size=16, last_batch='pad', shuffle_rows=True, seed=7),
    dict(batch_size=16, last_batch='short', shuffle_rows=True, seed=7,
         dtypes={'float64': np.float16, 'id': np.int16}),
], ids=['pad-tail', 'short-tail', 'dtype-cast', 'shuffled-pad-tail',
        'shuffled-dtype-cast'])
def test_round_trip_matches_pre_arena_path(scalar_dataset, kw):
    fields = ['id', 'float64']
    arena = _collect(scalar_dataset.url,
                     fields=['^%s$' % f for f in fields], **kw)
    plain = {k: v for k, v in kw.items() if k not in ('shuffle_rows', 'seed')}
    expected = reference.loader_batches(
        reference.read_rows(scalar_dataset.url, columns=fields),
        fields=fields, **plain)
    # 100 rows in batches of 16: 'drop' loses an arrival-dependent tail
    _assert_same(arena, expected, dropped_tail=kw['last_batch'] == 'drop')
    if kw['last_batch'] == 'pad':
        assert sum(not np.asarray(b[MASK_FIELD]).all() for b in arena) == 1
        assert not np.asarray(arena[-1][MASK_FIELD])[-1]


def test_ragged_round_trip_matches_pre_arena_path(ragged_dataset):
    url, rows = ragged_dataset
    kw = dict(batch_size=8, pad_ragged={'tokens': 8}, last_batch='pad')
    _assert_same(_collect(url, **kw), reference.loader_batches(rows, **kw))


def test_mixed_dtype_parts_promote_like_concatenate():
    """Regression (review finding): a batch spanning chunks of different
    dtypes must PROMOTE like the legacy ``np.concatenate`` — keying the
    slot on the first chunk's dtype would wrap an int64 value into an
    int32 buffer silently."""
    eng = staging.StagingEngine(4, {}, 'drop', jax.device_put, num_slots=2)
    # int16 + int32 promote to int32 (int64 would be re-narrowed by
    # jax's x64-disabled device_put — on the legacy path too)
    out = eng.stage([{'v': np.array([1, 2], np.int16)},
                     {'v': np.array([2 ** 30, 5], np.int32)}], 4)
    arr = np.asarray(out['v'])
    assert arr.dtype == np.int32
    np.testing.assert_array_equal(arr, [1, 2, 2 ** 30, 5])


def test_shape_mismatched_chunk_raises_instead_of_broadcasting():
    """Regression (review finding): np.copyto would BROADCAST a narrower
    chunk into the slot — e.g. a (m, 1) chunk replicated across a
    (m, 16) slot — where the legacy np.concatenate raised. The fill must
    reject the mismatch loudly."""
    eng = staging.StagingEngine(6, {}, 'drop', jax.device_put, num_slots=2)
    ok = np.ones((3, 16), np.float32)
    bad = np.ones((3, 1), np.float32)
    with pytest.raises(ValueError, match='pad_ragged'):
        eng.stage([{'v': ok}, {'v': bad}], 6)


def test_pass_end_releases_slabs_and_in_flight_refs():
    """Regression (review finding): the per-pass stager must drop its
    slot slabs (and the device-array refs they pin) when the pass ends —
    an idle loader between epochs must not hold batches in memory."""
    fields = {'x': ((16,), np.float64)}
    with make_jax_loader('dummy://', batch_size=25,
                         dtypes={'x': np.float32},
                         reader_factory=_dummy_factory(fields,
                                                       num_batches=4)) \
            as loader:
        list(loader)                       # consume the pass to its end
        assert loader._stager is not None
        assert loader._stager._rings == {}
        # replay still works after the release (fresh arena per pass)
        assert len(list(loader)) == 16


def test_bucketed_round_trip_matches_pre_arena_path(ragged_dataset):
    url, rows = ragged_dataset
    kw = dict(batch_size=4, bucket_boundaries={'tokens': [4, 8, 16]},
              last_batch='short')
    arena = _collect(url, **kw)
    _assert_same(arena, reference.loader_batches(rows, **kw))
    # bucketing produced more than one emitted width → more than one ring
    widths = {b['tokens'].shape[1] for b in arena}
    assert len(widths) > 1


# -- host-backed zero-copy safety --------------------------------------------


def test_host_backed_target_retires_the_ring():
    """Regression: XLA:CPU zero-copies suitably-aligned host arrays into
    device handles, so a recycled slot could corrupt a batch the
    consumer still holds (observed nondeterministically — alignment is
    per-allocation luck). On a host-backed target the engine must
    abandon the ring after its first dispatch and assemble every later
    batch into fresh buffers; all delivered values stay intact."""
    bs, w = 8, 16
    eng = staging.StagingEngine(bs, {}, 'drop', jax.device_put,
                                num_slots=2)
    base = np.arange(bs * w, dtype=np.float32).reshape(bs, w)
    # two-part batches force the assembly path (a single ready chunk
    # would take the direct no-copy dispatch)
    held = [eng.stage([{'v': (base + i)[:5]}, {'v': (base + i)[5:]}], bs)
            for i in range(8)]
    assert eng._host_backed is True
    assert eng._rings == {}            # ring retired, never recycled
    assert eng.slabs_allocated == 2    # only the first batch's ring
    for i, b in enumerate(held):
        np.testing.assert_array_equal(np.asarray(b['v']), base + i)


def test_unknown_array_types_default_to_fresh_assembly():
    """A put_fn returning arrays without a ``devices()`` surface counts
    as host-backed: fresh assembly is the always-correct strategy, and
    an always-aliasing runtime stays safe because buffers are never
    reused."""
    class _AliasedLeaf:
        def __init__(self, view):
            self.view = view

        def block_until_ready(self):
            return self

    eng = staging.StagingEngine(4, {}, 'drop',
                                lambda tree: {k: _AliasedLeaf(v)
                                              for k, v in tree.items()},
                                num_slots=2)
    rng = np.random.RandomState(0)
    held, sources = [], []
    for i in range(6):
        cols = {'v': rng.rand(4, 3).astype(np.float32)}
        sources.append(cols['v'].copy())
        held.append(eng.stage([{'v': cols['v'][:2]},
                               {'v': cols['v'][2:]}], 4))
    assert eng._host_backed is True
    # aliased handoffs were never overwritten by later fills
    for src, batch in zip(sources, held):
        np.testing.assert_array_equal(np.asarray(batch['v'].view), src)


# -- report surface -----------------------------------------------------------


def test_pipeline_report_surfaces_h2d_overlap_share():
    T.reset_for_tests()
    try:
        fields = {'x': ((16,), np.float32)}
        # batch 75 over 100-row chunks: chunk-spanning batches take the
        # slot path (stage_fill/h2d_ready), chunk-view batches the direct
        # path (h2d_dispatch only) — the report must cover both
        with make_jax_loader('dummy://', batch_size=75,
                             reader_factory=_dummy_factory(fields)) as loader:
            for _ in loader:
                pass
            report = loader.pipeline_report()
        assert 0.0 <= report['h2d_overlap_share'] <= 1.0
        assert 'h2d overlap' in T.format_pipeline_report(report)
        reg = T.get_registry()
        assert reg.counter_value(staging.H2D_BYTES) > 0
        # host-backed run: fresh assembly (fill) + async dispatch; the
        # ring's h2d_ready gate appears only on accelerator targets
        # (covered by test_ring_mode_records_h2d_ready)
        for stage in ('stage_fill', 'h2d_dispatch'):
            assert stage in report['stages'], report['stages'].keys()
    finally:
        T.reset_for_tests()


def test_ring_mode_records_h2d_ready():
    T.reset_for_tests()
    try:
        eng = staging.StagingEngine(8, {'v': np.float32}, 'drop',
                                    _accelerator_put, num_slots=2)
        rng = np.random.RandomState(0)
        for _ in range(5):
            eng.stage({'v': rng.rand(8, 4)}, 8)
        report = T.pipeline_report()
        for stage in ('stage_fill', 'h2d_dispatch', 'h2d_ready'):
            assert stage in report['stages'], report['stages'].keys()
        assert 0.0 <= report['h2d_overlap_share'] <= 1.0
    finally:
        T.reset_for_tests()
