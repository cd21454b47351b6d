"""Shard-aware staging engine + autotuner (ISSUE 14).

Exact-parity suite on the forced-8-device CPU mesh (conftest sets
``xla_force_host_platform_device_count=8``): staged batches equal the
plain numpy reference (``petastorm_tpu/test_util/reference.py``) on a 4x2
mesh across last-batch policies, multi-epoch replays and fused decode; a
mid-stream checkpoint resume; the one-dispatch-per-pytree contract; the
structural zero-per-batch-host-allocation guard on the sharded ring; the
sharded row plan's soundness properties; and the staging autotuner's
policy, bounds, decision records and knob discipline.
"""

import contextlib
import os
import tracemalloc

import numpy as np
import pytest

import jax

from petastorm_tpu import codecs
from petastorm_tpu import telemetry as T
from petastorm_tpu.benchmark.dummy_reader import DummyBatchReader
from petastorm_tpu.jax import autotune, staging
from petastorm_tpu.jax.loader import make_jax_loader
from petastorm_tpu.parallel.mesh import DATA_AXIS, make_mesh
from petastorm_tpu.parallel.sharding import local_shard_plan
from petastorm_tpu.telemetry.registry import metric_key
from petastorm_tpu.telemetry.spans import STAGE_SECONDS
from petastorm_tpu.test_util import reference
from petastorm_tpu.test_util.parity import assert_same_rows_by_id

N_SHARDS = 4


@pytest.fixture(scope='module')
def mesh():
    """4x2 (data x model) mesh over the virtual 8-CPU-device platform —
    the acceptance gate's shape."""
    return make_mesh(data=N_SHARDS, model=2)


@pytest.fixture(autouse=True)
def _fresh(request):
    staging.refresh_staging()
    autotune.refresh_autotune()
    yield
    codecs.set_image_decoder_threads_override(None)
    autotune._reset_for_tests()   # decision ring + override owner slot
    staging.refresh_staging()
    autotune.refresh_autotune()


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


def _read_all(url, mesh, batch_size, last_batch='drop', num_epochs=1,
              **kw):
    """Materialized batches (numpy) of a full mesh read."""
    out = []
    with make_jax_loader(url, batch_size=batch_size, mesh=mesh,
                         data_axes=(DATA_AXIS,), last_batch=last_batch,
                         num_epochs=num_epochs, fields=['^id$', '^float64$'],
                         shuffle_row_groups=False, **kw) as loader:
        for batch in loader:
            for arr in batch.values():
                assert isinstance(arr, jax.Array)
            out.append({k: np.asarray(v) for k, v in batch.items()})
    return out


def _expected(url, batch_size, last_batch='drop', num_epochs=1):
    """The plain reference for a ``_read_all`` read."""
    fields = ['id', 'float64']
    return reference.loader_batches(
        reference.read_rows(url, columns=fields), batch_size,
        fields=fields, last_batch=last_batch, num_epochs=num_epochs)


def _assert_batches_equal(staged, expected, dropped_tail=False):
    # reader pools deliver row-groups in completion order: compare by id
    assert_same_rows_by_id(staged, expected, dropped_tail=dropped_tail)


# -- the sharded row plan -----------------------------------------------------


def test_local_shard_plan_covers_local_rows(mesh):
    from jax.sharding import NamedSharding, PartitionSpec
    sharding = NamedSharding(mesh, PartitionSpec((DATA_AXIS,)))
    plan = local_shard_plan(sharding, 16)
    assert plan is not None
    # every addressable device appears (model-axis replicas included)
    assert len(plan) == 8
    # spans are unit-step, in-bounds, and union-cover [0, 16) exactly
    covered = set()
    for device, lo, hi in plan:
        assert 0 <= lo < hi <= 16
        covered.update(range(lo, hi))
    assert covered == set(range(16))
    # the 4 data shards each own a 4-row block, twice (model replicas)
    blocks = sorted((lo, hi) for _, lo, hi in plan)
    assert blocks == [(i * 4, i * 4 + 4) for i in range(4)
                      for _ in range(2)]


def test_local_shard_plan_declines_uneven_rows(mesh):
    from jax.sharding import NamedSharding, PartitionSpec
    sharding = NamedSharding(mesh, PartitionSpec((DATA_AXIS,)))
    # 10 rows over 4 shards: jax either refuses the indices map or hands
    # back uneven spans the checker must reject — either way the caller
    # must get the always-correct fallback, never a wrong plan
    plan = local_shard_plan(sharding, 10)
    if plan is not None:
        covered = set()
        for _, lo, hi in plan:
            covered.update(range(lo, hi))
        assert covered == set(range(10))


# -- exact parity: staged vs the plain reference ------------------------------


def test_sharded_parity_drop_multi_epoch(scalar_dataset, mesh):
    staged = _read_all(scalar_dataset.url, mesh, 8, num_epochs=2)
    # num_epochs=2 streams 200 rows through ONE staging pass: 25 full
    # batches, nothing dropped
    assert len(staged) == 200 // 8
    _assert_batches_equal(staged, _expected(scalar_dataset.url, 8,
                                            num_epochs=2))


def test_sharded_parity_pad_tail_mask(scalar_dataset, mesh):
    # 100 rows, batch 24: tail of 4 zero-pads with a valid_mask
    staged = _read_all(scalar_dataset.url, mesh, 24, last_batch='pad')
    assert all('valid_mask' in b for b in staged)
    tail = staged[-1]
    assert tail['valid_mask'].sum() == 100 % 24
    assert tail['valid_mask'].dtype == bool
    _assert_batches_equal(staged, _expected(scalar_dataset.url, 24,
                                            last_batch='pad'))


def test_sharded_parity_short_tail(scalar_dataset, mesh):
    # the 4-row short tail still divides over the 4 data shards
    staged = _read_all(scalar_dataset.url, mesh, 24, last_batch='short')
    assert staged[-1]['id'].shape[0] == 100 % 24
    _assert_batches_equal(staged, _expected(scalar_dataset.url, 24,
                                            last_batch='short'))


def test_sharded_checkpoint_resume_midstream(scalar_dataset, mesh):
    """Mid-stream state_dict on the mesh: a fresh loader restoring it
    delivers every not-yet-delivered row (at-least-once), and the union
    covers the dataset exactly."""
    # 'pad': the resumed stream's row count is not batch-aligned (the
    # checkpoint lands mid-row-group), and a padded tail still divides
    # over the 4 data shards where a 'short' one could not — valid rows
    # are filtered by the mask, so padding never fakes an id
    kw = dict(batch_size=4, mesh=mesh, data_axes=(DATA_AXIS,),
              num_epochs=1, last_batch='pad', fields=['^id$'],
              shuffle_row_groups=False)

    def _valid_ids(batch):
        ids = np.asarray(batch['id'])
        return ids[np.asarray(batch['valid_mask'])].tolist()

    before = set()
    with make_jax_loader(scalar_dataset.url, **kw) as loader:
        it = iter(loader)
        for _ in range(4):
            before.update(_valid_ids(next(it)))
        state = loader.state_dict()
    after = set()
    with make_jax_loader(scalar_dataset.url, **kw) as loader:
        loader.load_state_dict(state)
        for batch in loader:
            after.update(_valid_ids(batch))
    all_ids = set(range(100))
    assert before | after == all_ids
    # the checkpoint was mid-stream: the resume must not replay
    # everything (delivered row-groups stay consumed)
    assert len(after) < 100


def test_sharded_fused_decode_parity(synthetic_dataset, mesh):
    """Deferred image cells decode straight into the shard-slice staging
    buffers (``decode_fused``) and the sharded dispatch ships the result
    — values exactly equal to the rows written (png is lossless)."""
    kw = dict(batch_size=8, mesh=mesh, data_axes=(DATA_AXIS,),
              num_epochs=1, fields=['^id$', '^image_png$'],
              shuffle_row_groups=False)
    with make_jax_loader(synthetic_dataset.url, **kw) as loader:
        staged = [{k: np.asarray(v) for k, v in b.items()}
                  for b in loader]
        fused_rows = loader.diagnostics['fused_decode_rows']
        mode = loader.diagnostics['fused_decode_mode']
    expected = reference.loader_batches(synthetic_dataset.data, 8,
                                        fields=['id', 'image_png'])
    # 100 rows in batches of 8: 'drop' loses an arrival-dependent tail
    _assert_batches_equal(staged, expected, dropped_tail=True)
    # the fused pass really ran (CPU mesh: host-backed fresh assembly)
    assert fused_rows > 0
    assert mode == 'fused-into-slab'


# -- one dispatch covering the whole pytree -----------------------------------


def test_sharded_stage_is_one_device_put_per_batch(mesh, monkeypatch):
    """The staged sharded path ships ALL fields' shard slices in ONE
    batched ``jax.device_put`` call per batch — never one runtime round
    trip per field."""
    calls = []
    real_put = jax.device_put

    def counting_put(x, device=None, **kw):
        calls.append(x)
        return real_put(x, device, **kw)

    monkeypatch.setattr(jax, 'device_put', counting_put)

    def factory(url, **kw):
        return DummyBatchReader(
            fields={'a': ((8,), np.float32), 'b': ((4,), np.int64),
                    'c': ((), np.int32)},
            batch_size=16, num_batches=4)

    with make_jax_loader('dummy://', batch_size=16, mesh=mesh,
                         data_axes=(DATA_AXIS,),
                         reader_factory=factory) as loader:
        batches = list(loader)
    assert len(batches) == 4
    assert len(calls) == 4  # one dispatch per batch, not per field
    # each dispatch carried every field x every addressable device
    assert all(isinstance(c, list) and len(c) == 3 * 8 for c in calls)


def test_sharded_fallback_when_plan_unavailable(mesh, monkeypatch):
    """A sharding the row plan cannot prove sound falls back to the
    per-field ``make_array_from_process_local_data`` build — correct
    batches either way."""
    import petastorm_tpu.parallel.sharding as parallel_sharding
    monkeypatch.setattr(parallel_sharding, 'local_shard_plan',
                        lambda *a, **kw: None)

    def factory(url, **kw):
        return DummyBatchReader(fields={'x': ((8,), np.float32)},
                                batch_size=16, num_batches=3)

    with make_jax_loader('dummy://', batch_size=16, mesh=mesh,
                         data_axes=(DATA_AXIS,),
                         reader_factory=factory) as loader:
        batches = list(loader)
        assert loader._shard_plans == {16: None}
    assert len(batches) == 3
    for batch in batches:
        assert batch['x'].shape == (16, 8)


# -- zero per-batch host allocations on the sharded ring ----------------------


class _ShardLeaf:
    """Per-device shard stand-in that copies on construction (what a
    real transfer does) and claims a non-host platform, pinning ring
    mode on the CPU test host."""

    def __init__(self, arr):
        self.value = np.array(arr, copy=True)

    def devices(self):
        class _Dev:
            platform = 'tpu'
        return (_Dev(),)

    def block_until_ready(self):
        return self


def _sharded_accelerator_put(n_shards):
    """Mimic the sharded dispatch shape: slice each field's local rows
    into per-shard blocks, 'transfer' each (copy), return one leaf per
    field holding its shards."""
    def put(tree):
        out = {}
        for name, arr in tree.items():
            rows = len(arr)
            step = max(1, rows // n_shards)
            shards = [_ShardLeaf(arr[lo:lo + step])
                      for lo in range(0, rows, step)]

            class _Global:
                def __init__(self, shards):
                    self._shards = shards
                    self.value = np.concatenate(
                        [s.value for s in shards])

                def devices(self):
                    class _Dev:
                        platform = 'tpu'
                    return (_Dev(),)

                def block_until_ready(self):
                    return self

            out[name] = _Global(shards)
        return out
    return put


def test_sharded_ring_zero_per_batch_host_allocations():
    """The structural guard on the sharded ring: after warmup, staging N
    more shard-sliced batches allocates no new host buffers — slot slabs
    sized to the LOCAL shard slice are recycled, and tracemalloc growth
    attributed to staging.py stays far below one batch's bytes."""
    bs = 64
    eng = staging.StagingEngine(bs, {'b': np.float32}, 'pad',
                                _sharded_accelerator_put(N_SHARDS),
                                num_slots=2)
    rng = np.random.RandomState(0)
    cols = {'a': rng.rand(bs, 256).astype(np.float32),
            'b': rng.rand(bs, 16)}                      # f64 -> f32 cast
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
    assert grown < batch_bytes / 2, \
        'staging.py allocated %d bytes over %d sharded batches' % (grown, n)


# -- the staging autotuner ----------------------------------------------------


def _window(ready_share=0.0, verdict='idle', dur_s=1.0):
    return {'dur_s': dur_s, 'verdict': verdict,
            'rates': {metric_key(STAGE_SECONDS,
                                 {'stage': 'h2d_ready'}): ready_share}}


class _FakeLoader:
    def __init__(self, stager):
        self._stager = stager
        self._prefetch = 2
        self._out_queue = None

    def _set_prefetch(self, depth):
        self._prefetch = max(1, int(depth))
        return self._prefetch


def _tuner(num_slots=2):
    eng = staging.StagingEngine(8, {}, 'drop',
                                _sharded_accelerator_put(N_SHARDS),
                                num_slots=num_slots)
    loader = _FakeLoader(eng)
    return autotune.StagingAutotuner(loader, window_s=10.0), loader, eng


def test_autotune_knob_default_and_refresh():
    assert autotune.autotune_enabled()
    with _env(PETASTORM_TPU_STAGING_AUTOTUNE='0'):
        assert not autotune.autotune_enabled()
    assert autotune.autotune_enabled()
    with _env(PETASTORM_TPU_STAGING_AUTOTUNE_MAX_SLOTS='3',
              PETASTORM_TPU_STAGING_AUTOTUNE_MAX_PREFETCH='5',
              PETASTORM_TPU_STAGING_AUTOTUNE_WINDOW_SEC='0.5'):
        assert autotune.autotune_max_slots() == 3
        assert autotune.autotune_max_prefetch() == 5
        assert autotune.autotune_window_sec() == 0.5


def test_autotune_disabled_loader_has_no_tuner(scalar_dataset, mesh):
    with _env(PETASTORM_TPU_STAGING_AUTOTUNE='0'):
        with make_jax_loader(scalar_dataset.url, batch_size=8, mesh=mesh,
                             data_axes=(DATA_AXIS,), num_epochs=1,
                             fields=['^id$'],
                             shuffle_row_groups=False) as loader:
            next(iter(loader))
            assert loader._autotuner is None
            assert not loader.diagnostics['staging_autotune']


def test_autotune_deepens_on_sustained_h2d_starvation():
    """3 consecutive starved windows deepen slots AND prefetch; a
    non-starved window resets the streak; bounds hold."""
    tuner, loader, eng = _tuner()
    assert tuner.observe(_window(ready_share=0.9)) == []
    assert tuner.observe(_window(ready_share=0.9)) == []
    # streak broken: no action on the next two starved windows
    assert tuner.observe(_window(ready_share=0.0)) == []
    assert tuner.observe(_window(ready_share=0.9)) == []
    assert tuner.observe(_window(ready_share=0.9)) == []
    actions = tuner.observe(_window(ready_share=0.9))
    assert [a['action'] for a in actions] == ['deepen_slots',
                                             'deepen_prefetch']
    assert eng.num_slots == 3
    assert loader._prefetch == 3
    assert tuner.decisions == 2


def test_autotune_respects_bounds():
    with _env(PETASTORM_TPU_STAGING_AUTOTUNE_MAX_SLOTS='3',
              PETASTORM_TPU_STAGING_AUTOTUNE_MAX_PREFETCH='3'):
        tuner, loader, eng = _tuner()
        for _ in range(12):
            tuner.observe(_window(ready_share=0.9))
        assert eng.num_slots == 3
        assert loader._prefetch == 3
        # saturated at the bounds: further starvation moves nothing
        total = tuner.decisions
        for _ in range(3):
            tuner.observe(_window(ready_share=0.9))
        assert tuner.decisions == total


def test_autotune_ring_grows_to_learned_depth():
    """A deepened engine actually grows its rings at next use, and
    apply_learned carries the depth into a fresh pass's engine."""
    eng = staging.StagingEngine(8, {'x': np.float32}, 'drop',
                                _sharded_accelerator_put(N_SHARDS),
                                num_slots=2)
    loader = _FakeLoader(eng)
    tuner = autotune.StagingAutotuner(loader, window_s=10.0)
    # f64 -> f32 cast routes the batch through the slot ring (a no-cast
    # full single chunk would take the slot-less direct dispatch)
    cols = {'x': np.arange(32, dtype=np.float64).reshape(8, 4)}
    eng.stage(dict(cols), 8)            # ring exists at depth 2
    assert eng.slabs_allocated == 2
    for _ in range(3):
        tuner.observe(_window(ready_share=0.9))
    eng.stage(dict(cols), 8)            # ring grows lazily at next use
    assert eng.num_slots == 3
    assert eng.slabs_allocated == 3
    fresh = staging.StagingEngine(8, {}, 'drop',
                                  _sharded_accelerator_put(N_SHARDS),
                                  num_slots=2)
    tuner.apply_learned(fresh)
    assert fresh.num_slots == 3


def test_autotune_sheds_and_restores_decode_threads():
    # pin the knob so the policy is testable on any host (incl. 1-core
    # CI boxes whose default width is already the floor)
    with _env(PETASTORM_TPU_IMAGE_DECODER_THREADS='3'):
        tuner, _, _ = _tuner()
        assert codecs.image_decoder_threads() == 3
        for _ in range(3):
            actions = tuner.observe(_window(verdict=T.CONSUMER_BOUND))
        assert [a['action'] for a in actions] == ['shed_decode_threads']
        assert codecs.image_decoder_threads() == 2
        # a second consumer-bound streak sheds further, to the floor of 1
        for _ in range(3):
            tuner.observe(_window(verdict=T.CONSUMER_BOUND))
        assert codecs.image_decoder_threads() == 1
        for _ in range(3):
            tuner.observe(_window(verdict=T.CONSUMER_BOUND))
        assert codecs.image_decoder_threads() == 1   # floor holds
        for _ in range(3):
            actions = tuner.observe(_window(verdict=T.PRODUCER_BOUND))
        assert [a['action'] for a in actions] == ['restore_decode_threads']
        assert codecs.image_decoder_threads() == 2
        for _ in range(3):
            tuner.observe(_window(verdict=T.PRODUCER_BOUND))
        assert codecs.image_decoder_threads() == 3   # back at baseline
        # fully restored: the override is gone, the knob rules again
        tuner.close()
        assert codecs.image_decoder_threads() == 3


@pytest.mark.parametrize('fill_at_4,floor', [(0.0, 1), (0.1, 1), (0.2, 2),
                                              (0.3, 3), (0.4, 4)])
def test_autotune_shed_stops_at_the_decode_fill_floor(fill_at_4, floor):
    """Where the staging fill decodes, a shed stops at the width below
    which the fill (its share of the window growing as width / new
    width) would take half the consumer's interval between batches;
    with no ``decode_fused`` in the window it sheds to 1 as before."""
    fill_key = metric_key(STAGE_SECONDS, {'stage': 'decode_fused'})
    with _env(PETASTORM_TPU_IMAGE_DECODER_THREADS='4'):
        tuner, _, _ = _tuner()
        for _ in range(3 * 5):
            window = _window(verdict=T.CONSUMER_BOUND)
            width = codecs.image_decoder_threads()
            if fill_at_4:
                window['rates'][fill_key] = fill_at_4 * 4 / width
            tuner.observe(window)
        assert codecs.image_decoder_threads() == floor
        assert tuner.decisions == 4 - floor
        tuner.close()


def test_autotune_shed_floor_holds_over_a_diluted_window():
    """The window that closes after a consumer pause reads a diluted
    ``decode_fused`` share; the floor answers to the streak's largest."""
    fill_key = metric_key(STAGE_SECONDS, {'stage': 'decode_fused'})
    with _env(PETASTORM_TPU_IMAGE_DECODER_THREADS='2'):
        tuner, _, _ = _tuner()
        for _ in range(4):
            for share in (0.4, 0.4, 0.01):
                window = _window(verdict=T.CONSUMER_BOUND)
                window['rates'][fill_key] = share
                assert tuner.observe(window) == []
        assert codecs.image_decoder_threads() == 2
        # a streak whose every window leaves room sheds
        for _ in range(3):
            window = _window(verdict=T.CONSUMER_BOUND)
            window['rates'][fill_key] = 0.2
            actions = tuner.observe(window)
        assert [a['action'] for a in actions] == ['shed_decode_threads']
        assert codecs.image_decoder_threads() == 1
        tuner.close()


def test_autotune_thread_override_is_single_owner():
    """Two live tuners in one process: the thread override is one slot —
    the second tuner neither sheds over the first's setting nor wipes it
    at close, and its restore ceiling is the KNOB's width, never the
    first tuner's live override."""
    with _env(PETASTORM_TPU_IMAGE_DECODER_THREADS='3'):
        first, _, _ = _tuner()
        for _ in range(3):
            first.observe(_window(verdict=T.CONSUMER_BOUND))
        assert codecs.image_decoder_threads() == 2
        # constructed while the override is live: baseline is the knob's 3
        second, _, _ = _tuner()
        assert second._baseline_threads == 3
        # the second tuner cannot move the owned override...
        for _ in range(3):
            assert second.observe(
                _window(verdict=T.CONSUMER_BOUND)) == []
        assert codecs.image_decoder_threads() == 2
        # ...and its close leaves the owner's setting intact
        second.close()
        assert codecs.image_decoder_threads() == 2
        first.close()
        assert codecs.image_decoder_threads() == 3
        # slot free again: a fresh tuner may now shed
        third, _, _ = _tuner()
        for _ in range(3):
            third.observe(_window(verdict=T.CONSUMER_BOUND))
        assert codecs.image_decoder_threads() == 2
        third.close()


def test_autotune_close_clears_thread_override():
    with _env(PETASTORM_TPU_IMAGE_DECODER_THREADS='2'):
        tuner, _, _ = _tuner()
        for _ in range(3):
            tuner.observe(_window(verdict=T.CONSUMER_BOUND))
        assert codecs.image_decoder_threads() == 1
        tuner.close()
        # the override dies with the loader; the knob rules again
        assert codecs.image_decoder_threads() == 2


def test_autotune_decisions_recorded_everywhere():
    """One decision = ring entry + counter + pipeline_report section
    (+ the tuner's own summary)."""
    T.reset_for_tests()
    tuner, _, eng = _tuner()
    for _ in range(3):
        tuner.observe(_window(ready_share=0.9))
    counts = autotune.decision_counts()
    assert counts.get('deepen_slots') == 1
    assert counts.get('deepen_prefetch') == 1
    registry = T.get_registry()
    by_action = registry.counters_with_prefix(autotune.AUTOTUNE_DECISIONS)
    assert sum(by_action.values()) == 2
    section = T.pipeline_report().get('staging_autotune')
    assert section is not None
    assert section['total'] == 2
    assert {e['action'] for e in section['recent']} == {
        'deepen_slots', 'deepen_prefetch'}
    rendered = T.format_pipeline_report(T.pipeline_report())
    assert 'staging autotune: 2 decision(s)' in rendered
    summary = tuner.summary()
    assert summary['slots'] == eng.num_slots == 3
    assert summary['decisions'] == 2


def test_autotune_report_absent_without_decisions():
    T.reset_for_tests()
    autotune._reset_for_tests()
    assert 'staging_autotune' not in T.pipeline_report()


def test_autotune_loader_end_to_end_smoke(scalar_dataset, mesh):
    """A live mesh loader with aggressive windows ticks the loop on its
    staging thread (the ``autotune`` stage lands) without perturbing
    delivered values."""
    with _env(PETASTORM_TPU_STAGING_AUTOTUNE_WINDOW_SEC='0.05'):
        with make_jax_loader(scalar_dataset.url, batch_size=8, mesh=mesh,
                             data_axes=(DATA_AXIS,), num_epochs=2,
                             fields=['^id$'],
                             shuffle_row_groups=False) as loader:
            ids = [np.asarray(b['id']) for b in loader]
            tuner = loader._autotuner
            assert tuner is not None
            diag = loader.diagnostics
            assert diag['staging_autotune']
            assert diag['staging_prefetch'] >= 2
            assert diag['staging_slot_depth'] >= 2
        # num_epochs=2 streams 200 rows through one pass: 25 full batches
        assert len(ids) == 200 // 8
        # the tuner survives across passes (same object) and a direct
        # tick still works after the pass ended
        assert tuner is loader._autotuner
        result = tuner.tick()
        assert result is None or isinstance(result, list)
        # values asserted identical by the parity suite above; here the
        # stream must simply be the dataset exactly twice
        flat = np.concatenate(ids)
        assert sorted(flat.tolist()) == sorted(2 * list(range(100)))
