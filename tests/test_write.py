"""The distributed write plane (ISSUE 18): fleet-ETL writer,
read-optimized layout, compaction/re-shard, bounded-staleness append.

The load-bearing contracts:

* **Backend byte-parity** — local (pool=None), thread-pool and
  service-fleet writes of the same rows produce byte-identical part
  files AND byte-identical committed manifests.
* **Crash safety (the chaos drill)** — an injected ``io.write`` fault
  mid-distributed-write publishes zero partial files; the retried job
  commits a manifest byte-identical to a clean run's.
* **Torn-free compaction** — a reader opened before a compaction swap
  is multiset-exact; one opened after sees only folded files.
* **The write→read contract** — a dataset written with a declared sort
  key, read back through pushdown + readahead with a selective
  predicate, is multiset-exact with ``rowgroups_pruned > 0``, no
  ``no-statistics`` decline, and readahead hit share > 0.8.
"""

import glob
import hashlib
import json
import os
import threading
import time

import numpy as np
import pyarrow as pa
import pytest

from petastorm_tpu import faults, pushdown, readahead
from petastorm_tpu import telemetry as T
from petastorm_tpu.codecs import ScalarCodec
from petastorm_tpu.errors import MetadataError
from petastorm_tpu.etl.dataset_metadata import (
    DatasetWriter, ParquetDatasetInfo, get_schema,
)
from petastorm_tpu.filters import FiltersPredicate
from petastorm_tpu.fs import get_filesystem_and_path_or_paths
from petastorm_tpu.reader import make_batch_reader
from petastorm_tpu.test_util import reference
from petastorm_tpu.unischema import Unischema, UnischemaField
from petastorm_tpu.workers.thread_pool import ThreadPool
from petastorm_tpu.write import (
    AppendFollower, CompactionDaemon, DistributedDatasetWriter, ManifestError,
    compact_dataset, gc_superseded, load_manifest, plan_compaction,
    self_check, write_dataset_distributed,
)
from petastorm_tpu.write import manifest as wmanifest

SCHEMA = Unischema('WriteTest', [
    UnischemaField('id', np.int64, (), ScalarCodec(pa.int64()), False),
    UnischemaField('val', np.float64, (), ScalarCodec(pa.float64()), False),
])

_FAST = dict(heartbeat_interval_s=0.15, liveness_timeout_s=2.0,
             connect_timeout_s=60, no_workers_timeout_s=20)


def _rows(n, start=0):
    return [{'id': i, 'val': i * 0.5} for i in range(start, start + n)]


def _read_ids(url, **kwargs):
    with make_batch_reader(url, shuffle_row_groups=False, **kwargs) as r:
        return sorted(int(i) for b in r for i in b.id)


def _part_hashes(root):
    return [hashlib.sha1(open(p, 'rb').read()).hexdigest()
            for p in sorted(glob.glob(os.path.join(root, 'part-*')))]


@pytest.fixture(autouse=True)
def _fresh_telemetry():
    T.reset_for_tests()
    yield
    os.environ.pop('PETASTORM_TPU_FAULTS', None)
    faults.refresh_faults()
    assert faults.ARMED is None
    T.reset_for_tests()


def _service_pool(workers=1, retries=3):
    from petastorm_tpu.service.service_pool import ServicePool
    return ServicePool(spawn_local_workers=workers, max_retries=retries,
                       **_FAST)


# ---------------------------------------------------------------------------
# The commit manifest
# ---------------------------------------------------------------------------


class TestManifest:
    def test_bytes_deterministic(self):
        entries = [wmanifest.file_entry('b.parquet', 10, 1, 100),
                   wmanifest.file_entry('a.parquet', 10, 1, 100)]
        m1 = wmanifest.build_manifest(entries, generation=3, sort_key='id')
        m2 = wmanifest.build_manifest(list(reversed(entries)), generation=3,
                                      sort_key='id')
        assert wmanifest.dumps(m1) == wmanifest.dumps(m2)
        # no wall-clock state anywhere in the committed bytes
        assert b'time' not in wmanifest.dumps(m1)

    def test_swap_must_be_monotonic(self, tmp_path):
        fs, root = get_filesystem_and_path_or_paths('file://' + str(tmp_path))
        wmanifest.publish(fs, root, wmanifest.build_manifest([], generation=2))
        with pytest.raises(ManifestError, match='not monotonic'):
            wmanifest.publish(fs, root,
                              wmanifest.build_manifest([], generation=2))
        assert load_manifest(fs, root)['generation'] == 2

    def test_missing_is_none_unparseable_raises(self, tmp_path):
        fs, root = get_filesystem_and_path_or_paths('file://' + str(tmp_path))
        assert load_manifest(fs, root) is None
        (tmp_path / '_manifest.json').write_text('{nope')
        with pytest.raises(ManifestError, match='Unparseable'):
            load_manifest(fs, root)

    def test_staleness_from_file_mtime(self, tmp_path):
        fs, root = get_filesystem_and_path_or_paths('file://' + str(tmp_path))
        assert wmanifest.staleness_s(fs, root) is None
        wmanifest.publish(fs, root, wmanifest.build_manifest([], generation=1))
        age = wmanifest.staleness_s(fs, root)
        assert age is not None and age < 30.0

    def test_publish_serializes_via_commit_lease(self, tmp_path):
        """A held commit lease blocks a second committer loudly; a lease
        orphaned by a dead committer is broken once stale."""
        fs, root = get_filesystem_and_path_or_paths('file://' + str(tmp_path))
        lock_file = tmp_path / '_manifest.lock'
        lock_file.write_bytes(b'held by a live committer')
        with pytest.raises(ManifestError, match='lease'):
            wmanifest.publish(fs, root,
                              wmanifest.build_manifest([], generation=1),
                              lock_timeout_s=0.3)
        old = time.time() - 3600
        os.utime(lock_file, (old, old))
        with wmanifest.CommitLock(fs, root, timeout_s=5.0, stale_s=60.0):
            pass  # stale lease broken, fresh one taken and released
        assert not lock_file.exists()
        wmanifest.publish(fs, root, wmanifest.build_manifest([], generation=1))
        assert load_manifest(fs, root)['generation'] == 1
        assert not lock_file.exists()  # publish releases its own lease

    def test_load_propagates_transient_io_errors(self, tmp_path):
        """A transiently unreadable manifest must NOT read as
        'manifest-less dataset' — that silently degrades discovery to
        the torn directory walk."""
        fs, root = get_filesystem_and_path_or_paths('file://' + str(tmp_path))
        wmanifest.publish(fs, root, wmanifest.build_manifest([], generation=1))

        class FlakyFS:
            def exists(self, path):
                return fs.exists(path)

            def open(self, *args, **kwargs):
                raise OSError('transient storage hiccup')

        with pytest.raises(OSError, match='transient'):
            wmanifest.load(FlakyFS(), root)
        assert wmanifest.load(fs, root)['generation'] == 1

    def test_purge_respects_age_gate(self, tmp_path):
        fs, root = get_filesystem_and_path_or_paths('file://' + str(tmp_path))
        fresh = tmp_path / '.tmp.part-live.parquet'
        fresh.write_bytes(b'x')
        assert wmanifest.purge_stale_tmp(fs, root) == 0  # too young
        assert fresh.exists()
        assert wmanifest.purge_stale_tmp(fs, root, max_age_s=0.0) == 1
        assert not fresh.exists()


# ---------------------------------------------------------------------------
# Local backend + round-trip
# ---------------------------------------------------------------------------


class TestLocalWrite:
    def test_write_commit_read_round_trip(self, tmp_path):
        url = 'file://' + str(tmp_path)
        w = write_dataset_distributed(url, SCHEMA, _rows(300), sort_by='id',
                                      shard_rows=100)
        assert w.manifest['generation'] == 1
        assert all(e['path'].startswith('part-g0001-s')
                   for e in w.manifest['files'])
        assert _read_ids(url) == list(range(300))
        # Unischema fidelity: the committed footer round-trips the schema
        assert {f.name for f in get_schema(ParquetDatasetInfo(url))} == \
            {'id', 'val'}

    def test_no_tmp_litter_after_commit(self, tmp_path):
        url = 'file://' + str(tmp_path)
        write_dataset_distributed(url, SCHEMA, _rows(100), shard_rows=40)
        assert glob.glob(str(tmp_path / '.tmp.*')) == []

    def test_zero_row_dataset_commits_cleanly(self, tmp_path):
        from petastorm_tpu.errors import NoDataAvailableError
        url = 'file://' + str(tmp_path)
        w = write_dataset_distributed(url, SCHEMA, [])
        assert w.manifest['generation'] == 1
        assert w.manifest['files'][0]['rows'] == 0
        # schema round-trips even with zero rows; the reader's existing
        # no-row-groups guard fires rather than anything torn
        assert {f.name for f in get_schema(ParquetDatasetInfo(url))} == \
            {'id', 'val'}
        with pytest.raises(NoDataAvailableError):
            _read_ids(url)

    def test_fresh_target_refuses_second_nonappend_write(self, tmp_path):
        url = 'file://' + str(tmp_path)
        write_dataset_distributed(url, SCHEMA, _rows(10))
        with pytest.raises(ValueError, match='append=True'):
            DistributedDatasetWriter(url, SCHEMA)

    def test_abort_on_exception_leaves_no_generation_litter(self, tmp_path):
        url = 'file://' + str(tmp_path)
        with pytest.raises(RuntimeError, match='boom'):
            with DistributedDatasetWriter(url, SCHEMA, shard_rows=20) as w:
                w.write_row_dicts(_rows(50))  # dispatches 2 shards inline
                raise RuntimeError('boom')
        assert glob.glob(str(tmp_path / 'part-*')) == []
        assert glob.glob(str(tmp_path / '.tmp.*')) == []
        assert load_manifest(*get_filesystem_and_path_or_paths(url)) is None

    def test_write_metrics_and_report_section(self, tmp_path):
        url = 'file://' + str(tmp_path)
        write_dataset_distributed(url, SCHEMA, _rows(120), shard_rows=60)
        registry = T.get_registry()
        assert registry.counter_value(
            'petastorm_tpu_write_rows_total') == 120
        assert registry.counter_value(
            'petastorm_tpu_write_files_total') == 2
        assert registry.counter_value(
            'petastorm_tpu_write_commits_total') == 1
        report = T.pipeline_report()
        assert report['write']['rows_written'] == 120
        assert report['write']['generation'] == 1
        assert any('write plane:' in line
                   for line in T.format_pipeline_report(report).splitlines())


# ---------------------------------------------------------------------------
# Satellite: DatasetWriter lifecycle + statistics hygiene
# ---------------------------------------------------------------------------


class TestDatasetWriterLifecycle:
    def test_exception_path_aborts_not_publishes(self, tmp_path):
        url = 'file://' + str(tmp_path / 'ds')
        with pytest.raises(RuntimeError, match='boom'):
            with DatasetWriter(url, SCHEMA, rowgroup_size_rows=10,
                               workers_count=2) as w:
                w.write_row_dicts(_rows(25))
                raise RuntimeError('boom')
        # no partial output, and the encode pool is gone
        assert glob.glob(str(tmp_path / 'ds' / '*.parquet')) == []
        assert w._encode_pool is None

    def test_success_path_still_publishes(self, tmp_path):
        url = 'file://' + str(tmp_path / 'ds')
        with DatasetWriter(url, SCHEMA, rowgroup_size_rows=10) as w:
            w.write_row_dicts(_rows(25))
        assert len(w.paths_written) == 1
        assert w._rows_written == 25

    def test_footer_statistics_always_written(self, tmp_path):
        import pyarrow.parquet as pq
        url = 'file://' + str(tmp_path / 'ds')
        with DatasetWriter(url, SCHEMA, rowgroup_size_rows=10,
                           sort_by='id') as w:
            w.write_row_dicts(_rows(30))
        meta = pq.read_metadata(w.paths_written[0])
        for rg in range(meta.num_row_groups):
            st = meta.row_group(rg).column(0).statistics
            assert st is not None and st.has_min_max
        assert meta.row_group(0).sorting_columns  # sort key stamped

    def test_sort_by_unknown_column_rejected(self, tmp_path):
        with pytest.raises(ValueError, match='not in the schema'):
            DatasetWriter('file://' + str(tmp_path), SCHEMA, sort_by='nope')

    def test_pushdown_never_declines_no_statistics_on_own_output(
            self, tmp_path):
        """Satellite 2: the whole point of write_statistics hygiene —
        a self-written dataset is never full-scan-priced for lack of
        footer statistics."""
        url = 'file://' + str(tmp_path)
        write_dataset_distributed(url, SCHEMA, _rows(200), sort_by='id',
                                  shard_rows=50)
        T.reset_for_tests()
        pred = FiltersPredicate([('id', '<', 20)])
        got = _read_ids(url, predicate=pred)
        assert got == list(range(20))
        summary = pushdown.planner_summary()
        assert summary['declines'].get('no-statistics', 0) == 0
        assert summary['rowgroups_pruned'] > 0


# ---------------------------------------------------------------------------
# Backend byte-parity (local / thread / service fleet)
# ---------------------------------------------------------------------------


class TestBackendParity:
    def test_thread_pool_matches_local_bytes(self, tmp_path):
        rows = _rows(300)
        w_local = write_dataset_distributed(
            'file://' + str(tmp_path / 'local'), SCHEMA, rows,
            sort_by='id', shard_rows=75)
        w_thread = write_dataset_distributed(
            'file://' + str(tmp_path / 'thread'), SCHEMA, rows,
            sort_by='id', shard_rows=75, pool=ThreadPool(3))
        assert wmanifest.dumps(w_local.manifest) == \
            wmanifest.dumps(w_thread.manifest)
        assert _part_hashes(str(tmp_path / 'local')) == \
            _part_hashes(str(tmp_path / 'thread'))

    def test_service_fleet_matches_local_bytes(self, tmp_path):
        rows = _rows(200)
        w_local = write_dataset_distributed(
            'file://' + str(tmp_path / 'local'), SCHEMA, rows,
            sort_by='id', shard_rows=50)
        w_fleet = write_dataset_distributed(
            'file://' + str(tmp_path / 'fleet'), SCHEMA, rows,
            sort_by='id', shard_rows=50, pool=_service_pool(workers=2))
        assert wmanifest.dumps(w_local.manifest) == \
            wmanifest.dumps(w_fleet.manifest)
        assert _part_hashes(str(tmp_path / 'local')) == \
            _part_hashes(str(tmp_path / 'fleet'))


# ---------------------------------------------------------------------------
# Crash safety: the chaos drill
# ---------------------------------------------------------------------------


def _arm(spec):
    os.environ['PETASTORM_TPU_FAULTS'] = spec
    faults.refresh_faults()


def _disarm():
    os.environ.pop('PETASTORM_TPU_FAULTS', None)
    faults.refresh_faults()


class TestCrashSafety:
    def test_faulted_rename_retries_to_byte_identical_manifest(
            self, tmp_path):
        """The acceptance drill: an io.write fault at the publication
        rename kills the first shard attempt; the fleet retries and the
        committed manifest + part files are byte-identical to a clean
        run. Zero partial files are ever visible under the final names.
        """
        rows = _rows(200)
        w_clean = write_dataset_distributed(
            'file://' + str(tmp_path / 'clean'), SCHEMA, rows,
            sort_by='id', shard_rows=50)
        _arm('io.write:error:1:times=1:match=#rename')
        try:
            w_chaos = write_dataset_distributed(
                'file://' + str(tmp_path / 'chaos'), SCHEMA, rows,
                sort_by='id', shard_rows=50,
                pool=_service_pool(workers=1, retries=3))
        finally:
            _disarm()
        assert wmanifest.dumps(w_clean.manifest) == \
            wmanifest.dumps(w_chaos.manifest)
        assert _part_hashes(str(tmp_path / 'clean')) == \
            _part_hashes(str(tmp_path / 'chaos'))
        assert glob.glob(str(tmp_path / 'chaos' / '.tmp.*')) == []

    def test_fault_before_part_write_publishes_nothing(self, tmp_path):
        """A fault before any data write (the #part seam) on EVERY
        attempt exhausts the retry budget: the write raises, no final
        part file and no manifest are ever published."""
        url = 'file://' + str(tmp_path)
        _arm('io.write:error:1:match=#part')
        try:
            with pytest.raises(Exception):
                write_dataset_distributed(url, SCHEMA, _rows(60),
                                          shard_rows=30)
        finally:
            _disarm()
        assert glob.glob(str(tmp_path / 'part-*')) == []
        assert load_manifest(*get_filesystem_and_path_or_paths(url)) is None

    def test_faulted_manifest_swap_keeps_previous_generation(self,
                                                             tmp_path):
        """A fault at the #manifest seam mid-append: the new generation
        never commits, and readers keep seeing generation 1 exactly."""
        url = 'file://' + str(tmp_path)
        write_dataset_distributed(url, SCHEMA, _rows(100), shard_rows=50)
        _arm('io.write:error:1:match=#manifest')
        try:
            with pytest.raises(Exception):
                write_dataset_distributed(url, SCHEMA, _rows(100, start=100),
                                          shard_rows=50, append=True)
        finally:
            _disarm()
        fs, root = get_filesystem_and_path_or_paths(url)
        assert load_manifest(fs, root)['generation'] == 1
        assert _read_ids(url) == list(range(100))


# ---------------------------------------------------------------------------
# Compaction
# ---------------------------------------------------------------------------


def _small_file_dataset(tmp_path, files=6, rows_per=30):
    url = 'file://' + str(tmp_path)
    w = None
    for i in range(files):
        w = write_dataset_distributed(
            url, SCHEMA, _rows(rows_per, start=i * rows_per),
            sort_by='id', shard_rows=rows_per, append=(i > 0))
    return url, files * rows_per, w


class TestCompaction:
    def test_fold_preserves_rows_schema_and_statistics(self, tmp_path):
        import pyarrow.parquet as pq
        url, total, _ = _small_file_dataset(tmp_path)
        m = compact_dataset(url, minimum=2)
        assert m is not None
        folded = [e for e in m['files'] if e['source'] == 'compact']
        assert folded and all(e['replaces'] for e in folded)
        assert _read_ids(url) == list(range(total))
        assert {f.name for f in get_schema(ParquetDatasetInfo(url))} == \
            {'id', 'val'}
        fs, root = get_filesystem_and_path_or_paths(url)
        for e in folded:
            with fs.open(os.path.join(root, e['path']), 'rb') as f:
                meta = pq.read_metadata(f)
            st = meta.row_group(0).column(0).statistics
            assert st is not None and st.has_min_max

    def test_concurrent_reader_is_multiset_exact_across_swap(self,
                                                             tmp_path):
        """A reader that resolved the pre-compaction manifest keeps its
        file set; the swap happens mid-iteration and the delivered
        multiset is exact — no torn mix, no loss, no duplication."""
        url, total, _ = _small_file_dataset(tmp_path)
        got = []
        with make_batch_reader(url, shuffle_row_groups=False) as reader:
            it = iter(reader)
            got.extend(int(i) for i in next(it).id)
            assert compact_dataset(url, minimum=2) is not None
            for batch in it:
                got.extend(int(i) for i in batch.id)
        assert sorted(got) == list(range(total))
        # a reader opened AFTER the swap sees only the folded layout
        assert _read_ids(url) == list(range(total))

    def test_gc_waits_out_the_grace_window(self, tmp_path):
        url, total, _ = _small_file_dataset(tmp_path)
        compact_dataset(url, minimum=2)
        fs, root = get_filesystem_and_path_or_paths(url)
        assert gc_superseded(fs, root, grace_s=3600) == []  # readers live
        removed = gc_superseded(fs, root, grace_s=0)
        assert removed
        assert _read_ids(url) == list(range(total))

    def test_gc_grace_measured_from_swap_not_file_age(self, tmp_path):
        """High-severity regression: hour-old source files must NOT be
        GC'd the instant a compaction supersedes them — the grace
        window runs from the manifest swap, so a reader that resolved
        the previous generation seconds before the swap keeps its
        files."""
        url, total, _ = _small_file_dataset(tmp_path)
        old = time.time() - 7200
        for p in glob.glob(str(tmp_path / 'part-*')):
            os.utime(p, (old, old))
        assert compact_dataset(url, minimum=2) is not None
        fs, root = get_filesystem_and_path_or_paths(url)
        assert gc_superseded(fs, root, grace_s=5.0) == []
        assert _read_ids(url) == list(range(total))

    def test_reader_holding_old_file_list_survives_restamp(self, tmp_path):
        """The footer restamp merges the previous generation's
        row-group counts: a reader that resolved the pre-swap file list
        (or opens between restamp and swap) still loads row-groups for
        the superseded files it holds."""
        from petastorm_tpu.etl.dataset_metadata import load_row_groups
        url, total, _ = _small_file_dataset(tmp_path)
        old_paths = list(ParquetDatasetInfo(url).file_paths)
        assert compact_dataset(url, minimum=2) is not None
        stale = ParquetDatasetInfo(url, validate=False)
        stale.file_paths = old_paths
        pieces = load_row_groups(stale)  # no MetadataError
        assert len(pieces) >= len(old_paths)

    def test_plan_respects_min_files_floor(self):
        committed = wmanifest.build_manifest(
            [wmanifest.file_entry('a.parquet', 10, 1, 100),
             wmanifest.file_entry('b.parquet', 10, 1, 100)],
            generation=1)
        assert plan_compaction(committed, minimum=3) == []
        assert plan_compaction(committed, minimum=2)

    def test_nothing_to_fold_returns_none(self, tmp_path):
        url = 'file://' + str(tmp_path)
        write_dataset_distributed(url, SCHEMA, _rows(50), shard_rows=50)
        assert compact_dataset(url, minimum=4) is None

    def test_restores_sort_after_interleaved_appends(self, tmp_path):
        """Appends interleave key ranges; the fold re-sorts, so the
        self-check's predicted prune share recovers."""
        url = 'file://' + str(tmp_path)
        write_dataset_distributed(url, SCHEMA,
                                  _rows(50) + _rows(50, start=100),
                                  sort_by='id', shard_rows=25)
        write_dataset_distributed(url, SCHEMA,
                                  _rows(50, start=50) + _rows(50, start=150),
                                  sort_by='id', shard_rows=25, append=True)
        compact_dataset(url, minimum=2,
                        target_bytes=16 * 1024)  # force multiple outputs
        report = self_check(url, sort_key='id')
        assert report['stats_coverage'] == 1.0
        assert _read_ids(url) == list(range(200))


# ---------------------------------------------------------------------------
# Bounded-staleness append
# ---------------------------------------------------------------------------


class TestAppend:
    def test_generations_are_monotonic_and_union(self, tmp_path):
        url = 'file://' + str(tmp_path)
        w1 = write_dataset_distributed(url, SCHEMA, _rows(50), shard_rows=50,
                                       sort_by='id')
        w2 = write_dataset_distributed(url, SCHEMA, _rows(50, start=50),
                                       shard_rows=50, append=True)
        assert (w1.manifest['generation'], w2.manifest['generation']) == (1, 2)
        assert w2.sort_by == 'id'  # inherited from the committed manifest
        assert len(w2.manifest['files']) == 2
        assert _read_ids(url) == list(range(100))

    def test_reader_staleness_opt_in(self, tmp_path):
        url = 'file://' + str(tmp_path)
        bare_url = 'file://' + str(tmp_path / 'bare')
        write_dataset_distributed(url, SCHEMA, _rows(30), shard_rows=30)
        assert _read_ids(url, max_staleness_s=5) == list(range(30))
        # a manifest-less dataset has no commit point to bound against
        with DatasetWriter(bare_url, SCHEMA) as w:
            w.write_row_dicts(_rows(10))
        with pytest.raises(ValueError, match='committed manifest'):
            make_batch_reader(bare_url, max_staleness_s=5)

    def test_follower_picks_up_rows_within_bound(self, tmp_path):
        url = 'file://' + str(tmp_path)
        write_dataset_distributed(url, SCHEMA, _rows(60), shard_rows=30)
        seen = []
        stamps = {}
        follower = AppendFollower(url, max_staleness_s=0.4,
                                  stop_after_idle_s=3.0)

        def consume():
            for batch in follower:
                seen.extend(int(i) for i in batch.id)
                stamps[len(seen)] = time.monotonic()

        thread = threading.Thread(target=consume)
        thread.start()
        time.sleep(1.0)
        committed_at = time.monotonic()
        write_dataset_distributed(url, SCHEMA, _rows(40, start=60),
                                  shard_rows=40, append=True)
        thread.join(timeout=30)
        assert not thread.is_alive()
        assert sorted(seen) == list(range(100))
        # the appended rows arrived within the staleness bound (+read)
        first_tail_stamp = min(t for n, t in stamps.items() if n > 60)
        assert first_tail_stamp - committed_at < 3.0

    def test_follower_skips_delivered_compaction_folds(self, tmp_path):
        url, total, _ = _small_file_dataset(tmp_path, files=4, rows_per=20)
        follower = AppendFollower(url, max_staleness_s=0.2,
                                  stop_after_idle_s=1.5)
        seen = []

        def consume():
            for batch in follower:
                seen.extend(int(i) for i in batch.id)

        thread = threading.Thread(target=consume)
        thread.start()
        time.sleep(0.8)  # let the initial generation drain
        compact_dataset(url, minimum=2)
        thread.join(timeout=30)
        assert not thread.is_alive()
        # the fold's rows already flowed through the source files:
        # exactly-once, no redelivery
        assert sorted(seen) == list(range(total))


    def test_partial_fold_delivers_only_undelivered_sources(self, tmp_path):
        """A fold that mixes delivered and undelivered sources must not
        be delivered whole (that redelivers consumed rows): the
        follower reads the still-on-disk undelivered source files
        directly, and the fold is settled afterwards."""
        url = 'file://' + str(tmp_path)
        write_dataset_distributed(url, SCHEMA, _rows(40), shard_rows=20,
                                  sort_by='id')
        follower = AppendFollower(url)
        first = follower._fresh_entries()
        assert len(first) == 2
        follower._mark_delivered(first)
        # a new generation lands, then compaction folds it together
        # with the already-delivered files
        write_dataset_distributed(url, SCHEMA, _rows(40, start=40),
                                  shard_rows=40, append=True)
        assert compact_dataset(url, minimum=2) is not None
        fresh = follower._fresh_entries()
        assert fresh and all(e.get('settles') for e in fresh)
        urls = [url.rstrip('/') + '/' + e['path'] for e in fresh]
        with make_batch_reader(urls, shuffle_row_groups=False) as reader:
            got = sorted(int(i) for b in reader for i in b.id)
        assert got == list(range(40, 80))  # ONLY the undelivered rows
        follower._mark_delivered(fresh)
        # the fold is settled: the next generation delivers only its
        # own new file, nothing from the fold
        write_dataset_distributed(url, SCHEMA, _rows(10, start=80),
                                  shard_rows=10, append=True)
        nxt = follower._fresh_entries()
        assert len(nxt) == 1 and not nxt[0].get('replaces')


# ---------------------------------------------------------------------------
# Concurrent committers: the commit lease
# ---------------------------------------------------------------------------


class TestCommitConcurrency:
    def test_append_commit_rebases_over_concurrent_compaction(self,
                                                              tmp_path):
        """Lost-update regression: an append writer whose base
        generation is compacted away mid-write rebases onto the latest
        manifest at commit — the fold keeps its files, the append
        stacks on top, nothing is dropped or resurrected."""
        url, total, _ = _small_file_dataset(tmp_path, files=4, rows_per=20)
        w = DistributedDatasetWriter(url, SCHEMA, shard_rows=40, append=True)
        w.write_row_dicts(_rows(40, start=total))
        compacted = compact_dataset(url, minimum=2)
        assert compacted is not None  # swapped a generation mid-write
        w.close()
        assert w.manifest['generation'] == compacted['generation'] + 1
        assert any(e['source'] == 'compact' for e in w.manifest['files'])
        assert _read_ids(url) == list(range(total + 40))

    def test_same_generation_part_collision_fails_loudly(self, tmp_path):
        """Two appenders racing the same generation collide on the
        deterministic part names: the second must fail loudly instead
        of silently replacing the first's committed bytes."""
        url = 'file://' + str(tmp_path)
        write_dataset_distributed(url, SCHEMA, _rows(50), shard_rows=50)
        wa = DistributedDatasetWriter(url, SCHEMA, shard_rows=50, append=True)
        wb = DistributedDatasetWriter(url, SCHEMA, shard_rows=50, append=True)
        wa.write_row_dicts(_rows(50, start=50))  # renamed into place inline
        with pytest.raises(RuntimeError, match='collision'):
            wb.write_row_dicts(_rows(50, start=100))
        wa.close()
        assert _read_ids(url) == list(range(100))


# ---------------------------------------------------------------------------
# Layout: targets + self-check
# ---------------------------------------------------------------------------


class TestLayout:
    def test_target_tracks_readahead_window(self, monkeypatch):
        from petastorm_tpu.write import layout
        monkeypatch.setenv('PETASTORM_TPU_READAHEAD_MAX_RANGE_MB', '8')
        assert layout.target_rowgroup_bytes() == 8 * 1024 * 1024
        monkeypatch.setenv('PETASTORM_TPU_WRITE_ROWGROUP_MB', '4')
        assert layout.target_rowgroup_bytes() == 4 * 1024 * 1024

    def test_sorted_dataset_reports_clean(self, tmp_path):
        url = 'file://' + str(tmp_path)
        w = write_dataset_distributed(url, SCHEMA, _rows(400), sort_by='id',
                                      shard_rows=100)
        report = w.last_self_check
        assert report is not None
        assert report['stats_coverage'] == 1.0
        assert report['predicted_prune_share'] > 0.5
        assert report['coalesce']['fits_window_share'] == 1.0
        assert report['warnings'] == []

    def test_scattered_sort_key_warns(self, tmp_path):
        url = 'file://' + str(tmp_path)
        rng = np.random.RandomState(7)
        ids = rng.permutation(400)
        rows = [{'id': int(i), 'val': float(i)} for i in ids]
        write_dataset_distributed(url, SCHEMA, rows, sort_by='id',
                                  shard_rows=100)
        report = self_check(url, sort_key='id')
        assert report['predicted_prune_share'] < 0.5
        assert any('prunes only' in warning for warning in report['warnings'])

    def test_self_check_knob_skips(self, tmp_path, monkeypatch):
        monkeypatch.setenv('PETASTORM_TPU_WRITE_SELF_CHECK', '0')
        url = 'file://' + str(tmp_path)
        w = write_dataset_distributed(url, SCHEMA, _rows(50), shard_rows=50)
        assert w.last_self_check is None


# ---------------------------------------------------------------------------
# Satellite 3: the write→read property test
# ---------------------------------------------------------------------------


class TestWriteReadContract:
    @pytest.mark.parametrize('backend', ['local', 'fleet'])
    def test_selective_read_is_index_priced_and_exact(self, tmp_path,
                                                      backend):
        """Write with the new plane (both backends), read back through
        pushdown + readahead with a selective predicate: exact row
        multiset, rowgroups pruned, readahead hit share > 0.8."""
        url = 'file://' + str(tmp_path)
        pool = ThreadPool(3) if backend == 'fleet' else None
        write_dataset_distributed(url, SCHEMA, _rows(400), sort_by='id',
                                  shard_rows=50, pool=pool)
        T.reset_for_tests()
        pred = FiltersPredicate([('id', '>=', 300)])
        got = _read_ids(url, predicate=pred, num_epochs=4)
        assert got == sorted(list(range(300, 400)) * 4)
        summary = pushdown.planner_summary()
        assert summary['rowgroups_pruned'] > 0
        assert summary['declines'].get('no-statistics', 0) == 0
        registry = T.get_registry()
        hits = registry.counter_value(readahead.READAHEAD_HITS)
        misses = registry.counter_value(readahead.READAHEAD_MISSES)
        assert hits + misses > 0
        assert hits / (hits + misses) > 0.8

    def test_full_multiset_parity_against_oracle(self, tmp_path):
        """Pruned + late-materialized + readahead read vs a plain pyarrow
        read with pyarrow's own filters: identical rows."""
        url = 'file://' + str(tmp_path)
        write_dataset_distributed(url, SCHEMA, _rows(200), sort_by='id',
                                  shard_rows=40)
        filters = [('id', 'in', (3, 77, 150, 199))]
        plain = sorted(row['id'] for row in reference.read_rows(
            url, columns=['id'], filters=filters))
        got = _read_ids(url, predicate=FiltersPredicate(filters))
        assert got == plain == [3, 77, 150, 199]


# ---------------------------------------------------------------------------
# PR 19 satellites: trace threading, the staleness gauge, the daemon mount
# ---------------------------------------------------------------------------


class TestWriteObservability:
    def test_writer_threads_trace_and_dumps_chrome_json(self, tmp_path,
                                                        monkeypatch):
        """With tracing armed the writer mints per-shard contexts, the
        encode/write_flush stages land in the flight recorder, and
        ``dump_trace`` exports them as Chrome trace-event JSON — the
        write-plane sibling of ``Reader.dump_trace``."""
        monkeypatch.setenv('PETASTORM_TPU_TRACE', '1')
        monkeypatch.setenv('PETASTORM_TPU_TRACE_SAMPLE', '1')
        T.refresh()
        url = 'file://' + str(tmp_path / 'traced')
        w = write_dataset_distributed(url, SCHEMA, _rows(40), shard_rows=20)
        out = str(tmp_path / 'write-trace.json')
        assert w.dump_trace(out) > 0
        with open(out) as f:
            doc = json.load(f)
        names = {e.get('name') for e in doc['traceEvents']}
        assert {'encode', 'write_flush'} <= names, names
        # the shard lifelines carry minted contexts, so the critical-path
        # engine can reconstruct the write plane too
        assert any((e.get('args') or {}).get('trace_id')
                   for e in doc['traceEvents'])

    def test_append_follower_publishes_staleness_gauge(self, tmp_path):
        from petastorm_tpu.write import append as wappend
        url = 'file://' + str(tmp_path)
        write_dataset_distributed(url, SCHEMA, _rows(20), shard_rows=20)
        follower = AppendFollower(url, max_staleness_s=0.2)
        reg = T.get_registry()
        follower._note_staleness(True)   # undelivered rows pending
        lag = reg.gauge_value(wappend.APPEND_STALENESS)
        assert lag is not None and lag >= 0.0
        follower._note_staleness(False)  # caught up
        assert reg.gauge_value(wappend.APPEND_STALENESS) == 0.0

    def test_compaction_daemon_mounts_health_section(self, tmp_path,
                                                     monkeypatch):
        from petastorm_tpu.telemetry import obs_server
        monkeypatch.setenv('PETASTORM_TPU_OBS_PORT', '0')
        T.refresh()
        url, total, _ = _small_file_dataset(tmp_path, files=6, rows_per=30)
        daemon = CompactionDaemon(url, interval_s=0.1, gc_grace_s=600.0)
        daemon.start()
        try:
            deadline = time.monotonic() + 20
            while time.monotonic() < deadline and daemon.runs == 0:
                time.sleep(0.05)
            assert daemon.runs >= 1, 'daemon never folded the small files'
            health = obs_server.build_health()
            section = next(v for k, v in health['components'].items()
                           if k.startswith('compaction-daemon'))
            assert section['dataset_url'] == url
            assert section['runs'] >= 1
            assert section['generation'] >= 2  # the fold published
        finally:
            daemon.stop()
        # stop() unmounts: a dead daemon must not linger in /health
        assert not any(k.startswith('compaction-daemon')
                       for k in obs_server.build_health()['components'])
        assert _read_ids(url) == list(range(total))
