"""Query-shaped reads (ISSUE 12): statistics-driven row-group pruning,
projection pushdown + late materialization, and predicate cacheability.

The load-bearing contract is EXACT PARITY: a pruned + late-materialized
epoch must deliver the identical row multiset as a plain pyarrow read
(``petastorm_tpu/test_util/reference.py``: pyarrow's own ``filters=``,
or the predicate's ``do_include`` over the rows it read), across pool
types and under sharding — and pruning must be conservative everywhere
(null-bearing columns, missing statistics, faulted footer reads degrade
to unpruned reads, never to a wrong answer).
"""

import os
import tempfile

import numpy as np
import pytest

from petastorm_tpu import make_batch_reader, make_reader
from petastorm_tpu import pushdown
from petastorm_tpu import telemetry as T
from petastorm_tpu.filters import FiltersPredicate
from petastorm_tpu.predicates import in_lambda, in_negate, in_reduce, in_set
from petastorm_tpu.test_util import reference
from tests.test_common import TestSchema


@pytest.fixture(autouse=True)
def _fresh_telemetry():
    T.reset_for_tests()
    yield
    T.reset_for_tests()


def _read_ids(url, plain=False, pool='thread', predicate=None, **kwargs):
    """Sorted delivered ids; ``plain=True`` gives the plain read's
    instead (the reader's other options do not change which rows
    match, so the plain read ignores them)."""
    if plain:
        return sorted(int(row['id']) for row in _plain_rows(url, predicate))
    with make_batch_reader(url, reader_pool_type=pool, predicate=predicate,
                           shuffle_row_groups=False, **kwargs) as reader:
        return sorted(int(i) for batch in reader for i in batch.id)


def _plain_rows(url, predicate, schema=None, columns=None):
    """The plain read: pyarrow's own ``filters=`` for a
    FiltersPredicate, else the predicate's ``do_include`` over every
    row pyarrow read."""
    if isinstance(predicate, FiltersPredicate):
        return reference.read_rows(url, columns=columns, schema=schema,
                                   filters=predicate.clauses)
    rows = reference.read_rows(url, columns=columns, schema=schema)
    return rows if predicate is None else reference.matching_rows(
        rows, predicate)


def _do_include_ids(url, predicate, null=None):
    """Sorted ids of the rows pyarrow read that ``predicate.do_include``
    accepts, with null cells of ``x`` read as ``null``."""
    rows = reference.read_rows(url)
    for row in rows:
        if row['x'] is None:
            row['x'] = null
    return sorted(row['id'] for row in reference.matching_rows(rows,
                                                               predicate))


# ---------------------------------------------------------------------------
# The prover: interval logic per clause/op, against real footer stats
# ---------------------------------------------------------------------------


@pytest.fixture(scope='module')
def two_rowgroup_url(tmp_path_factory):
    """One file, two row-groups with disjoint known ranges:
    rg0 x∈[0,9] (no nulls), rg1 x∈[20,29] (no nulls)."""
    import pyarrow as pa
    import pyarrow.parquet as pq
    path = str(tmp_path_factory.mktemp('prover')) + '/ds'
    os.makedirs(path)
    t0 = pa.table({'x': pa.array(range(10), type=pa.int64()),
                   'id': pa.array(range(10), type=pa.int64())})
    t1 = pa.table({'x': pa.array(range(20, 30), type=pa.int64()),
                   'id': pa.array(range(20, 30), type=pa.int64())})
    writer = pq.ParquetWriter(os.path.join(path, 'part0.parquet'), t0.schema)
    writer.write_table(t0)
    writer.write_table(t1)
    writer.close()
    return 'file://' + path


class TestProver:
    @pytest.mark.parametrize('filters,expected_pruned', [
        ([('x', '=', 5)], 1),           # rg1 cannot hold 5
        ([('x', '=', 15)], 2),          # neither range holds 15
        ([('x', '<', 0)], 2),
        ([('x', '<', 1)], 1),
        ([('x', '<=', 0)], 1),
        ([('x', '>', 29)], 2),
        ([('x', '>=', 25)], 1),
        ([('x', '!=', 40)], 0),         # any value ≠ 40
        ([('x', 'in', (11, 15))], 2),
        ([('x', 'in', (5, 15))], 1),
        ([('x', 'not in', (5,))], 0),   # other values survive everywhere
        # OR of clauses: pruned only when EVERY clause proves empty
        ([[('x', '<', 0)], [('x', '>', 29)]], 2),
        ([[('x', '<', 0)], [('x', '=', 25)]], 1),
    ])
    def test_clause_interval_logic(self, two_rowgroup_url, filters,
                                   expected_pruned):
        pred = FiltersPredicate(filters)
        got = _read_ids(two_rowgroup_url, predicate=pred)
        assert got == _read_ids(two_rowgroup_url, plain=True,
                                predicate=pred)
        assert pushdown.planner_summary()['rowgroups_pruned'] == \
            expected_pruned

    def test_in_set_and_reduce_compositions(self, two_rowgroup_url):
        # in_set prunes by range; in_reduce(all) prunes through any
        # prunable child; in_reduce(any) needs every child prunable
        assert _read_ids(two_rowgroup_url,
                         predicate=in_set([15, 16], 'x')) == []
        assert pushdown.planner_summary()['rowgroups_pruned'] == 2
        T.reset_for_tests()
        pred = in_reduce([in_lambda(['x'], lambda v: True),
                          FiltersPredicate([('x', '>', 15)])], all)
        got = _read_ids(two_rowgroup_url, predicate=pred)
        assert got == list(range(20, 30))
        assert pushdown.planner_summary()['rowgroups_pruned'] == 1
        T.reset_for_tests()
        pred = in_reduce([FiltersPredicate([('x', '=', 15)]),
                          in_set([16], 'x')], any)
        assert _read_ids(two_rowgroup_url, predicate=pred) == []
        assert pushdown.planner_summary()['rowgroups_pruned'] == 2

    def test_arbitrary_predicates_decline(self, two_rowgroup_url):
        for pred in (in_lambda(['x'], lambda v: v['x'] == 25),
                     in_negate(FiltersPredicate([('x', '<', 15)]))):
            T.reset_for_tests()
            got = _read_ids(two_rowgroup_url, predicate=pred)
            assert got == _read_ids(two_rowgroup_url, plain=True,
                                    predicate=pred)
            summary = pushdown.planner_summary()
            assert summary['rowgroups_pruned'] == 0
            assert summary['declines'] == {'arbitrary-predicate': 1}

    def test_incomparable_types_keep(self, two_rowgroup_url):
        # str bound against int statistics: TypeError is conservative
        pred = FiltersPredicate([('x', 'in', ('zz',))])
        assert _read_ids(two_rowgroup_url, predicate=pred) == []
        assert pushdown.planner_summary()['rowgroups_pruned'] == 0

    def test_counters_and_report_section(self, two_rowgroup_url):
        pred = FiltersPredicate([('x', '<', 5)])
        got = _read_ids(two_rowgroup_url, predicate=pred)
        assert got == list(range(5))
        registry = T.get_registry()
        assert registry.counter_value(pushdown.ROWGROUPS_PRUNED) == 1
        assert registry.counter_value(pushdown.ROWS_PRUNED) == 10
        report = T.pipeline_report()
        section = report['pushdown']
        assert section['rowgroups_pruned'] == 1
        assert section['rows_pruned'] == 10
        assert section['prune_share'] == 0.5
        assert 'pushdown:' in T.format_pipeline_report(report)

    def test_no_section_without_predicates(self, two_rowgroup_url):
        _read_ids(two_rowgroup_url)
        assert 'pushdown' not in T.pipeline_report()

    def test_footer_memoization(self, two_rowgroup_url, monkeypatch):
        pred = FiltersPredicate([('x', '<', 5)])
        calls = []
        real = pushdown.StatsIndex._read_footer

        def counting(self, path):
            calls.append(path)
            return real(self, path)

        monkeypatch.setattr(pushdown.StatsIndex, '_read_footer', counting)
        _read_ids(two_rowgroup_url, predicate=pred)
        assert len(calls) == 1
        # the second reader's plan must hit the process-wide memo
        _read_ids(two_rowgroup_url, predicate=pred)
        assert len(calls) == 1


# ---------------------------------------------------------------------------
# Null safety
# ---------------------------------------------------------------------------


@pytest.fixture(scope='module')
def null_bearing_url(tmp_path_factory):
    """rg0: string x in ['a','c'] WITH a null; rg1: ['m','p'], no nulls.
    String column: nulls survive decode as None (a numeric column's
    nulls become NaN and can never match), so in_set(None) genuinely
    matches rows here."""
    import pyarrow as pa
    import pyarrow.parquet as pq
    path = str(tmp_path_factory.mktemp('nulls')) + '/ds'
    os.makedirs(path)
    t0 = pa.table({'x': pa.array(['a', None, 'c']),
                   'id': pa.array([0, 1, 2], type=pa.int64())})
    t1 = pa.table({'x': pa.array(['m', 'n', 'p']),
                   'id': pa.array([3, 4, 5], type=pa.int64())})
    writer = pq.ParquetWriter(os.path.join(path, 'part0.parquet'), t0.schema)
    writer.write_table(t0)
    writer.write_table(t1)
    writer.close()
    return 'file://' + path


class TestNullSafety:
    def test_in_set_none_not_wrongly_pruned(self, null_bearing_url):
        # REGRESSION (ISSUE 12 satellite): naive min/max logic prunes
        # BOTH row-groups ('zz' is outside both ranges) and silently
        # loses the null row that in_set(None) matches. The null-safe
        # prover must keep rg0 (null_count > 0) and prune only rg1.
        pred = in_set([None, 'zz'], 'x')
        got = _read_ids(null_bearing_url, predicate=pred)
        assert got == [1]
        assert got == _read_ids(null_bearing_url, plain=True,
                                predicate=pred)
        assert pushdown.planner_summary()['rowgroups_pruned'] == 1

    def test_negative_ops_keep_null_bearing_numeric_groups(
            self, tmp_path):
        # REGRESSION (review finding): numeric nulls decode to NaN, and
        # NaN DOES match '!='/'not in' at worker evaluation — so a
        # [5, null, 5] row-group must NOT be pruned against '!= 5' even
        # though its non-null min==max==5 (pre-fix, the pruned read lost
        # the NaN row the plain read delivers).
        import pyarrow as pa
        import pyarrow.parquet as pq
        path = str(tmp_path / 'numnulls')
        os.makedirs(path)
        t0 = pa.table({'x': pa.array([5, None, 5], type=pa.int64()),
                       'id': pa.array([0, 1, 2], type=pa.int64())})
        t1 = pa.table({'x': pa.array([7, 8, 9], type=pa.int64()),
                       'id': pa.array([3, 4, 5], type=pa.int64())})
        writer = pq.ParquetWriter(os.path.join(path, 'p0.parquet'),
                                  t0.schema)
        writer.write_table(t0)
        writer.write_table(t1)
        writer.close()
        url = 'file://' + path
        for filters in ([('x', '!=', 5)], [('x', 'not in', (5,))]):
            T.reset_for_tests()
            pred = FiltersPredicate(filters)
            got = _read_ids(url, predicate=pred)
            # pyarrow's filters= drops a null under '!=', where the
            # reader's decoded NaN matches: the reference is the
            # predicate's own do_include over the rows read
            assert got == _do_include_ids(url, pred, null=float('nan')), \
                filters
            assert got == [1, 3, 4, 5], (filters, got)
            # the null-bearing group was kept; the null-free one with
            # lo==hi==5 is still prunable against these ops
            assert pushdown.planner_summary()['rowgroups_pruned'] == 0
        # and WITHOUT nulls the negative ops do prune a lo==hi==value
        # group (the null guard must not blanket-disable them)
        T.reset_for_tests()
        path2 = str(tmp_path / 'nonulls')
        os.makedirs(path2)
        t0 = pa.table({'x': pa.array([5, 5, 5], type=pa.int64()),
                       'id': pa.array([0, 1, 2], type=pa.int64())})
        writer = pq.ParquetWriter(os.path.join(path2, 'p0.parquet'),
                                  t0.schema)
        writer.write_table(t0)
        writer.write_table(t1)
        writer.close()
        pred = FiltersPredicate([('x', '!=', 5)])
        got = _read_ids('file://' + path2, predicate=pred)
        assert got == [3, 4, 5]
        assert pushdown.planner_summary()['rowgroups_pruned'] == 1

    def test_negative_ops_keep_stored_nan_float_groups(self, tmp_path):
        # REGRESSION (review finding): a STORED float NaN is excluded
        # from pyarrow's min/max statistics WITHOUT counting as a null
        # (null_count stays 0), yet NaN != 5.0 is True at worker eval —
        # so float statistics can never prove a '!='/'not in' term
        # empty, even for a "null-free" lo==hi group.
        import pyarrow as pa
        import pyarrow.parquet as pq
        path = str(tmp_path / 'storednan')
        os.makedirs(path)
        t0 = pa.table({'x': pa.array([5.0, float('nan'), 5.0]),
                       'id': pa.array([0, 1, 2], type=pa.int64())})
        pq.write_table(t0, os.path.join(path, 'p0.parquet'))
        url = 'file://' + path
        for filters in ([('x', '!=', 5.0)], [('x', 'not in', (5.0,))]):
            T.reset_for_tests()
            pred = FiltersPredicate(filters)
            got = _read_ids(url, predicate=pred)
            # pyarrow's filters= prunes this row-group by its own NaN-blind
            # statistics: the reference is the predicate's do_include
            assert got == _do_include_ids(url, pred), filters
            assert got == [1], (filters, got)
            assert pushdown.planner_summary()['rowgroups_pruned'] == 0

    def test_dnf_terms_prune_through_nulls(self, null_bearing_url):
        # DNF filters: nulls never match ANY term, so min/max of the
        # non-null values alone decide — the null-bearing rg0 IS
        # prunable against a clause its range excludes
        pred = FiltersPredicate([('x', '>', 'f')])
        got = _read_ids(null_bearing_url, predicate=pred)
        assert got == [3, 4, 5]
        assert got == _read_ids(null_bearing_url, plain=True,
                                predicate=pred)
        assert pushdown.planner_summary()['rowgroups_pruned'] == 1


# ---------------------------------------------------------------------------
# Exact parity: pruned + late-materialized vs the plain read
# ---------------------------------------------------------------------------


def _read_rows(url, plain=False, predicate=None, **kwargs):
    """Sorted ``(id, image_png bytes, matrix bytes)``; ``plain=True``
    gives the plain read's, decoded by the codecs' own ``decode``."""
    if plain:
        return sorted(
            (row['id'], row['image_png'].tobytes(), row['matrix'].tobytes())
            for row in _plain_rows(url, predicate, schema=TestSchema,
                                   columns=['id', 'image_png', 'matrix']))
    with make_reader(url, shuffle_row_groups=False, predicate=predicate,
                     **kwargs) as reader:
        return sorted(
            (row.id, row.image_png.tobytes(), row.matrix.tobytes())
            for row in reader)


class TestExactParity:
    @pytest.mark.parametrize('pool', ['thread', 'dummy', 'process',
                                      'service'])
    def test_row_multiset_parity_across_pools(self, synthetic_dataset,
                                              pool):
        pred = FiltersPredicate([[('id', '<', 12)], [('id', '>=', 95)]])
        got = _read_ids(synthetic_dataset.url, pool=pool, predicate=pred,
                        workers_count=2)
        plain = _read_ids(synthetic_dataset.url, plain=True, predicate=pred)
        assert got == plain == list(range(12)) + list(range(95, 100))
        assert T.get_registry().counter_value(pushdown.ROWGROUPS_PRUNED) > 0

    def test_heavy_column_value_parity(self, synthetic_dataset):
        # pixels and ndarrays decoded late must be byte-identical to the
        # plain read's, decoded by the codecs
        pred = FiltersPredicate([('id', 'in', (3, 31, 47, 99))])
        got = _read_rows(synthetic_dataset.url, predicate=pred)
        plain = _read_rows(synthetic_dataset.url, plain=True,
                           predicate=pred)
        assert [g[0] for g in got] == [3, 31, 47, 99]
        assert got == plain
        registry = T.get_registry()
        assert registry.counter_value(
            'petastorm_tpu_late_materialized_rows_total') == 4
        assert registry.counter_value('petastorm_tpu_stage_calls_total',
                                      stage='late_materialize') > 0

    def test_sharding_parity(self, synthetic_dataset):
        pred = FiltersPredicate([('id', '<', 30)])
        per_shard = []
        for cur in (0, 1):
            got = _read_ids(synthetic_dataset.url, predicate=pred,
                            cur_shard=cur, shard_count=2)
            # pruning runs AFTER sharding, so each shard's row set is
            # its predicate-free self filtered — not just the union
            shard = _read_ids(synthetic_dataset.url, cur_shard=cur,
                              shard_count=2)
            assert got == [i for i in shard if i < 30]
            per_shard.append(got)
        assert sorted(per_shard[0] + per_shard[1]) == list(range(30))

    def test_prune_only_knob_keeps_late_materialization(
            self, synthetic_dataset):
        # a predicate the statistics cannot prune (arbitrary code): every
        # row-group reaches the worker, and only the survivors' heavy
        # columns materialize
        pred = in_lambda(['id'], lambda v: v['id'] in (3, 47))
        with make_batch_reader(synthetic_dataset.url, shuffle_row_groups=False,
                               predicate=pred) as reader:
            got = sorted(int(i) for b in reader for i in b.id)
        assert got == [3, 47]
        registry = T.get_registry()
        assert registry.counter_value(pushdown.ROWGROUPS_PRUNED) == 0
        assert registry.counter_value(
            'petastorm_tpu_late_materialized_rows_total') == 2

    @pytest.mark.parametrize('pool', ['thread', 'process'])
    def test_row_drop_partition_parity(self, synthetic_dataset, pool):
        # shuffle_row_drop_partitions under a predicate: each row-group
        # becomes k items; the late path decides survivors + drop BEFORE
        # the heavy read (an empty partition reads nothing), and the
        # delivered multiset must still match the plain read exactly
        pred = FiltersPredicate([('id', 'in', (3, 31, 47))])
        got = _read_ids(synthetic_dataset.url, pool=pool, predicate=pred,
                        shuffle_row_drop_partitions=3)
        assert got == _read_ids(synthetic_dataset.url, plain=True,
                                predicate=pred)
        assert got == [3, 31, 47]

    def test_fully_pruned_reader_delivers_empty(self, synthetic_dataset):
        pred = FiltersPredicate([('id', '>', 10 ** 6)])
        for epochs in (1, None):
            with make_batch_reader(synthetic_dataset.url, num_epochs=epochs,
                                   shuffle_row_groups=False,
                                   predicate=pred) as reader:
                assert list(reader) == []

    def test_multi_epoch_parity(self, synthetic_dataset):
        pred = FiltersPredicate([('id', '<', 7)])
        with make_batch_reader(synthetic_dataset.url, num_epochs=3,
                               shuffle_row_groups=False,
                               predicate=pred) as reader:
            got = sorted(int(i) for b in reader for i in b.id)
        assert got == sorted(list(range(7)) * 3)


class TestCheckpointAccounting:
    def test_completed_epoch_reads_complete(self, synthetic_dataset):
        # pruned items are completed-with-zero-rows: a fully consumed
        # epoch's state must say so (without this, resume would rewind
        # to re-read row-groups PROVEN empty, forever)
        pred = FiltersPredicate([('id', '<', 25)])
        with make_batch_reader(synthetic_dataset.url, num_epochs=1,
                               shuffle_row_groups=False,
                               predicate=pred) as reader:
            assert reader._pruned_items
            got = sorted(int(i) for b in reader for i in b.id)
            state = reader.state_dict()
        assert got == list(range(25))
        assert state['epoch'] == 1 and state['consumed_items'] == []

    @pytest.mark.parametrize('saver', ['fewer-items', 'more-items'])
    def test_resume_with_differing_saved_items(self, synthetic_dataset,
                                                saver):
        # REGRESSION (review finding): the filters= path prunes
        # PRE-shard, so a saver whose item list differs from the
        # restorer's (other filters; rewritten files whose statistics
        # changed) names DIFFERENT row-groups by the same raw consumed
        # indices. _localize_state translates through the saved per-index
        # global identities instead; no silent row loss whether the
        # saver had fewer items than the restorer or more.
        # an OR filter keeping a NON-contiguous piece set: the pruned
        # space's index->piece mapping then genuinely disagrees with the
        # unpruned one (a prefix-keeping filter would map identically
        # and hide the bug)
        filters = [[('id', '<', 10)], [('id', '>=', 30)]]
        pruned = set(range(10)) | set(range(30, 100))
        save_filters, restore_filters, expected = {
            'fewer-items': (filters, None, set(range(100))),
            'more-items': (None, filters, pruned)}[saver]

        def build(filters):
            return make_batch_reader(synthetic_dataset.url, num_epochs=1,
                                     shuffle_row_groups=False,
                                     filters=filters)
        with build(save_filters) as reader:
            it = iter(reader)
            seen = set(int(i) for i in next(it).id)
            seen |= set(int(i) for i in next(it).id)
            state = reader.state_dict()
        with build(restore_filters) as reader:
            saved_items = len(state['items_global'])
            own_items = len(reader._items_identity)
            assert (saved_items < own_items) == (saver == 'fewer-items')
            assert saved_items != own_items
            reader.load_state_dict(state)
            rest = set(int(i) for b in reader for i in b.id)
        assert expected <= seen | rest, sorted(expected - (seen | rest))
        # the consumed row-groups the restorer also holds were skipped
        assert not (rest & seen), sorted(rest & seen)

    def test_mid_epoch_resume_loses_no_rows(self, synthetic_dataset):
        pred = FiltersPredicate([('id', '<', 25)])
        with make_batch_reader(synthetic_dataset.url, num_epochs=1,
                               shuffle_row_groups=False,
                               predicate=pred) as reader:
            first = next(iter(reader))
            state = reader.state_dict()
        seen = set(int(i) for i in first.id)
        with make_batch_reader(synthetic_dataset.url, num_epochs=1,
                               shuffle_row_groups=False,
                               predicate=pred) as reader:
            reader.load_state_dict(state)
            rest = set(int(i) for b in reader for i in b.id)
        assert seen | rest == set(range(25))


# ---------------------------------------------------------------------------
# Degradation: footer faults prune nothing, never lose rows
# ---------------------------------------------------------------------------


class TestFooterFaultDegrade:
    def test_faulted_footer_degrades_to_unpruned(self, synthetic_dataset,
                                                 monkeypatch):
        from petastorm_tpu import faults
        monkeypatch.setenv('PETASTORM_TPU_FAULTS',
                           'io.read:error:1:match=#footer')
        faults.refresh_faults()
        try:
            assert faults.ARMED is not None
            pred = FiltersPredicate([('id', '<', 10)])
            got = _read_ids(synthetic_dataset.url, predicate=pred)
        finally:
            monkeypatch.delenv('PETASTORM_TPU_FAULTS')
            faults.refresh_faults()
        # the answer is RIGHT (degrade, not corrupt) and nothing pruned
        assert got == list(range(10))
        summary = pushdown.planner_summary()
        assert summary['rowgroups_pruned'] == 0
        assert summary['declines'].get('no-statistics', 0) > 0

    def test_statless_dataset_declines(self, tmp_path):
        import pyarrow as pa
        import pyarrow.parquet as pq
        path = str(tmp_path / 'nostats')
        os.makedirs(path)
        table = pa.table({'id': pa.array(range(20), type=pa.int64())})
        pq.write_table(table, os.path.join(path, 'p0.parquet'),
                       write_statistics=False)
        pred = FiltersPredicate([('id', '<', 5)])
        got = _read_ids('file://' + path, predicate=pred)
        assert got == list(range(5))
        summary = pushdown.planner_summary()
        assert summary['rowgroups_pruned'] == 0
        assert summary['declines'].get('no-statistics', 0) > 0


# ---------------------------------------------------------------------------
# Cacheability satellite: FiltersPredicate readers cache; arbitrary
# predicates stay uncached — counted, not invisible
# ---------------------------------------------------------------------------


class TestPredicateCache:
    def _arm(self, monkeypatch, tmp_path):
        monkeypatch.setenv('PETASTORM_TPU_DECODED_CACHE', '1')
        monkeypatch.setenv('PETASTORM_TPU_DECODED_CACHE_DIR',
                           str(tmp_path / 'decoded'))

    def test_filters_predicate_caches_under_knob(self, synthetic_dataset,
                                                 monkeypatch, tmp_path):
        from petastorm_tpu.materialized_cache import (
            DECODED_CACHE_HITS, DECODED_CACHE_MISSES,
        )
        self._arm(monkeypatch, tmp_path)
        pred = FiltersPredicate([('id', '<', 25)])
        first = _read_ids(synthetic_dataset.url, predicate=pred)
        registry = T.get_registry()
        assert first == list(range(25))
        assert registry.counter_value(DECODED_CACHE_MISSES) > 0
        assert _read_ids(synthetic_dataset.url, predicate=pred) == first
        assert registry.counter_value(DECODED_CACHE_HITS) > 0

    def test_distinct_filters_do_not_collide(self, synthetic_dataset,
                                             monkeypatch, tmp_path):
        self._arm(monkeypatch, tmp_path)
        a = _read_ids(synthetic_dataset.url,
                      predicate=FiltersPredicate([('id', '<', 10)]))
        b = _read_ids(synthetic_dataset.url,
                      predicate=FiltersPredicate([('id', '<', 5)]))
        assert a == list(range(10)) and b == list(range(5))

    def test_arbitrary_predicate_skip_is_counted(self, synthetic_dataset,
                                                 monkeypatch, tmp_path):
        from petastorm_tpu.materialized_cache import DECODED_CACHE_SKIPPED
        self._arm(monkeypatch, tmp_path)
        got = _read_ids(synthetic_dataset.url,
                        predicate=in_lambda(['id'],
                                            lambda v: v['id'] < 5))
        assert got == list(range(5))
        registry = T.get_registry()
        assert registry.counter_value(DECODED_CACHE_SKIPPED,
                                      reason='predicate') == 1

    def test_composed_predicate_downgrades_counted(self, synthetic_dataset,
                                                   monkeypatch, tmp_path):
        # filters= AND predicate= compose to in_reduce: no stable cache
        # identity — under the implicit knob the reader degrades to
        # uncached (counted), it must NOT raise
        from petastorm_tpu.materialized_cache import DECODED_CACHE_SKIPPED
        self._arm(monkeypatch, tmp_path)
        got = _read_ids(synthetic_dataset.url, filters=[('id', '<', 50)],
                        predicate=in_lambda(['id'],
                                            lambda v: v['id'] % 2 == 0))
        assert got == [i for i in range(50) if i % 2 == 0]
        assert T.get_registry().counter_value(DECODED_CACHE_SKIPPED,
                                              reason='predicate') == 1

    def test_explicit_cache_with_filters_predicate_allowed(
            self, synthetic_dataset, tmp_path):
        from petastorm_tpu.materialized_cache import DECODED_CACHE_HITS
        pred = FiltersPredicate([('id', '<', 10)])
        kwargs = dict(cache_type='decoded',
                      cache_location=str(tmp_path / 'explicit'),
                      predicate=pred)
        first = _read_ids(synthetic_dataset.url, **kwargs)
        assert first == list(range(10))
        assert _read_ids(synthetic_dataset.url, **kwargs) == first
        assert T.get_registry().counter_value(DECODED_CACHE_HITS) > 0

    def test_explicit_cache_with_arbitrary_predicate_raises(
            self, synthetic_dataset, tmp_path):
        with pytest.raises(RuntimeError, match='cache'):
            make_batch_reader(synthetic_dataset.url, cache_type='decoded',
                              cache_location=str(tmp_path / 'x'),
                              predicate=in_lambda(['id'],
                                                  lambda v: True))


# ---------------------------------------------------------------------------
# Late materialization internals
# ---------------------------------------------------------------------------


class TestLateMaterialization:
    def test_predicate_columns_not_decoded_twice(self, synthetic_dataset):
        # projection reuse: with id both predicate and output, the heavy
        # read must exclude it (io spans still happen for heavy cols;
        # the reused column arrives by slicing, not re-decode)
        pred = FiltersPredicate([('id', '<', 12)])
        with make_batch_reader(synthetic_dataset.url, shuffle_row_groups=False,
                               predicate=pred,
                               schema_fields=['^id$']) as reader:
            got = sorted(int(i) for b in reader for i in b.id)
        assert got == list(range(12))
        registry = T.get_registry()
        # id-only projection: nothing heavy left, so the late stage (and
        # its counter) must NOT fire at all
        assert registry.counter_value(
            'petastorm_tpu_late_materialized_rows_total') == 0
        assert registry.counter_value('petastorm_tpu_stage_calls_total',
                                      stage='late_materialize') == 0

    def test_deferred_encoded_column_ships_survivors_only(
            self, synthetic_dataset):
        from petastorm_tpu.fused import EncodedImageColumn
        pred = FiltersPredicate([('id', 'in', (3, 7, 47))])
        with make_batch_reader(synthetic_dataset.url, defer_image_decode=True,
                               shuffle_row_groups=False,
                               predicate=pred) as reader:
            batches = []
            while True:
                try:
                    columns, _, _ = reader.next_batch_info()
                except StopIteration:
                    break
                batches.append(columns)
        encoded = [c['image_png'] for c in batches]
        assert all(isinstance(e, EncodedImageColumn) for e in encoded)
        assert sorted(len(e) for e in encoded) == [1, 2]
        # decoded survivors equal the plain read's pixels
        plain = {row[0]: row[1] for row in _read_rows(
            synthetic_dataset.url, plain=True, predicate=pred)}
        for columns in batches:
            pixels = columns['image_png'].materialize()
            for k, rid in enumerate(int(i) for i in columns['id']):
                assert pixels[k].tobytes() == plain[rid]


# ---------------------------------------------------------------------------
# Ventilator always_exclude unit coverage
# ---------------------------------------------------------------------------


class TestVentilatorAlwaysExclude:
    def _run(self, items, **kwargs):
        from petastorm_tpu.workers.ventilator import ConcurrentVentilator
        out = []
        vent = ConcurrentVentilator(lambda **item: out.append(item['i']),
                                    items, **kwargs)
        vent.start()
        while not vent.completed():
            vent.processed_item()
        vent.stop()
        return out, vent

    def test_excluded_every_epoch(self):
        items = [{'i': n} for n in range(4)]
        out, _ = self._run(items, iterations=2, always_exclude={1, 3})
        assert out == [0, 2, 0, 2]

    def test_all_excluded_completes_immediately(self):
        items = [{'i': n} for n in range(3)]
        for iterations in (1, None):
            out, vent = self._run(items, iterations=iterations,
                                  always_exclude={0, 1, 2})
            assert out == [] and vent.completed()

    def test_composes_with_exclude_once(self):
        from petastorm_tpu.workers.ventilator import ConcurrentVentilator
        out = []
        vent = ConcurrentVentilator(lambda **item: out.append(item['i']),
                                    [{'i': n} for n in range(4)],
                                    iterations=2, always_exclude={3})
        vent.exclude_from_next_epoch({0})
        vent.start()
        while not vent.completed():
            vent.processed_item()
        vent.stop()
        # epoch 0 drops 0 (once) and 3 (always); epoch 1 only 3
        assert out == [1, 2, 0, 1, 2]
