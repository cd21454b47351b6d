"""Statistics-driven row-group pruning: the selective-read planner.

At production scale most traffic is selective — eval slices, per-user
shards, rejection-sampled RL batches — yet a predicate read used to
ventilate **every** row-group, decode it whole, and drop the rows after
the fact: full-scan price for an index-shaped question. This module is
the plan-time third of the selective-read fast path (ROADMAP
"Query-shaped reads"; the tabular-preprocessing study, PAPERS.md arxiv
2409.14912, locates the next order of magnitude for selective workloads
exactly here):

* **before ventilation** the planner reads each parquet file's footer —
  one footer read per *file*, memoized process-wide per file identity
  (size + mtime) so repeat readers over the same dataset pay zero
  footer I/O — and proves row-groups empty against the predicate from
  the per-row-group column statistics (min/max/null_count);
* proven-empty row-groups **never reach the worker pool**: the Reader
  treats their work items as completed-with-zero-rows (the ventilator
  skips them every epoch, checkpoint/resume accounting counts them
  consumed), so sharding, in-flight bounds and exactly-once delivery
  are untouched;
* everything uncertain is **kept**: a failed footer read, a column
  without statistics, an incomparable type, an arbitrary predicate — a
  wrong prune would silently lose rows, so the planner only ever prunes
  what the statistics *prove* empty. The exact-parity tests compare
  against a plain pyarrow read (``petastorm_tpu/test_util/reference.py``).

What the prover understands (everything else is `arbitrary-predicate`):

* :class:`~petastorm_tpu.filters.FiltersPredicate` — exact interval
  logic per DNF clause. Equality/range/``in`` terms prune on the
  non-null min/max alone (a null cell — None for object columns, NaN
  for numeric ones — can never compare true there); the negative terms
  ``!=``/``not in`` additionally require a null-free row-group, because
  numeric nulls decode to NaN and ``NaN != value`` IS true at worker
  evaluation.
* :class:`~petastorm_tpu.predicates.in_set` — interval logic over the
  value set, **null-safe**: ``in_set`` is a plain membership test, so
  ``None`` in the value set *does* match null rows and a row-group with
  ``null_count > 0`` (or an unknown null count) is then never pruned.
* :class:`~petastorm_tpu.predicates.in_reduce` — ``all``: pruned when
  any prunable child proves the row-group empty; ``any``: pruned only
  when every child is prunable and proves it empty.

The worker-side two-thirds live in
:meth:`petastorm_tpu.arrow_worker.RowGroupWorker._load_rowgroup`
(projection pushdown + late materialization, the ``late_materialize``
stage) and the decoded cache's predicate-digest keying. Planner
decisions surface as ``pipeline_report()['pushdown']`` and the
``petastorm_tpu_rowgroups_pruned_total`` / ``..._rows_pruned_total``
counters; the "My selective read is still full-scan-priced" runbook in
docs/troubleshoot.md reads the decline reasons recorded here.
"""

import logging
import threading
from collections import OrderedDict
from concurrent.futures import ThreadPoolExecutor

from petastorm_tpu import faults
from petastorm_tpu import filters as _filters
from petastorm_tpu.predicates import in_reduce, in_set
from petastorm_tpu.telemetry import get_registry, knobs, metrics_disabled

logger = logging.getLogger(__name__)

#: registry counters (docs/telemetry.md metric reference). Pruning
#: happens in the consumer process (Reader construction); the
#: late-materialization counter is incremented worker-side
#: (arrow_worker) and rides the pool delta channels like every metric.
ROWGROUPS_PRUNED = 'petastorm_tpu_rowgroups_pruned_total'
ROWS_PRUNED = 'petastorm_tpu_rows_pruned_total'
LATE_MATERIALIZED_ROWS = 'petastorm_tpu_late_materialized_rows_total'

#: decline reasons recorded in the planner summary
#: (``pipeline_report()['pushdown']['declines']``; see the
#: full-scan-priced runbook in docs/troubleshoot.md). Units differ by
#: reason: ``arbitrary-predicate`` and ``low-selectivity`` count planner
#: RUNS, ``no-statistics`` counts ROW-GROUPS kept for lack of usable
#: statistics (missing column stats or a failed footer read).
DECLINE_ARBITRARY = 'arbitrary-predicate'
DECLINE_NO_STATS = 'no-statistics'
DECLINE_LOW_SELECTIVITY = 'low-selectivity'

#: process-wide footer-stats memo: (dataset url, file path, size-mtime
#: fingerprint) -> per-row-group [(col stats, num_rows), ...]. Bounded
#: FIFO so long-lived multi-dataset processes cannot grow it without
#: limit; a rewritten file changes its fingerprint and misses.
_FOOTER_CACHE_MAX_FILES = 4096
_footer_cache_lock = threading.Lock()
_footer_cache = OrderedDict()

_summary_lock = threading.Lock()


def _fresh_summary():
    return {'planner_runs': 0, 'rowgroups_considered': 0,
            'rowgroups_pruned': 0, 'rows_pruned': 0, 'declines': {}}


_summary = _fresh_summary()


def planner_summary():
    """Consumer-local planner activity: runs, row-groups considered /
    pruned, and decline reasons — the ``pushdown`` report section's
    plan-time half (the registry counters are its fleet-merged half)."""
    with _summary_lock:
        out = dict(_summary)
        out['declines'] = dict(_summary['declines'])
        return out


def reset_for_tests():
    """Fresh planner summary + footer memo (test isolation only)."""
    global _summary
    with _summary_lock:
        _summary = _fresh_summary()
    with _footer_cache_lock:
        _footer_cache.clear()


def _note_run(considered, pruned=0, rows=0, declines=None):
    with _summary_lock:
        _summary['planner_runs'] += 1
        _summary['rowgroups_considered'] += considered
        _summary['rowgroups_pruned'] += pruned
        _summary['rows_pruned'] += rows
        for reason, count in (declines or {}).items():
            if count:
                _summary['declines'][reason] = \
                    _summary['declines'].get(reason, 0) + count


# -- footer statistics index -------------------------------------------------


class StatsIndex:
    """Per-file parquet footer statistics, fetched lazily and in
    parallel (``PETASTORM_TPU_PUSHDOWN_WORKERS`` threads), memoized
    process-wide by file identity. One footer read per *file*, never per
    row-group; a file whose footer fails to load yields None and every
    one of its row-groups is conservatively kept.

    Each memoized row-group entry also carries the exact **byte ranges**
    of its column chunks (``{root column: [(offset, length), ...]}``) —
    the readahead plane (:mod:`petastorm_tpu.readahead`) plans its
    coalesced prefetch reads from the same one-footer-read-per-file memo
    the pruning planner already pays for."""

    def __init__(self, dataset_info):
        self._info = dataset_info
        self._per_file = {}

    def prefetch(self, paths):
        todo = sorted(set(paths) - set(self._per_file))
        if not todo:
            return
        workers = knobs.get_int('PETASTORM_TPU_PUSHDOWN_WORKERS', 8, floor=1)
        with ThreadPoolExecutor(max_workers=min(workers, len(todo))) as ex:
            for path, stats in zip(todo, ex.map(self._load, todo)):
                self._per_file[path] = stats

    def get(self, path, row_group):
        """``(column stats dict, num_rows)`` for one row-group, or None
        when statistics are unavailable for its file."""
        stats = self._per_file.get(path)
        if stats is None or row_group >= len(stats):
            return None
        cols, num_rows, _ = stats[row_group]
        return cols, num_rows

    def get_ranges(self, path, row_group):
        """``{root column: [(byte offset, length), ...]}`` of one
        row-group's column chunks, or None when the footer was
        unreadable — the readahead plane's range planner."""
        stats = self._per_file.get(path)
        if stats is None or row_group >= len(stats):
            return None
        return stats[row_group][2]

    def _load(self, path):
        key = None
        fingerprint = self._fingerprint(path)
        if fingerprint is not None:
            key = (str(self._info.url), path, fingerprint)
            with _footer_cache_lock:
                if key in _footer_cache:
                    _footer_cache.move_to_end(key)
                    return _footer_cache[key]
        stats = self._read_footer(path)
        if stats is not None and key is not None:
            with _footer_cache_lock:
                _footer_cache[key] = stats
                while len(_footer_cache) > _FOOTER_CACHE_MAX_FILES:
                    _footer_cache.popitem(last=False)
        return stats

    def _fingerprint(self, path):
        """File identity for the memo — the decoded cache's size+mtime
        rule (:func:`~petastorm_tpu.materialized_cache.
        dataset_file_fingerprint`, the ONE owner of that logic), except
        its path-only ``'nostat'`` fallback becomes None here: rather
        than risking stale statistics, an unidentifiable file simply
        skips memoization."""
        from petastorm_tpu.materialized_cache import dataset_file_fingerprint
        fingerprint = dataset_file_fingerprint(self._info, path)
        return None if fingerprint == 'nostat' else fingerprint

    def _read_footer(self, path):
        import pyarrow.parquet as pq
        try:
            # same faultpoint as the worker's row-group read: a chaos
            # spec can fail footer reads (match=#footer) and the planner
            # must degrade to unpruned reads, never to a wrong answer
            if faults.ARMED:
                faults.fault_hit('io.read', key='%s#footer' % path)
            with self._info.fs.open(path, 'rb') as f:
                meta = pq.ParquetFile(f).metadata
        except Exception:  # noqa: BLE001 - degrade to unpruned, loudly
            logger.warning('pushdown: failed to read parquet footer of %s; '
                           'its row-groups will not be pruned', path,
                           exc_info=True)
            return None
        out = []
        for rg in range(meta.num_row_groups):
            row_group = meta.row_group(rg)
            cols = {}
            ranges = {}
            for ci in range(row_group.num_columns):
                col = row_group.column(ci)
                name = col.path_in_schema.split('.')[0]
                # chunk byte range for the readahead plane: the chunk
                # starts at its first page (the dictionary page when one
                # exists, else the first data page) and spans its total
                # compressed size
                starts = [offset for offset
                          in (col.dictionary_page_offset,
                              col.data_page_offset)
                          if offset is not None]
                if starts and col.total_compressed_size:
                    ranges.setdefault(name, []).append(
                        (int(min(starts)),
                         int(col.total_compressed_size)))
                st = col.statistics
                if st is None or not st.has_min_max:
                    continue
                null_count = (int(st.null_count) if st.has_null_count
                              else None)
                cols[name] = (st.min, st.max, null_count)
            out.append((cols, int(row_group.num_rows), ranges))
        return out


# -- the prover --------------------------------------------------------------


class _Ctx:
    """One row-group's evidence: hive partition values (exact) and
    footer column statistics (min/max over the NON-null values +
    null_count; None when the footer was unreadable). ``missing`` is set
    by any term that wanted statistics and found none — the
    ``no-statistics`` decline evidence."""

    __slots__ = ('partition_values', 'stats', 'missing', '_schema')

    def __init__(self, piece, stats, stored_schema):
        self.partition_values = piece.partition_values
        self.stats = stats
        self.missing = False
        self._schema = stored_schema

    def typed(self, col):
        from petastorm_tpu.arrow_worker import typed_partition_value
        field = (self._schema.fields.get(col)
                 if self._schema is not None else None)
        return typed_partition_value(field, self.partition_values.get(col))

    def column_stats(self, col):
        if self.stats is None:
            self.missing = True
            return None
        st = self.stats.get(col)
        if st is None:
            self.missing = True
        return st


def _may_have_nulls(null_count):
    return null_count is None or null_count > 0


def _negative_op_unprovable(lo, hi, null_count):
    """True when a ``!=``/``not in`` term cannot be proven empty from
    these statistics. Two NaN-shaped holes make the negative ops
    special: (a) a NULL cell decodes to NaN in numeric columns, and (b)
    a STORED float NaN is excluded from pyarrow's min/max statistics
    without counting as a null — and ``NaN != value`` / ``NaN not in
    values`` are TRUE at worker evaluation. So the negative ops demand a
    provably null-free row-group AND non-float statistics (float stats
    can never prove the absence of a stored NaN cell)."""
    return (_may_have_nulls(null_count)
            or isinstance(lo, float) or isinstance(hi, float))


def _term_provably_empty(term, ctx):
    """True when NO row of the row-group can satisfy one DNF term.

    Null handling is op-specific because null CELLS are not uniform at
    worker evaluation time: object/string columns decode nulls to None
    (which ``filters._eval_term`` rejects under every op), but NUMERIC
    columns decode nulls to NaN — and ``NaN != value`` / ``NaN not in
    values`` are TRUE in both the scalar and the vectorized worker
    paths. So the equality/range/``in`` ops (where a None or NaN cell
    can never compare true) prune on the non-null min/max alone, while
    the negative ops ``!=``/``not in`` additionally require a provably
    NaN-free row-group (:func:`_negative_op_unprovable`: null-free AND
    non-float statistics, since a stored float NaN is invisible to
    min/max without counting as a null) — without that guard a
    ``[5, null, 5]`` group would be pruned against ``!= 5`` while the
    oracle delivers its NaN row (silent row loss; regression-tested).
    Anything incomparable keeps the row-group.
    """
    col, op, value = term
    if col in ctx.partition_values:
        try:
            return not _filters._eval_term(op, ctx.typed(col), value)
        except TypeError:
            return False  # incomparable: the worker's exact eval decides
    st = ctx.column_stats(col)
    if st is None:
        return False
    lo, hi, null_count = st
    try:
        if op in ('=', '=='):
            return not bool(lo <= value <= hi)
        if op == '!=':
            return bool(lo == hi == value) \
                and not _negative_op_unprovable(lo, hi, null_count)
        if op == '<':
            return not bool(lo < value)
        if op == '>':
            return not bool(hi > value)
        if op == '<=':
            return not bool(lo <= value)
        if op == '>=':
            return not bool(hi >= value)
        if op == 'in':
            # None members skipped: a None VALUE matches neither a None
            # nor a NaN cell under `in` (equality compares false)
            return not any(v is not None and bool(lo <= v <= hi)
                           for v in value)
        if op == 'not in':
            return (bool(lo == hi) and lo in set(value)
                    and not _negative_op_unprovable(lo, hi, null_count))
    except TypeError:
        return False  # e.g. str filter against int statistics
    return False


def _compile_clauses(clauses):
    """Prover for DNF clauses: the row-group is empty iff EVERY
    OR-clause is empty, and an AND-clause is empty iff ANY of its terms
    provably matches nothing."""
    fields = {t[0] for clause in clauses for t in clause}

    def prove(ctx):
        return all(any(_term_provably_empty(t, ctx) for t in clause)
                   for clause in clauses)

    return prove, fields


def _compile_in_set(field, values):
    """Prover for :class:`~petastorm_tpu.predicates.in_set` — the
    null-safety satellite lives here: ``in_set`` is plain membership, so
    ``None`` in the value set MATCHES null rows, and a row-group whose
    column may hold nulls is then never prunable by min/max alone."""
    matches_null = any(v is None for v in values)

    def prove(ctx):
        if field in ctx.partition_values:
            try:
                return ctx.typed(field) not in values
            except TypeError:
                return False
        st = ctx.column_stats(field)
        if st is None:
            return False
        lo, hi, null_count = st
        if matches_null and _may_have_nulls(null_count):
            return False
        try:
            return not any(v is not None and bool(lo <= v <= hi)
                           for v in values)
        except TypeError:
            return False

    return prove, {field}


def _compile(predicate):
    """Predicate tree → ``(prove_empty(ctx), fields)`` or None when the
    tree holds no component the statistics prover understands."""
    if isinstance(predicate, _filters.FiltersPredicate):
        return _compile_clauses(predicate.clauses)
    if isinstance(predicate, in_set):
        return _compile_in_set(predicate.field, predicate.values)
    if isinstance(predicate, in_reduce):
        children = [_compile(p) for p in predicate.predicates]
        if predicate.reduce_func is all:
            # AND: empty when ANY prunable child proves it empty;
            # arbitrary children simply cannot contribute evidence
            usable = [c for c in children if c is not None]
            if not usable:
                return None

            def prove_all(ctx):
                return any(prove(ctx) for prove, _ in usable)

            return prove_all, set().union(*(f for _, f in usable))
        if predicate.reduce_func is any:
            # OR: empty only when EVERY child is prunable and empty
            if not children or any(c is None for c in children):
                return None

            def prove_any(ctx):
                return all(prove(ctx) for prove, _ in children)

            return prove_any, set().union(*(f for _, f in children))
    return None


# -- the planner -------------------------------------------------------------


class PushdownPlan:
    """One Reader construction's pruning decision: ``kept``/``pruned``
    are piece indices (``pruned`` PROVABLY deliver zero rows),
    ``rows_pruned`` the skipped row count from the footers, ``decline``
    the reason nothing could be pruned (None when pruning ran)."""

    __slots__ = ('kept', 'pruned', 'rows_pruned', 'considered',
                 'no_stats_rowgroups', 'decline')

    def __init__(self, kept, pruned, rows_pruned, considered,
                 no_stats_rowgroups, decline):
        self.kept = kept
        self.pruned = pruned
        self.rows_pruned = rows_pruned
        self.considered = considered
        self.no_stats_rowgroups = no_stats_rowgroups
        self.decline = decline


def plan_rowgroup_pruning(dataset_info, pieces, piece_indices,
                          predicate=None, clauses=None, stored_schema=None):
    """Prove row-groups empty against a predicate before any of them is
    ventilated. Pass either a predicate tree (``predicate=``) or
    already-normalized DNF ``clauses`` (the ``filters=`` kwarg path).
    Conservative everywhere: only PROVABLY empty row-groups land in
    ``plan.pruned``; callers treat them as completed-with-zero-rows.
    """
    piece_indices = list(piece_indices)
    considered = len(piece_indices)
    if clauses is not None:
        compiled = _compile_clauses(clauses)
    else:
        compiled = _compile(predicate)
    if compiled is None:
        _note_run(considered, declines={DECLINE_ARBITRARY: 1})
        return PushdownPlan(kept=piece_indices, pruned=[], rows_pruned=0,
                            considered=considered, no_stats_rowgroups=0,
                            decline=DECLINE_ARBITRARY)
    prove, fields = compiled

    index = StatsIndex(dataset_info)
    stat_paths = {pieces[i].path for i in piece_indices
                  if any(f not in pieces[i].partition_values
                         for f in fields)}
    index.prefetch(stat_paths)

    kept, pruned = [], []
    rows_pruned = 0
    no_stats = 0
    for i in piece_indices:
        piece = pieces[i]
        entry = index.get(piece.path, piece.row_group)
        cols, num_rows = entry if entry is not None else (None, 0)
        ctx = _Ctx(piece, cols, stored_schema)
        if prove(ctx):
            pruned.append(i)
            rows_pruned += num_rows
        else:
            kept.append(i)
            if ctx.missing:
                no_stats += 1

    declines = {}
    if no_stats:
        declines[DECLINE_NO_STATS] = no_stats
    if not pruned and not no_stats:
        # statistics were usable everywhere and still proved nothing
        # empty: the predicate matches every row-group's range — the
        # runbook's "low selectivity at row-group granularity" case
        declines[DECLINE_LOW_SELECTIVITY] = 1
    _note_run(considered, pruned=len(pruned), rows=rows_pruned,
              declines=declines)
    if pruned and not metrics_disabled():
        registry = get_registry()
        registry.counter(ROWGROUPS_PRUNED).inc(len(pruned))
        if rows_pruned:
            registry.counter(ROWS_PRUNED).inc(rows_pruned)
    if pruned:
        logger.debug('pushdown: pruned %d/%d row-group(s) (%d rows) '
                     'against the predicate', len(pruned), considered,
                     rows_pruned)
    return PushdownPlan(kept=kept, pruned=pruned, rows_pruned=rows_pruned,
                        considered=considered, no_stats_rowgroups=no_stats,
                        decline=None)


__all__ = ['PushdownPlan', 'StatsIndex', 'plan_rowgroup_pruning',
           'planner_summary', 'reset_for_tests']
