"""Reader: the read-path front end (``make_reader`` / ``make_batch_reader``).

Re-design of ``petastorm/reader.py`` for TPU hosts. Same contract — open a
(materialized or plain) Parquet dataset, enumerate row-groups, filter them by
predicate/selector/shard, ventilate them to a decode pool, iterate results —
with these deliberate differences:

* Column-major core: every pool result is a decoded :class:`ColumnBatch`;
  ``make_reader`` row iteration is a view over it (SURVEY.md §7.1).
* Sharding defaults come from ``jax.process_index()/process_count()`` when a
  distributed JAX runtime is initialized (:mod:`petastorm_tpu.parallel.sharding`)
  instead of manual ``cur_shard``/Horovod env checks.
* Checkpointable iteration state (``state_dict``/``load_state_dict``) — the
  reference can only restart epochs from scratch (SURVEY.md §5.4).
"""

import logging
import time
import warnings

from petastorm_tpu.arrow_worker import RowGroupWorker, defer_config_ok
from petastorm_tpu.telemetry import note_consumer_wait, span, tracing
from petastorm_tpu.telemetry.spans import timed
from petastorm_tpu.cache import LocalDiskCache, NullCache
from petastorm_tpu.errors import MetadataError, NoDataAvailableError
from petastorm_tpu.etl.dataset_metadata import (
    ParquetDatasetInfo, infer_or_load_unischema, load_row_groups,
)
from petastorm_tpu.transform import transform_schema
from petastorm_tpu.workers import EmptyResultError
from petastorm_tpu.workers.dummy_pool import DummyPool
from petastorm_tpu.workers.thread_pool import ThreadPool
from petastorm_tpu.workers.ventilator import ConcurrentVentilator

logger = logging.getLogger(__name__)

# Extra row-groups ventilated beyond worker count: bounds host memory while
# keeping workers busy (reference: ``reader.py:44-46``).
_VENTILATE_EXTRA_ROWGROUPS = 2

# Stall-note floor for reader pulls, higher than the generic
# STALL_NOTE_FLOOR_S: a pool's get_results includes per-result work even
# when a result was already queued (process/service pools deserialize the
# payload inside the call — easily >1ms for image batches), and billing
# that as starvation would steadily inflate producer-bound evidence in a
# pipeline that is actually keeping up. Genuine starvation blocks in
# poll-interval (≥50ms) chunks, far above this floor.
_PULL_NOTE_FLOOR_S = 0.01


def make_reader(dataset_url, schema_fields=None, reader_pool_type='thread',
                workers_count=None, results_queue_size=50, shuffle_row_groups=True,
                shuffle_row_drop_partitions=1, predicate=None,
                rowgroup_selector=None, num_epochs=1, cur_shard=None,
                shard_count=None, seed=0, cache_type='null', cache_location=None,
                cache_size_limit=None, cache_row_size_estimate=None,
                transform_spec=None, ngram=None, filters=None,
                storage_options=None, filesystem=None, poison_policy=None):
    """Reader over a petastorm_tpu/petastorm materialized dataset, iterating
    rows as namedtuples with all codecs decoded.

    Parity: ``petastorm/reader.py:61-196``. Use :func:`make_batch_reader` for
    plain Parquet stores or column-batch output.

    :param poison_policy: service pools only (docs/service.md, "Failure
        semantics") — what a quarantined (retry-budget-exhausted)
        row-group does to this reader: ``'raise'`` (default) surfaces
        the poison; ``'skip'`` reads past it, with the loss recorded on
        the pool's ``poisoned_items`` and the dispatcher's ``/health``.

    :param filters: pyarrow-style DNF filters (``[(col, op, value), ...]`` or
        an OR-list of such AND-lists). Row-groups that provably cannot match
        (hive partition values + parquet min/max statistics) are skipped
        without any I/O; surviving rows are filtered exactly on the workers.
    :param cache_type: ``'null'`` (default; upgraded to ``'decoded'``
        when ``PETASTORM_TPU_DECODED_CACHE=1``), ``'local-disk'`` (raw
        pickled reads, pre-transform), or ``'decoded'`` — the
        materialized decoded-row-group cache: finished post-transform
        column batches in Arrow IPC files, zero-copy mmap'd back on hit,
        shared across processes/jobs via one directory
        (``cache_location`` or ``PETASTORM_TPU_DECODED_CACHE_DIR``);
        ``cache_size_limit`` bounds the disk tier
        (default ``PETASTORM_TPU_DECODED_CACHE_DISK_MB``).
    :param filesystem: an already-constructed fsspec filesystem (e.g. a
        pre-authenticated gcsfs/s3fs instance) used instead of resolving
        one from the URL scheme; mutually exclusive with
        ``storage_options`` (reference ``reader.py:61`` ``filesystem=``).
    """
    info = ParquetDatasetInfo(dataset_url, storage_options,
                              filesystem=filesystem)
    try:
        from petastorm_tpu.etl.dataset_metadata import get_schema
        get_schema(info)
    except MetadataError:
        warnings.warn('Dataset at %s is missing petastorm metadata; the schema '
                      'will be inferred. Consider make_batch_reader for plain '
                      'Parquet stores' % dataset_url)

    return Reader(info, schema_fields=schema_fields,
                  reader_pool_type=reader_pool_type, workers_count=workers_count,
                  results_queue_size=results_queue_size,
                  shuffle_row_groups=shuffle_row_groups,
                  shuffle_row_drop_partitions=shuffle_row_drop_partitions,
                  predicate=predicate, rowgroup_selector=rowgroup_selector,
                  num_epochs=num_epochs, cur_shard=cur_shard,
                  shard_count=shard_count, seed=seed,
                  cache=_make_cache(cache_type, cache_location, cache_size_limit,
                                    cache_row_size_estimate,
                                    predicate=predicate),
                  transform_spec=transform_spec, ngram=ngram, filters=filters,
                  batched_output=False, poison_policy=poison_policy)


def make_batch_reader(dataset_url_or_urls, schema_fields=None,
                      reader_pool_type='thread', workers_count=None,
                      results_queue_size=50, shuffle_row_groups=True,
                      shuffle_row_drop_partitions=1, predicate=None,
                      rowgroup_selector=None, num_epochs=1, cur_shard=None,
                      shard_count=None, seed=0, cache_type='null',
                      cache_location=None, cache_size_limit=None,
                      cache_row_size_estimate=None, transform_spec=None,
                      filters=None, storage_options=None, filesystem=None,
                      defer_image_decode=False, poison_policy=None,
                      mixture_interleave=None, max_staleness_s=None):
    """Reader yielding whole row-groups as namedtuples of column arrays.

    Works on any Parquet store, petastorm metadata or not
    (parity: ``petastorm/reader.py:198-328``). ``filters`` and
    ``filesystem`` as in :func:`make_reader`.

    :param defer_image_decode: the fused-decode hand-shake
        (:mod:`petastorm_tpu.fused`): eligible image columns are
        published as still-encoded
        :class:`~petastorm_tpu.fused.EncodedImageColumn` stubs instead of
        decoded pixels, for a consumer (the JAX loader's staging arena)
        that decodes them straight into its destination buffers. Plain
        batch consumers should leave this off — namedtuple batches would
        carry encoded stubs.
    :param mixture_interleave: set by the mixture engine
        (:mod:`petastorm_tpu.mixture`) when this reader serves one source
        of a weighted mixture: a dict with the source's exact interleave
        ``share``, annotated into the readahead plan so per-worker
        prefetch depth follows the mixing ratio.
    :param max_staleness_s: bounded-staleness opt-in for growing
        (append-mode) datasets: requires a committed manifest
        (:mod:`petastorm_tpu.write`) and resolves the file set from a
        manifest snapshot taken at open — so the reader sees every row
        committed before the open, and rows written seconds ago are
        picked up by simply reopening (or by
        :class:`petastorm_tpu.write.AppendFollower`, which tails
        continuously within this bound). Raises ``ValueError`` on a
        manifest-less dataset — there is no commit point to bound
        staleness against.
    """
    info = ParquetDatasetInfo(dataset_url_or_urls, storage_options,
                              filesystem=filesystem)
    if max_staleness_s is not None:
        from petastorm_tpu.write import manifest as write_manifest
        if isinstance(dataset_url_or_urls, (list, tuple)) or \
                write_manifest.load(info.fs, info.root_path) is None:
            raise ValueError(
                'max_staleness_s requires a single dataset URL with a '
                'committed manifest (written by petastorm_tpu.write); '
                '%r has none' % (dataset_url_or_urls,))
    return Reader(info, schema_fields=schema_fields,
                  reader_pool_type=reader_pool_type, workers_count=workers_count,
                  results_queue_size=results_queue_size,
                  shuffle_row_groups=shuffle_row_groups,
                  shuffle_row_drop_partitions=shuffle_row_drop_partitions,
                  predicate=predicate, rowgroup_selector=rowgroup_selector,
                  num_epochs=num_epochs, cur_shard=cur_shard,
                  shard_count=shard_count, seed=seed,
                  cache=_make_cache(cache_type, cache_location, cache_size_limit,
                                    cache_row_size_estimate,
                                    predicate=predicate),
                  transform_spec=transform_spec, ngram=None, filters=filters,
                  batched_output=True,
                  defer_image_decode=defer_image_decode,
                  poison_policy=poison_policy,
                  mixture_interleave=mixture_interleave)


def _make_cache(cache_type, location, size_limit, row_size_estimate,
                predicate=None):
    from petastorm_tpu.telemetry import knobs
    if cache_type in (None, 'null', 'none'):
        # operators can arm the decoded tier fleet-wide without touching
        # reader call sites: PETASTORM_TPU_DECODED_CACHE=1 upgrades the
        # default no-cache readers to the materialized cache. A
        # FiltersPredicate participates — its clause digest joins the
        # cache key (arrow_worker._cache_key), so filtered results are
        # served from the cache instead of silently bypassing it.
        # Readers with an ARBITRARY predicate stay uncached (no stable
        # identity to key on): the knob must never turn a
        # previously-working job into Reader's cache+predicate
        # RuntimeError — that check is for EXPLICIT cache requests — but
        # the skip is counted, never invisible
        # (petastorm_tpu_decoded_cache_skipped_total{reason=predicate}).
        if knobs.is_enabled('PETASTORM_TPU_DECODED_CACHE'):
            from petastorm_tpu.filters import FiltersPredicate
            if predicate is not None \
                    and not isinstance(predicate, FiltersPredicate):
                from petastorm_tpu.materialized_cache import count_cache_skip
                count_cache_skip('predicate')
                logger.info(
                    'PETASTORM_TPU_DECODED_CACHE=1: reader with an '
                    'arbitrary predicate stays uncached (no stable cache '
                    'identity); use DNF filters/FiltersPredicate for '
                    'cacheable selective reads')
                return NullCache()
            cache_type = 'decoded'
            implicit = True
        else:
            return NullCache()
    else:
        implicit = False
    if cache_type == 'local-disk':
        if not location or not size_limit:
            raise ValueError("cache_type='local-disk' requires cache_location "
                             'and cache_size_limit')
        return LocalDiskCache(location, size_limit, row_size_estimate)
    if cache_type == 'decoded':
        # Materialized decoded-row-group cache (docs/telemetry.md):
        # decode-once-serve-many Arrow IPC tier, zero-copy mmap on hit.
        # Everything defaults from knobs so the fleet can share one
        # directory with no per-reader configuration.
        from petastorm_tpu.materialized_cache import (
            MaterializedRowGroupCache, default_cache_dir,
        )
        location = (location
                    or knobs.get_str('PETASTORM_TPU_DECODED_CACHE_DIR')
                    or default_cache_dir())
        disk_limit = size_limit or knobs.get_int(
            'PETASTORM_TPU_DECODED_CACHE_DISK_MB', 8192, floor=1) * 2 ** 20
        mem_limit = knobs.get_int(
            'PETASTORM_TPU_DECODED_CACHE_MEM_MB', 256, floor=0) * 2 ** 20
        # implicit (knob-upgraded) caches are conservative about
        # TransformSpecs whose determinism was never declared — see
        # MaterializedRowGroupCache.implicit_upgrade
        return MaterializedRowGroupCache(location, disk_limit, mem_limit,
                                         implicit_upgrade=implicit)
    raise ValueError('Unknown cache_type %r' % cache_type)


def _make_pool(reader_pool_type, workers_count, results_queue_size,
               poison_policy=None):
    import os
    if poison_policy not in (None, 'raise', 'skip'):
        raise ValueError("poison_policy must be 'raise' or 'skip'; got %r"
                         % (poison_policy,))
    if not isinstance(reader_pool_type, str):
        # A pre-built pool instance (any object honoring the pool contract):
        # lets callers configure endpoints/timeouts a string cannot carry,
        # e.g. Reader(..., reader_pool_type=ServicePool(endpoint=...)).
        pool = reader_pool_type
        missing = [m for m in ('start', 'ventilate', 'get_results', 'stop',
                               'join', 'workers_count', 'diagnostics')
                   if not hasattr(pool, m)]
        if missing:
            raise ValueError('reader_pool_type instance %r lacks pool '
                             'contract member(s) %s' % (pool, missing))
        if poison_policy is not None:
            if not hasattr(pool, 'poison_policy'):
                # silently dropping the policy would surprise exactly
                # when it matters (a poison arriving) — same fail-loud
                # stance as the local-pool check below
                raise ValueError(
                    'poison_policy given but pool instance %r has no '
                    'poison_policy support' % (pool,))
            pool.poison_policy = poison_policy
        return pool
    if poison_policy is not None and reader_pool_type != 'service':
        # local pools have no quarantine machinery: a worker error is
        # in-process and raises directly — fail loud, not silently no-op
        raise ValueError('poison_policy is only supported with '
                         "reader_pool_type='service'")
    if workers_count is None:
        # Auto-size to the host: decode is CPU-bound (cv2/numpy release the
        # GIL but still need a core each), so extra workers on a small box
        # only thrash. 4 matches the previous fixed default on TPU VMs.
        workers_count = max(1, min(4, os.cpu_count() or 1))
    if reader_pool_type == 'thread':
        return ThreadPool(workers_count, results_queue_size)
    if reader_pool_type == 'process':
        from petastorm_tpu.workers.process_pool import ProcessPool
        return ProcessPool(workers_count, results_queue_size)
    if reader_pool_type == 'service':
        # Disaggregated decode over tcp:// (docs/service.md). With the env
        # var set, the dispatcher binds there and an externally-started
        # worker-server fleet registers with it; without it, a localhost
        # fleet of workers_count servers is spawned (same shape as
        # 'process', but through the full network stack).
        from petastorm_tpu.service import ServicePool
        from petastorm_tpu.telemetry import knobs
        daemon = knobs.get_str('PETASTORM_TPU_SERVICE_DAEMON') or None
        if daemon:
            # STANDING service (docs/service.md, "Standing service"):
            # register this reader as one job with the long-lived daemon
            # at the given endpoint — many concurrent readers share its
            # supervised fleet; no dispatcher runs in this process.
            from petastorm_tpu.service.daemon import DaemonClientPool
            return DaemonClientPool(daemon,
                                    results_queue_size=results_queue_size,
                                    poison_policy=poison_policy or 'raise')
        endpoint = knobs.get_str('PETASTORM_TPU_SERVICE_DISPATCHER') or None
        if endpoint:
            # workers_count deliberately does NOT feed expected_workers: it
            # sizes LOCAL decode parallelism, while the external fleet size
            # is the operator's (default: start as soon as one worker
            # registers; more join live — docs/env_knobs.md).
            expected = knobs.get_str('PETASTORM_TPU_SERVICE_WORKERS') or None
            return ServicePool(endpoint=endpoint,
                               expected_workers=int(expected) if expected
                               else None,
                               results_queue_size=results_queue_size,
                               poison_policy=poison_policy or 'raise')
        return ServicePool(spawn_local_workers=workers_count,
                           results_queue_size=results_queue_size,
                           poison_policy=poison_policy or 'raise')
    if reader_pool_type == 'dummy':
        return DummyPool()
    raise ValueError("reader_pool_type must be one of 'thread', 'process', "
                     "'service', 'dummy' (or a pool instance); got %r"
                     % reader_pool_type)


class Reader:
    """Iterator over dataset rows (or column batches).

    The 5-step construction mirrors ``petastorm/reader.py:384-391``:
    1. resolve dataset + schema, 2. normalize the requested schema view,
    3. enumerate + filter row-groups, 4. build the ventilator, 5. start the
    worker pool.
    """

    def __init__(self, dataset_info, schema_fields=None, reader_pool_type='thread',
                 workers_count=None, results_queue_size=50, shuffle_row_groups=True,
                 shuffle_row_drop_partitions=1, predicate=None,
                 rowgroup_selector=None, num_epochs=1, cur_shard=None,
                 shard_count=None, seed=0, cache=None, transform_spec=None,
                 ngram=None, filters=None, batched_output=True,
                 defer_image_decode=False, poison_policy=None,
                 mixture_interleave=None):
        self.dataset_info = dataset_info
        self.batched_output = batched_output and ngram is None
        self.ngram = ngram

        if ngram is not None and not ngram.timestamp_overlap and \
                shuffle_row_drop_partitions > 1:
            raise NotImplementedError('Using timestamp deduplication with '
                                      'shuffle_row_drop_partitions is not supported')

        from petastorm_tpu.filters import FiltersPredicate
        self._filter_clauses = None
        self._filters_born = None
        if filters:
            filters_predicate = FiltersPredicate(filters)
            self._filter_clauses = filters_predicate.clauses
            if predicate is not None:
                from petastorm_tpu.predicates import in_reduce
                predicate = in_reduce([predicate, filters_predicate], all)
            else:
                # pure-filters predicate: the pre-shard prune below
                # already proved everything statistics can prove, so the
                # post-shard planner run is skipped for exactly this
                # object (a composed predicate may still prune more)
                predicate = filters_predicate
                self._filters_born = filters_predicate

        if predicate is not None and cache is not None and \
                not isinstance(cache, NullCache) and \
                not isinstance(predicate, FiltersPredicate):
            # A cached row-group must carry its predicate's identity in
            # the key; only DNF filters / FiltersPredicate have one (a
            # stable clause digest, see arrow_worker._cache_key) — those
            # cache. Anything else (in_lambda, in_set, a composed
            # in_reduce) cannot: an EXPLICIT cache request fails loud
            # (reference forbids the combination too,
            # ``reader.py:416-418``); the knob-armed implicit upgrade
            # degrades to uncached with the skip counted — the fleet
            # knob must never break a running job.
            if getattr(cache, 'implicit_upgrade', False):
                from petastorm_tpu.materialized_cache import count_cache_skip
                count_cache_skip('predicate')
                logger.info('PETASTORM_TPU_DECODED_CACHE=1: composed '
                            'predicate has no stable cache identity; '
                            'reading uncached')
                cache = NullCache()
            else:
                raise RuntimeError('Local cache is not supported together '
                                   'with predicates')

        # (1) schema
        self.stored_schema = infer_or_load_unischema(dataset_info)

        # (2) requested view (pre-transform), then transform edit
        if ngram is not None:
            ngram.resolve_regex_field_names(self.stored_schema)
            fields = ngram.get_field_names_at_all_timesteps()
            self.loaded_schema = (self.stored_schema.create_schema_view(fields)
                                  if fields else self.stored_schema)
        elif schema_fields is not None:
            self.loaded_schema = self.stored_schema.create_schema_view(schema_fields)
            if schema_fields and not len(self.loaded_schema):
                # all patterns missed: reading zero columns is never what
                # the user meant (reference raises the same way,
                # ``py_dict_reader_worker``'s EmptyResultError path)
                raise ValueError(
                    'No fields matching the criteria %r in schema %s'
                    % (schema_fields, list(self.stored_schema.fields)))
        else:
            self.loaded_schema = self.stored_schema
        if transform_spec is not None:
            self.schema = transform_schema(self.loaded_schema, transform_spec)
        else:
            self.schema = self.loaded_schema

        # (3) row-groups: enumerate, then predicate-pushdown/selector/shard
        all_pieces = load_row_groups(dataset_info)
        self._row_groups = all_pieces
        piece_indices = list(range(len(all_pieces)))
        filters_emptied = False
        if self._filter_clauses is not None:
            from petastorm_tpu.filters import prune_row_group_indices
            piece_indices = prune_row_group_indices(
                dataset_info, all_pieces, piece_indices, self._filter_clauses,
                stored_schema=self.stored_schema)
            filters_emptied = not piece_indices
        piece_indices, worker_predicate = self._apply_predicate_pushdown(
            piece_indices, predicate)
        piece_indices = self._apply_selector(piece_indices, rowgroup_selector)
        piece_indices = self._apply_sharding(piece_indices, cur_shard, shard_count)
        if not piece_indices:
            detail = 'check shard/predicate/selector configuration'
            if filters_emptied:
                from petastorm_tpu.filters import describe_clauses
                detail = 'filters %s matched no row-groups' % describe_clauses(
                    self._filter_clauses)
            raise NoDataAvailableError(
                'No row-groups left to read for this reader (dataset %s): %s'
                % (dataset_info.url, detail))
        self._piece_indices = piece_indices

        # (4) ventilator items
        items = []
        for idx in piece_indices:
            for drop in range(shuffle_row_drop_partitions):
                items.append({'piece_index': idx,
                              'worker_predicate': worker_predicate,
                              'shuffle_row_drop_partition':
                                  (drop, shuffle_row_drop_partitions),
                              'item_index': len(items)})

        # (4b) plan-time statistics pruning (petastorm_tpu/pushdown.py,
        # docs/telemetry.md "Query-shaped reads"): row-groups PROVABLY
        # empty against the predicate never reach the pool. Pruning runs
        # AFTER sharding and keeps every item in the list, so shard
        # assignment, item indices and checkpoint identities are
        # bit-identical to a reader without the predicate; the pruned items are simply never ventilated and the epoch
        # accounting below treats them as completed-with-zero-rows.
        self._pruned_items = frozenset()
        self._pushdown_plan = None
        if worker_predicate is not None \
                and worker_predicate is not self._filters_born:
            from petastorm_tpu import pushdown
            with span('rowgroup_prune'):
                self._pushdown_plan = pushdown.plan_rowgroup_pruning(
                    dataset_info, all_pieces, piece_indices,
                    predicate=worker_predicate,
                    stored_schema=self.stored_schema)
            if self._pushdown_plan.pruned:
                pruned_pieces = set(self._pushdown_plan.pruned)
                self._pruned_items = frozenset(
                    it['item_index'] for it in items
                    if it['piece_index'] in pruned_pieces)

        self._pool = _make_pool(reader_pool_type, workers_count,
                                results_queue_size,
                                poison_policy=poison_policy)
        self._num_epochs = num_epochs
        # The bound is a callable so pools whose fleet grows at runtime
        # (service pool: worker servers can register with a RUNNING job)
        # get proportionally more row-groups in flight without a restart
        # — and so the staging autotuner can raise the in-flight extra
        # live (set_ventilate_extra).
        self._ventilate_extra = _VENTILATE_EXTRA_ROWGROUPS
        self._shuffle_row_groups = shuffle_row_groups
        self._resume_excluded = {}
        self._ventilator = ConcurrentVentilator(
            self._pool.ventilate, items, iterations=num_epochs,
            max_ventilation_queue_size=lambda: (
                self._pool.workers_count + self._ventilate_extra),
            randomize_item_order=shuffle_row_groups, random_seed=seed,
            pass_epoch=True, trace_shard=self.cur_shard,
            always_exclude=self._pruned_items)

        # (4c) readahead plan (petastorm_tpu/readahead.py, docs/telemetry.md
        # "Readahead"): a picklable description of the ventilator's
        # upcoming-item sequence — shard- and shuffle-aware, with the
        # statistics-pruned items excluded so they never fetch — that the
        # workers' per-process readahead manager mirrors arithmetically.
        # Caching readers ship no plan: a warm epoch never touches
        # storage, so prefetching its bytes would be pure waste (counted).
        from petastorm_tpu import readahead
        readahead_plan = None
        if cache is None or isinstance(cache, NullCache):
            readahead_plan = readahead.build_plan(
                items, all_pieces, randomize=shuffle_row_groups,
                seed=self._ventilator.state_dict()['seed'],
                iterations=num_epochs, exclude=self._pruned_items,
                workers=self._pool.workers_count,
                interleave=mixture_interleave)
        elif readahead.readahead_enabled():
            readahead.count_degrade('cache')

        # (5) start workers; ventilation begins lazily on first read so that
        # load_state_dict can reposition the cursor first.
        defer = defer_image_decode and self.batched_output
        if defer and not defer_config_ok(transform_spec, ngram, cache):
            # counted HERE, once per Reader — the N workers each re-derive
            # the same gate silently (docs/troubleshoot.md reads this)
            from petastorm_tpu.fused import count_fallback
            count_fallback('worker-config')
        self._pool.start(RowGroupWorker,
                         worker_args={
                             'dataset_info': dataset_info,
                             'schema': self.schema,
                             'loaded_schema': self.loaded_schema,
                             'stored_schema': self.stored_schema,
                             'transform_spec': transform_spec,
                             'cache': cache,
                             'ngram': ngram,
                             'row_groups': all_pieces,
                             # fused decode (petastorm_tpu/fused.py): only
                             # batched consumers can host encoded stubs
                             'defer_image_decode': defer,
                             # the workers resolve PETASTORM_TPU_READAHEAD
                             # in their OWN process (service fleets set it
                             # fleet-wide, like the pushdown knobs)
                             'readahead_plan': readahead_plan,
                         },
                         ventilator=self._ventilator, start_ventilator=False)

        self.last_row_consumed = False
        self._started = False
        self._stopped = False
        self._current_batch = None
        self._batch_cursor = 0
        # Per-epoch sets of fully-consumed item indices (for exact resume).
        self._consumed_by_epoch = {}
        self._num_items = len(items)
        # Shard-independent identity of each local item — (global piece
        # index, drop partition, drop partition COUNT). This is what makes
        # a checkpoint portable across a pod resize: consumed work can be
        # re-expressed globally and re-localized under a different
        # shard_count (elastic resume). The count is part of the identity
        # because (piece, drop) only names the same ROWS at the same k: a
        # restore under a different shuffle_row_drop_partitions must not
        # match (the old drop's rows are a different subset), making the
        # piece re-read in full — at-least-once, never silent loss.
        self._items_identity = [
            (it['piece_index'],) + tuple(it['shuffle_row_drop_partition'])
            for it in items]

        # live observability plane (docs/telemetry.md): with
        # PETASTORM_TPU_OBS_PORT set this process serves /metrics /report
        # /health /trace over HTTP; the reader contributes its pool
        # gauges + iteration state to /health. Unarmed: a shared no-op
        # handle, no thread, no socket.
        from petastorm_tpu.telemetry import obs_server
        self._obs_mount = obs_server.mount('reader', health=self._obs_health)

    # -- construction helpers ------------------------------------------------

    def _apply_predicate_pushdown(self, piece_indices, predicate):
        """Predicates referencing only partition keys filter whole row-groups;
        others go to the workers (reference: ``reader.py:577-608``)."""
        if predicate is None:
            return piece_indices, None
        pred_fields = predicate.get_fields()
        partition_keys = set(self.dataset_info.partition_keys)
        if pred_fields and pred_fields <= partition_keys:
            from petastorm_tpu.arrow_worker import typed_partition_value
            kept = [i for i in piece_indices
                    if predicate.do_include(
                        {k: typed_partition_value(
                            self.stored_schema.fields.get(k),
                            self._row_groups[i].partition_values.get(k))
                         for k in pred_fields})]
            return kept, None
        return piece_indices, predicate

    def _apply_selector(self, piece_indices, selector):
        if selector is None:
            return piece_indices
        from petastorm_tpu.etl.rowgroup_indexing import get_row_group_indexes
        index_dict = get_row_group_indexes(self.dataset_info)
        needed = selector.get_index_names()
        missing = [n for n in needed if n not in index_dict]
        if missing:
            raise ValueError('Dataset has no row-group index named %s' % missing)
        selected = selector.select_row_groups(index_dict)
        return [i for i in piece_indices if i in selected]

    def _apply_sharding(self, piece_indices, cur_shard, shard_count):
        """Modulo assignment of row-groups to data-parallel ranks.

        Defaults from the JAX distributed runtime when only one of the two
        args is provided (reference requires both, ``reader.py:537-554``).
        """
        from petastorm_tpu.parallel.sharding import default_shard_info
        cur_shard, shard_count = default_shard_info(cur_shard, shard_count)
        # observability parity with the reference Reader's exposed shard
        # attributes: the RESOLVED assignment (post JAX-process defaulting)
        self.cur_shard = cur_shard
        self.shard_count = shard_count
        if shard_count is None:
            return piece_indices
        if shard_count > len(piece_indices):
            raise NoDataAvailableError(
                'Number of row-groups in the dataset (%d) must be greater or '
                'equal to the number of requested shards (%d)'
                % (len(piece_indices), shard_count))
        return [i for n, i in enumerate(piece_indices) if n % shard_count == cur_shard]

    # -- iteration -----------------------------------------------------------

    def __iter__(self):
        return self

    def _ensure_started(self):
        if not self._started:
            self._ventilator.start()
            self._started = True

    def _pull_result(self):
        """One pool result under the ``queue_wait`` stage span, whose
        seconds leave out the pool's own work inside the call (the process
        pool's ``deserialize``, the dummy pool's inline decode); blocked
        time above the noise floor feeds the stall attributor as consumer
        wait (= producer-bound evidence). With tracing on, the wait is
        also stamped onto the ARRIVED item's trace (the context is
        re-derived from the result's item_index/epoch — sampling is
        deterministic, so no wire change on the result path) and the
        producer-bound auto-dump trigger is polled."""
        waiting = timed('queue_wait')
        result = None
        try:
            with waiting:
                result = self._pool.get_results()
            return result
        finally:
            waited = waiting.seconds
            if waited > _PULL_NOTE_FLOOR_S:
                note_consumer_wait(waited)
            if tracing.trace_enabled():
                self._note_trace_pull(result, waited)
                tracing.maybe_autodump()

    def _note_trace_pull(self, result, waited):
        item_index = getattr(result, 'item_index', None)
        epoch = getattr(result, 'epoch', None)
        if item_index is None and isinstance(result, dict):
            item_index = result.get('item_index')
            epoch = result.get('epoch')
        ctx = tracing.ctx_for(item_index, epoch, self.cur_shard)
        if ctx is not None:
            end = time.perf_counter_ns()
            tracing.record_complete('queue_wait', end - int(waited * 1e9),
                                    end, ctx, track='consumer')

    def __next__(self):
        if self._stopped:
            raise RuntimeError('Trying to read a sample from a stopped reader')
        self._ensure_started()
        if self.batched_output:
            columns, _, _ = self.next_batch_info()
            return self.schema.make_namedtuple(**columns)
        if self.ngram is not None:
            try:
                # Workers publish wrapped {timestep: dict} windows (picklable
                # across the process pool); namedtuple-ization happens here on
                # the consumer, as in the reference
                # (py_dict_reader_worker.py:91).
                wrapped = self._pull_result()
            except EmptyResultError:
                self.last_row_consumed = True
                raise StopIteration from None
            if wrapped['last'] and wrapped['epoch'] is not None:
                self._consumed_by_epoch.setdefault(
                    wrapped['epoch'], set()).add(wrapped['item_index'])
            return self.ngram.make_namedtuple(self.schema, wrapped['window'])
        # row-at-a-time view over column batches
        while self._current_batch is None or self._batch_cursor >= self._current_batch.length:
            if self._current_batch is not None:
                self._mark_consumed(self._current_batch)
            try:
                self._current_batch = self._pull_result()
                self._batch_cursor = 0
            except EmptyResultError:
                self.last_row_consumed = True
                self._current_batch = None
                raise StopIteration from None
        row = self._current_batch.row(self._batch_cursor)
        self._batch_cursor += 1
        return self.schema.make_namedtuple(**row)

    def _mark_consumed(self, batch):
        item_index = getattr(batch, 'item_index', None)
        if item_index is not None and batch.epoch is not None:
            self._consumed_by_epoch.setdefault(batch.epoch, set()).add(item_index)

    def next_batch_info(self):
        """``(columns_dict, item_index, epoch)`` for one row-group batch.

        The provenance-carrying flavor of ``__next__`` (batched readers
        only): consumers that buffer rows downstream — the JaxLoader's
        staging pipeline — need to know WHICH row-group each batch came
        from so their checkpoints mark a row-group consumed only once all
        its rows were actually delivered, not merely pulled into a buffer
        (see :meth:`resume_state_from`). Raises StopIteration at the end
        like ``__next__``.
        """
        if not self.batched_output:
            raise TypeError('next_batch_info requires a batched reader')
        if self._stopped:
            raise RuntimeError('Trying to read a sample from a stopped reader')
        self._ensure_started()
        try:
            batch = self._pull_result()
        except EmptyResultError:
            self.last_row_consumed = True
            raise StopIteration from None
        self._mark_consumed(batch)
        # same projection make_namedtuple applies on the __next__ path
        # (schema fields only) — otherwise transform side-products would
        # leak into downstream staging
        columns = {name: batch.columns[name] for name in self.schema.fields
                   if name in batch.columns}
        return columns, batch.item_index, batch.epoch

    def next(self):
        return self.__next__()

    # -- lifecycle -----------------------------------------------------------

    def reset(self):
        """Restart the epoch sweep. Only valid after full consumption
        (reference: ``reader.py:468-492``)."""
        if not self.last_row_consumed:
            raise NotImplementedError(
                'Resetting a reader while in the middle of iteration is not '
                'supported; consume all samples first')
        self._ventilator.reset()
        self.last_row_consumed = False
        self._current_batch = None
        self._batch_cursor = 0
        # The new sweep restarts epoch numbering from 0; stale consumption
        # records would otherwise corrupt state_dict()'s resume math.
        self._consumed_by_epoch = {}
        self._resume_excluded = {}

    def ventilation_order(self, epoch):
        """Item indices the ventilator will emit for ``epoch``, in order.

        The public face of the ventilator's arithmetic order (shared
        with the readahead mirror): the per-epoch permutation from
        :func:`petastorm_tpu.workers.ventilator.epoch_order` under the
        ventilator's LIVE seed, minus the statistics-pruned items and —
        for a restored reader's resume epoch — the items excluded as
        already consumed. Downstream resequencers (the mixture engine's
        ordered sources) use this to turn the pool's completion-order
        deliveries back into a deterministic stream.
        """
        from petastorm_tpu.workers.ventilator import epoch_order
        order = epoch_order(self._num_items,
                            self._ventilator.state_dict()['seed'],
                            epoch, self._shuffle_row_groups)
        skip = set(self._pruned_items)
        skip.update(self._resume_excluded.get(epoch, ()))
        return [int(i) for i in order if i not in skip]

    def _obs_health(self):
        """This reader's /health contribution: iteration state + the
        pool's liveness gauges (JSON-safe scalars only)."""
        health = {
            'started': self._started,
            'stopped': self._stopped,
            'last_row_consumed': self.last_row_consumed,
            'num_epochs': self._num_epochs,
            'row_groups': len(self._piece_indices),
            'cur_shard': self.cur_shard,
            'shard_count': self.shard_count,
            # plan-time pushdown (docs/telemetry.md "Query-shaped
            # reads"): items proven empty and skipped this run
            'pruned_items': len(self._pruned_items),
            # autotunable in-flight bound (docs/telemetry.md "Readahead")
            'ventilate_extra': self._ventilate_extra,
        }
        from petastorm_tpu import readahead
        health['readahead'] = readahead.health_snapshot()
        try:
            health.update(self._pool.diagnostics)
        except Exception:  # noqa: BLE001 - health must answer regardless
            pass
        return health

    def stop(self):
        self._obs_mount.close()
        self._pool.stop()
        self._stopped = True

    def join(self):
        self._pool.join()

    def cleanup(self):
        pass

    def exit(self):
        self.stop()
        self.join()

    def __enter__(self):
        return self

    def __exit__(self, exc_type, exc_val, exc_tb):
        self.stop()
        self.join()

    @property
    def num_epochs(self):
        """Requested epoch count (None = infinite)."""
        return self._num_epochs

    @property
    def ventilate_extra(self):
        """Row-groups kept in flight beyond the pool's worker count."""
        return self._ventilate_extra

    def set_ventilate_extra(self, extra):
        """Autotuner seam: adjust the ventilator's in-flight bound
        mid-run (the bound is a callable re-read on every wait cycle, so
        the new value is observed without waking anyone). Returns the
        applied value."""
        self._ventilate_extra = max(1, int(extra))
        return self._ventilate_extra

    @property
    def diagnostics(self):
        return self._pool.diagnostics

    def pipeline_report(self, wall_time_s=None):
        """Per-stage time breakdown + stall attribution for this process's
        pipeline (:func:`petastorm_tpu.telemetry.pipeline_report`) —
        worker-side stages (io/decode/filter/transform) are included for
        every pool flavor because the process/service pools merge worker
        metric deltas back over their result channels."""
        from petastorm_tpu.telemetry import pipeline_report
        return pipeline_report(wall_time_s=wall_time_s)

    def dump_trace(self, path):
        """Export the flight recorder's per-item trace as Chrome
        trace-event JSON (Perfetto-viewable; needs ``PETASTORM_TPU_TRACE=1``
        during the read — docs/telemetry.md). Worker-side events from
        every pool flavor are already merged here via the pools' delta
        channels. Returns the number of events written."""
        from petastorm_tpu.telemetry import dump_trace
        return dump_trace(path)

    # -- checkpointable iteration state --------------------------------------

    def state_dict(self):
        """Row-group-granular iteration state — a capability the reference
        lacks (SURVEY.md §5.4).

        Semantics: **at-least-once**. Resume restarts at the earliest epoch
        with unconsumed row-groups, skipping the ones already fully consumed
        in that epoch; row-groups in flight (or consumed in a *later* epoch
        due to pipelining across the epoch boundary) are re-read.
        """
        return self.resume_state_from(self._consumed_by_epoch)

    def resume_state_from(self, consumed_by_epoch):
        """A ``state_dict``-shaped resume point built from an EXTERNAL
        ``{epoch: {item_index, ...}}`` consumption record — used by
        downstream buffering consumers (JaxLoader) whose notion of
        "consumed" is delivery to the user, which lags this reader's."""
        vent_seed = self._ventilator.state_dict()['seed']
        # Statistics-pruned items (petastorm_tpu/pushdown.py) are
        # completed-with-zero-rows: they are never ventilated, so no
        # delivery can ever mark them consumed — without counting them
        # here, every epoch would read forever-incomplete and resume
        # would rewind to re-read row-groups PROVEN to deliver nothing.
        pruned = self._pruned_items

        def consumed_in(epoch):
            done = set(consumed_by_epoch.get(epoch, ()))
            return done | pruned if pruned else done

        epochs_seen = sorted(consumed_by_epoch)
        if not epochs_seen:
            resume_epoch, consumed = 0, []
        else:
            # Walk epochs from 0 (NOT just the epochs present in the
            # record): a delivery-order record can contain epoch 1 while
            # epoch 0 still has undelivered row-groups in flight — an
            # absent epoch is maximally incomplete, and skipping it would
            # lose its rows on resume.
            resume_epoch = None
            for e in range(epochs_seen[-1] + 1):
                if len(consumed_in(e)) < self._num_items:
                    resume_epoch = e
                    break
            if resume_epoch is None:
                # every seen epoch complete: resume into a FRESH epoch —
                # nothing consumed there yet (the new reader's own
                # planner re-derives its pruned set)
                resume_epoch, consumed = epochs_seen[-1] + 1, []
            else:
                consumed = sorted(consumed_in(resume_epoch))
        if self._num_epochs is None:
            iterations_remaining = None
        else:
            iterations_remaining = max(0, self._num_epochs - resume_epoch)
        return {
            'version': 1,
            'seed': vent_seed,
            'epoch': resume_epoch,
            'iterations_remaining': iterations_remaining,
            'consumed_items': consumed,
            # shard-independent identities: (global piece index, drop,
            # drop count) per LOCAL item, enabling cross-shard-count merge
            # (elastic resume after a pod resize —
            # see jax/checkpoint.merge_loader_states)
            'items_global': [list(ident) for ident in self._items_identity],
            'shard_count': self.shard_count,
            'cur_shard': self.cur_shard,
        }

    def _localize_state(self, state):
        """Normalize a possibly-rescaled state to LOCAL ``consumed_items``.

        A merged (elastic) state carries ``consumed_global`` — shard-
        independent ``(piece_index, drop, drop_count)`` identities of
        consumed items (the 3-tuple shape of ``_items_identity``) —
        instead of local indices. Identities belonging to other shards
        under THIS reader's assignment are simply absent from
        ``_items_identity`` and drop out, which is exactly right: each new
        shard skips the consumed subset of its own items.
        """
        if 'consumed_global' in state:
            consumed = {tuple(ident) for ident in state['consumed_global']}
            local = [i for i, ident in enumerate(self._items_identity)
                     if ident in consumed]
            state = dict(state)
            state['consumed_items'] = local
            return state
        saved = state.get('items_global')
        if saved is not None:
            saved = [tuple(ident) for ident in saved]
            if saved != self._items_identity:
                # Index-space drift: the SAVING reader's item list differs
                # from ours — rewritten files change the statistics the
                # filters= pre-shard prune reads. Local indices would then
                # silently name DIFFERENT row-groups (row loss), so
                # translate through the saver's per-index identities:
                # identities absent from our list drop (their row-groups
                # are not in this sweep), our extra items are simply
                # re-read (at-least-once; zero rows for provably-empty
                # groups). items_global is rewritten to OURS so a second
                # localization (consumption_record_for_resume) is a no-op.
                position = {ident: i for i, ident
                            in enumerate(self._items_identity)}
                local = sorted(
                    position[saved[i]] for i in state['consumed_items']
                    if i < len(saved) and saved[i] in position)
                state = dict(state)
                state['consumed_items'] = local
                state['items_global'] = [list(ident) for ident
                                         in self._items_identity]
        return state

    def load_state_dict(self, state):
        """Reposition the iteration before the first read. Accepts a
        per-shard state from ``state_dict`` or a merged elastic state
        (``consumed_global``) from
        :func:`petastorm_tpu.jax.checkpoint.merge_loader_states`."""
        if self._started:
            raise RuntimeError('load_state_dict must be called before iteration '
                               'starts')
        state = self._localize_state(state)
        self._ventilator.load_state_dict({
            'epoch': state['epoch'],
            'cursor': 0,
            'seed': state['seed'],
            'iterations_remaining': state['iterations_remaining'],
        })
        self._ventilator.exclude_from_next_epoch(state['consumed_items'])
        # ventilation_order must mirror the exclusion: the resume epoch's
        # already-consumed items never ventilate again
        self._resume_excluded = {
            int(state['epoch']): frozenset(state['consumed_items'])}
        # Seed the consumption record to match the restored position: a
        # LATER checkpoint must see epochs before the resume epoch as
        # complete and the resume epoch's pre-restore items as consumed —
        # without this, a checkpoint taken after a restore rewinds to
        # epoch 0 (those epochs would look "absent" to resume_state_from),
        # and the resume epoch could never read complete (the excluded
        # items are never re-delivered).
        self._consumed_by_epoch = self.consumption_record_for_resume(state)

    def consumption_record_for_resume(self, state):
        """``{epoch: {item_index}}`` as of the restored position in
        ``state``: every epoch before the resume epoch complete, the resume
        epoch holding its already-consumed items. Shared with the
        JaxLoader's delivery-accurate record, which must be seeded the same
        way on restore."""
        state = self._localize_state(state)
        record = {e: set(range(self._num_items))
                  for e in range(state['epoch'])}
        record[state['epoch']] = set(state['consumed_items'])
        return record
