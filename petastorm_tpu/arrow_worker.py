"""The row-group decode worker: parquet → decoded numpy column batches.

This merges the reference's two worker flavors
(``petastorm/arrow_reader_worker.py`` and ``py_dict_reader_worker.py``) into a
single **column-major** worker, per the TPU-first design stance (SURVEY.md
§7.1): every row-group is processed as columns end-to-end; the row-at-a-time
``make_reader`` API is a thin slicing view applied at the consumer
(:mod:`petastorm_tpu.reader`), not a separate decode path.

Pipeline per ventilated item (cf. ``arrow_reader_worker.py:116-170``):
rowgroup read (predicate columns first, early-exit) → row mask → shuffle-row-
drop partition → vectorized codec decode of surviving rows → TransformSpec →
publish a :class:`ColumnBatch`.
"""

import hashlib
import logging
from collections import OrderedDict

import numpy as np
import pyarrow.parquet as pq

from petastorm_tpu import faults
# the wire-speed I/O plane (docs/telemetry.md "Readahead"): coalesced
# column-chunk prefetch serving _read_columns zero-copy; a miss IS the
# blocking read below, so parity never depends on it
from petastorm_tpu import readahead
from petastorm_tpu.cache import NullCache
from petastorm_tpu.codecs import CompressedImageCodec, decode_batch_with_nulls
from petastorm_tpu.fused import (
    EncodedImageColumn, alloc_column_slab, count_fallback,
)

#: the worker-side deferral gate, re-derived per worker but COUNTED once
#: per Reader: deferral is sound only when the worker adds nothing after
#: decode (a TransformSpec/NGram needs pixels there; a cache must store
#: finished batches, not deferred stubs)
def defer_config_ok(transform_spec, ngram, cache):
    return (transform_spec is None and ngram is None
            and (cache is None or isinstance(cache, NullCache)))
from petastorm_tpu.materialized_cache import (
    MaterializedRowGroupCache, dataset_file_fingerprint, decode_fingerprint,
)
# the planner half of the selective-read fast path (docs/telemetry.md
# "Query-shaped reads"); also the ONE owner of the
# late-materialized-rows counter name this worker increments
from petastorm_tpu import pushdown
from petastorm_tpu.telemetry import get_registry, metrics_disabled, span
from petastorm_tpu.workers.worker_base import WorkerBase

logger = logging.getLogger(__name__)

_ALL_ROWS = slice(None)

#: bound on the per-worker open-ParquetFile memo: many-file datasets must
#: not grow an unbounded open-file/handle map per worker — least-recently
#: used files are closed and transparently re-opened on the next touch.
#: The trade-off is explicit: an eviction costs a footer re-read/parse on
#: the next touch of that file (a remote round trip on object stores,
#: paid even on readahead hits — serve() needs the parsed metadata), so
#: the bound is sized to make eviction RARE under shuffled many-file
#: reads while keeping worst-case handle count (workers × this) well
#: under default ulimits
_PARQUET_FILE_CACHE_MAX = 64


def _binary_cell_views(arrow_col):
    """Zero-copy ``np.uint8`` views over a binary column's cells.

    Returns None when the column is not binary-typed (caller falls back to
    ``to_pylist``). Null cells become None. The views alias the arrow data
    buffer, which each view's numpy ``base`` (the ``pyarrow.Buffer``) keeps
    alive.
    """
    import pyarrow as pa
    chunks = (arrow_col.chunks if isinstance(arrow_col, pa.ChunkedArray)
              else [arrow_col])
    cells = []
    for chunk in chunks:
        if pa.types.is_large_binary(chunk.type):
            offsets_dtype = np.int64
        elif pa.types.is_binary(chunk.type):
            offsets_dtype = np.int32
        else:
            return None
        if chunk.null_count:
            cells.extend(
                np.frombuffer(v.as_buffer(), np.uint8) if v.is_valid else None
                for v in chunk)
            continue
        buffers = chunk.buffers()
        offsets = np.frombuffer(buffers[1], dtype=offsets_dtype,
                                count=chunk.offset + len(chunk) + 1)
        offsets = offsets[chunk.offset:]
        data = buffers[2]
        cells.extend(
            np.frombuffer(data, np.uint8, offsets[i + 1] - offsets[i],
                          offsets[i])
            for i in range(len(chunk)))
    return cells


def typed_partition_value(field, value):
    """Cast a hive-partition path string to the schema field's numpy dtype.

    Without this, predicates would compare typed data values against raw
    partition-directory strings (e.g. ``5 != '5'``) and silently match
    nothing.
    """
    if field is None or value is None:
        return value
    try:
        dtype = np.dtype(field.numpy_dtype)
    except TypeError:  # e.g. Decimal
        return value
    if dtype.kind in 'iuf':
        try:
            return dtype.type(value)
        except (TypeError, ValueError):
            return value
        except OverflowError as e:
            # only reachable with an EXPLICITLY declared dtype (inference
            # bounds-checks); silently returning the string would make
            # predicates mismatch quietly — fail loud and early instead
            raise ValueError(
                'Hive partition value %r of field %r does not fit its '
                'declared dtype %s' % (value, field.name, dtype)) from e
    if dtype.kind == 'b':
        return value in (True, 'true', 'True', '1', 1)
    return value


class ColumnBatch:
    """Decoded columns of (a filtered subset of) one row-group.

    ``item_index`` identifies the ventilated work item that produced the batch
    (set by the worker, used for exact checkpoint/resume accounting).
    """

    __slots__ = ('columns', 'length', 'item_index', 'epoch')

    def __init__(self, columns, length, item_index=None, epoch=None):
        self.columns = columns
        self.length = length
        self.item_index = item_index
        self.epoch = epoch

    def row(self, i):
        return {name: col[i] for name, col in self.columns.items()}


class RowGroupWorker(WorkerBase):
    """Args (dict): dataset_info, loaded_schema (view of stored fields to
    read+decode), schema (final output schema, after TransformSpec),
    stored_schema, transform_spec, cache, ngram, row_groups."""

    def __init__(self, worker_id, publish_func, args):
        super().__init__(worker_id, publish_func, args)
        self._dataset_info = args['dataset_info']
        self._schema = args['schema']
        self._loaded_schema = args['loaded_schema']
        self._stored_schema = args['stored_schema']
        self._transform_spec = args.get('transform_spec')
        self._cache = args.get('cache')
        self._ngram = args.get('ngram')
        self._row_groups = args['row_groups']
        # Fused-decode deferral (petastorm_tpu/fused.py): the consumer
        # (JaxLoader) asked for encoded image cells instead of decoded
        # pixels so the staging arena can decode straight into its slot
        # buffers. The config decline is COUNTED once at Reader
        # construction, not here — N pool workers re-deriving the same
        # gate must not inflate the fallbacks counter by the worker count.
        self._defer_decode = (bool(args.get('defer_image_decode'))
                              and defer_config_ok(self._transform_spec,
                                                  self._ngram, self._cache))
        self._parquet_files = OrderedDict()
        # per-process readahead manager (petastorm_tpu/readahead.py),
        # shared by every thread-pool worker in this process and
        # refcounted across them; None when the plane is off or the
        # reader shipped no plan (caching readers — a warm epoch must
        # not prefetch bytes it will never read)
        self._readahead = readahead.attach(args)
        # decoded-cache key identity, resolved lazily (per process, per
        # parquet file) — see _decoded_fingerprint
        self._decode_fp = None
        self._file_fps = {}

    # -- worker contract ----------------------------------------------------

    def process(self, piece_index, worker_predicate=None,
                shuffle_row_drop_partition=(0, 1), item_index=None, epoch=None):
        from petastorm_tpu.filters import FiltersPredicate
        piece = self._row_groups[piece_index]
        # Cache only content with a stable identity: arbitrary predicates
        # have none, and for the raw pickle cache neither do TransformSpec
        # callables. The MATERIALIZED cache fingerprints the transform
        # (code + closure + schema edits) and the codec/schema view into
        # its key, so it caches the post-transform batch — the whole
        # point of the decoded tier.
        decoded = isinstance(self._cache, MaterializedRowGroupCache)
        # TransformSpec(cacheable=False) marks a STOCHASTIC transform
        # (random augmentation): caching its output would silently replay
        # epoch 1's randomness forever, so those readers always decode.
        # Under the implicit fleet-knob upgrade the bar is higher still:
        # only transforms that DECLARED cacheable=True participate — the
        # operator's knob must not freeze an unmarked transform whose
        # determinism nobody ever vouched for.
        transform_ok = self._transform_spec is None or (
            decoded and self._spec_cacheable())
        if self._cache is not None and not isinstance(self._cache, NullCache) \
                and transform_ok \
                and (worker_predicate is None
                     or isinstance(worker_predicate, FiltersPredicate)):
            cache_key = self._cache_key(piece, worker_predicate,
                                        shuffle_row_drop_partition)
            if decoded:
                cache_key += ':d%s' % self._decoded_fingerprint(piece)
            batch = self._cache.get(
                cache_key,
                lambda: self._load_rowgroup(piece, worker_predicate,
                                            shuffle_row_drop_partition,
                                            item_index=item_index,
                                            epoch=epoch))
        else:
            batch = self._load_rowgroup(piece, worker_predicate,
                                        shuffle_row_drop_partition,
                                        item_index=item_index, epoch=epoch)
        if batch is not None:
            batch.item_index = item_index
            batch.epoch = epoch
        if batch is not None and batch.length > 0:
            if self._ngram is not None:
                windows = self._ngram.form_ngram(batch, self._schema)
                for i, window in enumerate(windows):
                    # Wrapped as a plain dict (picklable across the process
                    # pool); 'last' lets the consumer mark the whole work
                    # item consumed for checkpoint/resume accounting.
                    self.publish_func({'window': window,
                                       'item_index': item_index,
                                       'epoch': epoch,
                                       'last': i == len(windows) - 1})
            else:
                self.publish_func(batch)

    def shutdown(self):
        if self._readahead is not None:
            self._readahead = None
            readahead.release(self.args)
        for f in self._parquet_files.values():
            try:
                f.close()
            except Exception:  # noqa: BLE001 - best-effort close
                pass
        self._parquet_files = OrderedDict()

    # -- internals ----------------------------------------------------------

    def _cache_key(self, piece, worker_predicate, drop_partition):
        # Reader rejects cache + arbitrary predicates up front (no stable
        # content identity to key on). DNF filters ARE content-addressable —
        # plain tuples — so they participate in the key instead.
        from petastorm_tpu.filters import FiltersPredicate
        filter_part = ''
        if worker_predicate is not None:
            assert isinstance(worker_predicate, FiltersPredicate)
            filter_part = ':f%s' % hashlib.md5(
                repr(worker_predicate.clauses).encode('utf-8')).hexdigest()
        # The loaded column set is part of the content: readers with
        # different schema_fields sharing a cache dir must not serve each
        # other truncated batches.
        columns_hash = hashlib.md5(
            ','.join(sorted(self._needed_stored_fields()))
            .encode('utf-8')).hexdigest()[:12]
        url_hash = hashlib.md5(
            str(self._dataset_info.url).encode('utf-8')).hexdigest()
        return '%s:%s:rg%d:%s:c%s%s' % (url_hash,
                                        self._dataset_info.relpath(piece.path),
                                        piece.row_group, drop_partition,
                                        columns_hash, filter_part)

    def _spec_cacheable(self):
        """May the decoded cache store this TransformSpec's output?
        ``cacheable``: False → never; True → always; None (undeclared) →
        only when the reader explicitly asked for the decoded cache, not
        when the fleet knob upgraded it behind the job's back."""
        cacheable = getattr(self._transform_spec, 'cacheable', None)
        if cacheable is not None:
            return cacheable
        return not getattr(self._cache, 'implicit_upgrade', False)

    def _decoded_fingerprint(self, piece):
        """Decode-identity suffix of a materialized-cache key: what was
        decoded (schema view + codecs), what transformed it, and the
        parquet file's bytes identity — any change must miss (serving a
        stale decoded batch is silent corruption). Both halves are
        cached: the decode fingerprint once per worker, the file
        fingerprint once per parquet file."""
        if self._decode_fp is None:
            self._decode_fp = decode_fingerprint(self._loaded_schema,
                                                 self._transform_spec,
                                                 self._ngram)
        file_fp = self._file_fps.get(piece.path)
        if file_fp is None:
            file_fp = dataset_file_fingerprint(self._dataset_info,
                                               piece.path)
            self._file_fps[piece.path] = file_fp
        return '%s:%s' % (self._decode_fp, file_fp)

    def _parquet_file(self, path):
        pf = self._parquet_files.get(path)
        if pf is None:
            pf = pq.ParquetFile(self._dataset_info.open(path))
            self._parquet_files[path] = pf
            while len(self._parquet_files) > _PARQUET_FILE_CACHE_MAX:
                _, evicted = self._parquet_files.popitem(last=False)
                try:
                    evicted.close()
                except Exception:  # noqa: BLE001 - best-effort close
                    pass
        else:
            self._parquet_files.move_to_end(path)
        return pf

    def _needed_stored_fields(self):
        """Names of stored fields to read+decode (pre-transform view)."""
        return [f.name for f in self._loaded_schema
                if f.name in self._stored_schema.fields]

    def _load_rowgroup(self, piece, worker_predicate, drop_partition,
                       item_index=None, epoch=None):
        needed = self._needed_stored_fields()
        partition_keys = [k for k in piece.partition_values if k in needed]
        file_columns = [n for n in needed if n not in piece.partition_values]

        if self._readahead is not None:
            # advance the readahead clock BEFORE any read of this item.
            # The prefetchable set respects the late-materialization
            # two-phase split: under a predicate only the predicate
            # columns fetch ahead — survivors' heavy columns stay
            # on-demand (most row-groups never materialize them)
            prefetch = (sorted(worker_predicate.get_fields())
                        if worker_predicate is not None else file_columns)
            self._readahead.observe(item_index, epoch, prefetch)

        pf = self._parquet_file(piece.path)

        pred_columns = {}
        if worker_predicate is not None:
            keep, pred_columns = self._predicate_mask(pf, piece,
                                                      worker_predicate)
            if keep is not None and not keep.any():
                return None
        else:
            keep = None

        # Late materialization (docs/telemetry.md "Query-shaped reads"):
        # under a predicate, the two-phase read is the general shape —
        # the heavy non-predicate columns are read only HERE, after the
        # surviving-row mask proved non-empty, and the predicate columns
        # already decoded for the mask are reused instead of being read
        # and decoded a second time.
        late = keep is not None
        reuse = {n: pred_columns[n] for n in file_columns
                 if n in pred_columns}
        read_columns = [n for n in file_columns if n not in reuse]

        overlap = self._ngram.length - 1 if self._ngram is not None else 0
        if late:
            # survivors + row-drop partition decided BEFORE the heavy
            # read (the mask already knows the row count): a drop
            # partition whose survivors all landed elsewhere must not
            # pay the heavy-column I/O just to return None
            num_rows = len(keep)
            row_indices = self._apply_row_drop(np.flatnonzero(keep),
                                               drop_partition, overlap)
            if row_indices.size == 0:
                return None
            table = (self._read_columns(pf, piece, read_columns)
                     if read_columns else None)
        else:
            table = self._read_columns(pf, piece, read_columns)
            num_rows = table.num_rows
            row_indices = self._apply_row_drop(np.arange(num_rows),
                                               drop_partition, overlap)
            if row_indices.size == 0:
                return None

        select_all = row_indices.size == num_rows

        if faults.ARMED:
            # inside a decode span so an injected delay is charged to the
            # decode stage — the critical-path ground-truth drill depends
            # on the slowdown being attributable; unarmed runs never enter
            with span('decode'):
                faults.fault_hit('decode.rowgroup', key='%s#rg%d'
                                 % (piece.path, piece.row_group))
        columns = {}
        if read_columns:
            if late:
                # the late-materialization specialization of the `decode`
                # stage: only SURVIVING rows of the heavy columns decode
                with span('late_materialize'):
                    for name in read_columns:
                        columns[name] = self._decode_survivors(
                            name, table.column(name), row_indices,
                            select_all)
                if not metrics_disabled():
                    get_registry().counter(
                        pushdown.LATE_MATERIALIZED_ROWS).inc(
                            int(row_indices.size))
            else:
                with span('decode'):
                    for name in read_columns:
                        arrow_col = table.column(name)
                        selected = (arrow_col if select_all
                                    else arrow_col.take(row_indices))
                        columns[name] = self._decode_column(
                            name, selected, allow_defer=True)
        for name, decoded in reuse.items():
            # projection pushdown: the predicate phase decoded the full
            # column; serving survivors is a select, not a re-decode
            columns[name] = decoded if select_all else decoded[row_indices]
        return self._finish_batch(columns, piece, partition_keys,
                                  row_indices.size)

    def _read_columns(self, pf, piece, read_columns):
        """One row-group read under the ``io`` span and its faultpoint.
        Faultpoint key: one stable identity per row-group, so chaos
        specs can poison a specific one (match=) or rate-sample reads;
        '#' not ':' as the separator — ':' is the spec grammar's own
        field separator, so a match= value could never contain it.

        A readahead hit serves the same columns zero-copy from the
        prefetched coalesced ranges (pa.BufferReader-backed, zero
        storage I/O); a miss — or ``PETASTORM_TPU_READAHEAD=0``, the
        exact-parity oracle — is the blocking read below."""
        if faults.ARMED:
            faults.fault_hit('io.read', key='%s#rg%d'
                             % (piece.path, piece.row_group))
        with span('io'):
            if self._readahead is not None:
                table = self._readahead.serve(pf, piece.path,
                                              piece.row_group, read_columns)
                if table is not None:
                    return table
            return pf.read_row_group(piece.row_group, columns=read_columns)

    def _finish_batch(self, columns, piece, partition_keys, count):
        """Shared batch tail: fill partition-key columns from the hive
        path values, then run the TransformSpec."""
        for name in partition_keys:
            field = self._stored_schema.fields.get(name)
            value = self._typed_partition_value(field, piece.partition_values[name])
            dtype = np.dtype(field.numpy_dtype) if field is not None else np.dtype(object)
            columns[name] = np.full(count, value,
                                    dtype=dtype if dtype.kind in 'iufb' else object)

        batch = ColumnBatch(columns, count)
        if self._transform_spec is not None:
            with span('transform'):
                batch = self._apply_transform(batch)
        return batch

    def _predicate_mask(self, pf, piece, predicate):
        """Two-phase read: evaluate the predicate on its own columns first
        (reference: ``py_dict_reader_worker.py:188-236``).

        Returns ``(mask, decoded)`` where ``decoded`` maps each predicate
        FILE column to its decoded full-row-group array — the
        late-materialization path reuses these for output columns so a
        predicate column is read and decoded exactly once per row-group.
        """
        pred_fields = sorted(predicate.get_fields())
        missing = [f for f in pred_fields
                   if f not in self._stored_schema.fields
                   and f not in piece.partition_values]
        if missing:
            raise ValueError('Predicate references unknown fields: %s' % missing)
        file_fields = [f for f in pred_fields if f not in piece.partition_values]
        with span('io'):
            # phase-1 of the two-phase split: exactly the columns the
            # readahead plane prefetches under a predicate
            pred_table = None
            if self._readahead is not None:
                pred_table = self._readahead.serve(pf, piece.path,
                                                   piece.row_group,
                                                   file_fields)
            if pred_table is None:
                pred_table = pf.read_row_group(piece.row_group,
                                               columns=file_fields)
        with span('decode'):
            decoded = {name: self._decode_column(name,
                                                 pred_table.column(name))
                       for name in file_fields}
        n = pred_table.num_rows
        for name in pred_fields:
            if name in piece.partition_values:
                field = self._stored_schema.fields.get(name)
                value = self._typed_partition_value(field, piece.partition_values[name])
                decoded[name] = np.full(n, value, dtype=object)
        reusable = {name: decoded[name] for name in file_fields}
        with span('filter'):
            mask = predicate.do_include_batch(
                {f: decoded[f] for f in pred_fields})
            if mask is not None:
                mask = np.asarray(mask, dtype=bool)
                if mask.shape != (n,):
                    raise ValueError(
                        'Predicate %s.do_include_batch returned mask of '
                        'shape %s for %d rows'
                        % (type(predicate).__name__, mask.shape, n))
                return mask, reusable
            # fallback: per-row loop for predicates without a columnar form
            # (e.g. in_lambda), matching the reference's evaluation exactly
            mask = np.empty(n, dtype=bool)
            for i in range(n):
                mask[i] = predicate.do_include(
                    {f: decoded[f][i] for f in pred_fields})
        return mask, reusable

    @staticmethod
    def _typed_partition_value(field, value):
        """Hive partition values are stored as path strings; cast them to the
        schema's dtype so predicates and output columns see typed values."""
        return typed_partition_value(field, value)

    @staticmethod
    def _apply_row_drop(row_indices, drop_partition, overlap=0):
        """Keep 1/k of the rows (contiguous split ``j`` of ``k``), improving
        shuffle decorrelation (reference: ``_read_with_shuffle_row_drop``).

        With an NGram, each partition borrows the first ``overlap``
        (= ngram length - 1) rows of the next partition so windows spanning
        the split boundary are not lost (``py_dict_reader_worker.py:266-271``).
        """
        j, k = drop_partition
        if k <= 1:
            return row_indices
        parts = np.array_split(row_indices, k)
        selected = parts[j]
        if overlap and j + 1 < k:
            borrow = np.concatenate(parts[j + 1:])[:overlap]
            selected = np.concatenate([selected, borrow])
        return selected

    def _decode_survivors(self, name, arrow_col, row_indices, select_all):
        """Decode ONLY the surviving rows of a heavy column — the
        late-materialization path. Image columns compact the survivor
        indices over zero-copy cell views of the FULL arrow column (no
        ``take()`` copy of the encoded bytes) and feed the compacted
        cells to the batched decode — or ship them still-encoded when
        the consumer deferred decode (``EncodedImageColumn`` carries
        only survivor cells, so ``decode_fused`` in the staging arena
        decodes survivors straight into slot rows). Other codecs fall
        back to ``take()`` + the classic decode."""
        if select_all:
            return self._decode_column(name, arrow_col, allow_defer=True)
        field = (self._loaded_schema.fields.get(name)
                 or self._stored_schema.fields.get(name))
        if field is not None and isinstance(field.codec, CompressedImageCodec):
            cells = _binary_cell_views(arrow_col)
            if cells is not None:
                survivors = [cells[i] for i in row_indices]
                return self._image_column(field, survivors, allow_defer=True)
        return self._decode_column(name, arrow_col.take(row_indices),
                                   allow_defer=True)

    def _decode_column(self, name, arrow_col, allow_defer=False):
        """Arrow column → decoded numpy values (vectorized where possible).

        Collation semantics follow ``arrow_reader_worker.py:38-80``: scalars
        to typed numpy arrays, strings to unicode arrays, codec'd binary cells
        through the codec's batched decode; outputs with uniform shapes are
        stacked into ``(n,) + shape`` ndarrays, ragged outputs stay object
        arrays.

        Row-group-granularity image dispatch: fixed-shape, null-free image
        columns decode in ONE vectorized call per row-group into a
        page-aligned column slab (``decode_batch(out=)``, the fused-decode
        destination API) — and, when the consumer deferred decode
        (``allow_defer`` + the reader's ``defer_image_decode``), skip
        decoding here entirely and publish an
        :class:`~petastorm_tpu.fused.EncodedImageColumn` for the staging
        arena to decode straight into its slot buffers. Predicate-column
        decode (``allow_defer=False``) always yields pixels — predicates
        compare values.
        """
        field = self._loaded_schema.fields.get(name) or self._stored_schema.fields.get(name)
        if field is not None and field.codec is not None:
            if isinstance(field.codec, CompressedImageCodec):
                # image cells go to cv2 untouched: zero-copy views over the
                # arrow data buffer instead of a per-cell bytes copy
                cells = _binary_cell_views(arrow_col)
                if cells is not None:
                    return self._image_column(field, cells, allow_defer)
            return self._stack(decode_batch_with_nulls(
                field, arrow_col.to_pylist()))
        return self._collate_plain(field, arrow_col, arrow_col.to_pylist())

    def _image_column(self, field, cells, allow_defer):
        """One image column of one row-group: defer (fused), decode dense
        into a page-aligned slab, or fall back to the per-cell path."""
        shape = field.shape
        dense_ok = (shape and not any(d is None for d in shape)
                    and not any(c is None for c in cells))
        if dense_ok:
            try:
                dtype = np.dtype(field.numpy_dtype)
            except TypeError:
                dense_ok = False
        if self._defer_decode and allow_defer:
            if dense_ok and dtype.kind in 'iuf':
                return EncodedImageColumn(field, cells)
            count_fallback('column-shape')
        if dense_ok:
            try:
                return decode_batch_with_nulls(
                    field, cells,
                    out=alloc_column_slab((len(cells),) + tuple(shape),
                                          dtype))
            except Exception:  # noqa: BLE001 - slab path is an accelerator
                logger.debug('Dense slab image decode failed; falling back '
                             'to the per-cell path', exc_info=True)
        return self._stack(decode_batch_with_nulls(field, cells))

    def _collate_plain(self, field, arrow_col, values):
        """Codec-less columns (plain parquet / make_batch_reader path)."""
        if field is not None and field.shape:
            # list<primitive> column → per-row ndarrays
            dtype = field.numpy_dtype
            arrays = [None if v is None else np.asarray(v, dtype=dtype) for v in values]
            return self._stack(arrays)
        try:
            out = arrow_col.combine_chunks().to_numpy(zero_copy_only=False)
        except Exception:  # noqa: BLE001 - fall back for exotic arrow types
            out = np.asarray(values, dtype=object)
        if (out.dtype == object and field is not None
                and field.numpy_dtype in (np.str_, np.bytes_)
                and not any(v is None for v in values)):
            # String columns collate to unicode/bytes arrays, matching the
            # reference (``arrow_reader_worker.py:64-65``).
            out = out.astype(field.numpy_dtype)
        return out

    @staticmethod
    def _stack(items):
        """Stack per-row values: uniform ndarray shapes → one (n,)+shape array;
        anything ragged/None-bearing → 1-d object array. A pre-stacked
        contiguous batch (from the native decode path) passes through."""
        if isinstance(items, np.ndarray) and items.dtype.kind not in 'OU':
            return items
        if not len(items):
            return np.empty(0, dtype=object)
        first = items[0]
        if isinstance(first, np.ndarray) and first.dtype.kind not in 'OU':
            shape = first.shape
            if all(isinstance(x, np.ndarray) and x.shape == shape for x in items):
                return np.stack(items)
        if isinstance(first, (int, float, bool, np.generic)) and \
                all(x is not None and not isinstance(x, np.ndarray) for x in items):
            return np.asarray(items)
        out = np.empty(len(items), dtype=object)
        for i, x in enumerate(items):
            out[i] = x
        return out

    def _apply_transform(self, batch):
        """Run the TransformSpec on a pandas view of the whole row-group
        (reference: ``arrow_reader_worker.py:146-152``)."""
        import pandas as pd
        spec = self._transform_spec
        frame = pd.DataFrame({name: list(col) for name, col in batch.columns.items()})
        if spec.func is not None:
            frame = spec.func(frame)
        for name in spec.removed_fields:
            if name in frame.columns:
                frame = frame.drop(columns=[name])
        if spec.selected_fields is not None:
            frame = frame[[c for c in spec.selected_fields]]
        columns = {name: self._stack(list(frame[name])) for name in frame.columns}
        return ColumnBatch(columns, len(frame))
