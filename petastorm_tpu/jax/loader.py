"""JAX device stage: Parquet row-groups → sharded ``jax.Array`` batches.

This is the layer the reference does not have (its consumers stop at numpy /
torch / tf tensors): decoded column batches are re-batched to a fixed size,
optionally shuffled, cast per a dtype policy, and staged into device HBM as
``jax.Array``s laid out for a ``jax.sharding.Mesh`` — with the host→device
transfer overlapped with consumption (double/triple buffering, bounded by
``prefetch``).

Design notes (SURVEY.md §7.1/§7.2 step 4):

* Fixed batch sizes + 'drop'/'pad' last-batch policies keep every step's
  shapes static, so the training step compiles once (XLA requirement).
* Sharding uses ``jax.make_array_from_process_local_data``: each host feeds
  only its own shard (the reader is already sharded by
  ``jax.process_index()``), and the resulting global array's batch axis is
  laid out over the mesh's data axes — collectives then ride ICI.
* All decode/shuffle/cast work happens on a background staging thread; the
  consumer thread only dequeues ready device arrays.
"""

import logging
import queue
import threading
import time

import numpy as np

from petastorm_tpu import fused
from petastorm_tpu.fused import EncodedImageColumn
from petastorm_tpu.jax import staging
from petastorm_tpu.telemetry import (
    STALL_NOTE_FLOOR_S, StallAttributor, note_consumer_wait,
    note_producer_wait, span, tracing,
)
from petastorm_tpu.telemetry.spans import timed

logger = logging.getLogger(__name__)

_SENTINEL_END = object()
_NO_ITEM = object()

#: name of the validity-mask column added under ``last_batch='pad'``
#: (one canonical definition, shared with the staging engine)
MASK_FIELD = staging.MASK_FIELD
#: suffix of the true-size companion column added per ``pad_ragged`` field
LEN_SUFFIX = '_len'
# hidden per-row provenance column riding through the staging buffers; maps
# each row back to the reader pull (row-group) it came from so checkpoints
# can be delivery-accurate. Added after the reader, stripped before device.
_PULL_FIELD = '__petastorm_tpu_pull__'


def make_jax_loader(dataset_url_or_urls, batch_size, mesh=None, data_axes=None,
                    fields=None, shuffle_rows=False,
                    shuffling_queue_capacity=None, min_after_retrieve=None,
                    extra_capacity=None, seed=0, last_batch='drop',
                    dtypes=None, prefetch=2, num_epochs=1,
                    inmemory_cache_all=False, pad_ragged=None,
                    bucket_boundaries=None,
                    reader_factory=None, mixture=None, **reader_kwargs):
    """Create a :class:`JaxLoader` over a Parquet dataset.

    :param batch_size: rows per emitted batch **per host**. With a mesh, must
        divide evenly over the mesh's data-axis shards.
    :param mesh: optional :class:`jax.sharding.Mesh`; batches become global
        ``jax.Array``s whose leading axis is sharded over ``data_axes``.
    :param data_axes: mesh axis name(s) to shard the batch axis over
        (default: all mesh axis names).
    :param fields: field name/regex list forwarded to the reader
        (column projection).
    :param shuffle_rows: decorrelate rows across row-groups with a
        :class:`~petastorm_tpu.buffers.BatchedRandomShufflingBuffer`.
    :param last_batch: ``'drop'`` (default: constant shapes), ``'pad'``
        (zero-pad + ``valid_mask`` bool column), or ``'short'`` (emit the
        ragged tail batch — breaks shape stability under jit).
    :param dtypes: optional ``{field: numpy dtype}`` cast applied on host
        before staging (e.g. ``{'image': jnp.bfloat16}``).
    :param prefetch: number of device batches staged ahead of the consumer.
    :param inmemory_cache_all: decode once, replay epochs from device
        memory (see :class:`InMemoryCachedLoader`); requires
        ``num_epochs=1`` — re-iterate for more epochs.
    :param pad_ragged: ``{field: size or (sizes...)}`` — densify
        variable-shape fields (``(None, ...)`` Unischema dims, the shape
        class the reference's batched reader simply rejects,
        ``arrow_reader_worker.py:176-178``) to STATIC shapes: each
        variable dim pads with zeros (or truncates) to the given size, and
        a companion ``<field>_len`` int32 column carries every row's TRUE
        size(s) — ``(B,)`` for one variable dim, ``(B, k)`` for ``k``. A
        truncated row's stored length exceeds the padded extent, so
        truncation stays detectable and ``arange(L) < len`` masks
        saturate correctly. Static shapes are the XLA-idiomatic answer to
        raggedness: the train step compiles once, and masks built from
        ``<field>_len`` replace dynamic shapes.
    :param bucket_boundaries: ``{field: [b1, b2, ...]}`` (one field) —
        length-bucketed batching, the XLA re-design of tf.data's
        ``bucket_by_sequence_length``: rows route to the smallest
        boundary ≥ their leading length, each bucket fills its own
        fixed-``batch_size`` batches, and the field pads to the BUCKET's
        bound (rows past the largest boundary truncate into it; the
        ``<field>_len`` column keeps true lengths). Emitted shapes are
        static per bucket, so jit compiles one step per bucket and
        padding waste drops from pad-to-max to pad-to-bucket. Composes
        with ``pad_ragged`` for OTHER fields. Memory/latency contract:
        when ``shuffle_rows`` is on, EACH bucket keeps its own shuffle
        buffer, so host memory scales as ``shuffling_queue_capacity ×
        len(boundaries)`` — divide the capacity you would use unbucketed
        by the boundary count to keep the same footprint. A batch emits
        only when ITS bucket fills, so rows routed to a rarely-hit bucket
        can be held until the END OF THE STREAM — the final epoch's
        exhaustion, when every bucket flushes (none are lost); with
        ``num_epochs=None`` the stream never ends and an unfilled
        bucket's rows are held indefinitely. Prefer boundaries that match
        the actual length distribution over a uniform grid.
    :param reader_factory: reader constructor (defaults to
        :func:`petastorm_tpu.reader.make_batch_reader`).
    :param mixture: a :class:`petastorm_tpu.mixture.MixtureSpec` — feed
        the loader a deterministic weighted multi-dataset mixture of
        packed token rows (``tokens`` / ``loss_mask`` / ``segment_ids``
        columns; the spec needs ``seq_len``) instead of one dataset
        (``dataset_url_or_urls`` must then be None; the sources carry
        their own URLs). ``reader_kwargs`` flow to every source's
        reader; with ``reader_pool_type='service'`` and a standing
        daemon configured, each source registers as its own
        QoS-weighted job on the shared fleet (docs/mixture.md).
    :param reader_kwargs: forwarded to the reader factory (predicates,
        sharding overrides, pool type, ...).

    .. warning:: **Multi-host epochs.** Row-group sharding can hand hosts
        unequal row counts, so per-host loaders may emit different numbers of
        batches per epoch. A host that exhausts its shard stops stepping
        while the others still issue collectives — a pod-wide hang. For
        multi-host training drive a FIXED number of steps per epoch (e.g.
        ``steps = global_rows // (batch_size * jax.process_count())``) over
        an infinite loader (``num_epochs=None``), the standard TPU-pod
        pattern — :meth:`JaxLoader.iter_steps` packages exactly that.

    A fully consumed loader may be iterated again: re-iteration resets the
    reader and replays the dataset, matching the torch loaders' ergonomics.
    Replays reshuffle at whatever levels shuffling is enabled —
    ``shuffle_row_groups`` (reader, on by default) and/or ``shuffle_rows``.
    """
    from petastorm_tpu.reader import make_batch_reader
    factory = reader_factory or make_batch_reader
    if mixture is not None:
        if dataset_url_or_urls is not None:
            raise ValueError('mixture= and dataset_url_or_urls are mutually '
                             'exclusive: the MixtureSpec sources carry their '
                             'own URLs')
        if reader_factory is not None:
            raise ValueError('mixture= builds its own source readers; give '
                             'per-source factories on the MixtureSource '
                             'entries instead of reader_factory=')
        if fields is not None:
            raise ValueError('mixture= emits fixed packed columns (tokens/'
                             'loss_mask/segment_ids); fields= does not apply')
        if inmemory_cache_all:
            raise ValueError('mixture= does not support inmemory_cache_all')
        from petastorm_tpu.mixture import MixtureBatchReader, MixtureStream
        stream = MixtureStream(mixture, num_epochs=num_epochs,
                               **reader_kwargs)
        reader = MixtureBatchReader(stream, rows_per_pull=batch_size)
        try:
            return JaxLoader(reader, batch_size, mesh=mesh,
                             data_axes=data_axes, shuffle_rows=shuffle_rows,
                             shuffling_queue_capacity=shuffling_queue_capacity,
                             min_after_retrieve=min_after_retrieve,
                             extra_capacity=extra_capacity, seed=seed,
                             last_batch=last_batch, dtypes=dtypes,
                             prefetch=prefetch, pad_ragged=pad_ragged,
                             bucket_boundaries=bucket_boundaries)
        except Exception:
            reader.stop()
            reader.join()
            raise
    if inmemory_cache_all and num_epochs not in (1, None):
        raise ValueError(
            'inmemory_cache_all caches exactly one epoch and replays it; '
            'pass num_epochs=1 (the default) and re-iterate the loader for '
            'more epochs (got num_epochs=%r)' % (num_epochs,))
    if reader_factory is None:
        # Fused decode hand-shake (petastorm_tpu/fused.py): ask the
        # worker for still-encoded image cells whenever this loader's
        # batch path can decode them straight into its staging buffers —
        # unbucketed batches, row-shuffled or not.
        # Other configurations (and per-field surprises like a dtype
        # recast) fall back via the loader's own materialization, so
        # requesting here is an optimization hint, never a correctness
        # bet. Custom reader factories are left untouched — their
        # signatures may not know the kwarg.
        reader_kwargs.setdefault('defer_image_decode',
                                 bucket_boundaries is None)
    reader = factory(dataset_url_or_urls, schema_fields=fields,
                     num_epochs=1 if inmemory_cache_all else num_epochs,
                     **reader_kwargs)
    try:
        loader = JaxLoader(reader, batch_size, mesh=mesh, data_axes=data_axes,
                           shuffle_rows=shuffle_rows,
                           shuffling_queue_capacity=shuffling_queue_capacity,
                           min_after_retrieve=min_after_retrieve,
                           extra_capacity=extra_capacity, seed=seed,
                           last_batch=last_batch, dtypes=dtypes,
                           prefetch=prefetch, pad_ragged=pad_ragged,
                           bucket_boundaries=bucket_boundaries)
    except Exception:
        reader.stop()
        reader.join()
        raise
    if inmemory_cache_all:
        return InMemoryCachedLoader(loader, seed=seed)
    return loader


class JaxLoader:
    """Iterator of ``{field: jax.Array}`` batches over a batched reader."""

    def __init__(self, reader, batch_size, mesh=None, data_axes=None,
                 shuffle_rows=False, shuffling_queue_capacity=None,
                 min_after_retrieve=None, extra_capacity=None, seed=0,
                 last_batch='drop', dtypes=None, prefetch=2,
                 pad_ragged=None, bucket_boundaries=None):
        if last_batch not in ('drop', 'pad', 'short'):
            raise ValueError("last_batch must be 'drop', 'pad' or 'short'; "
                             'got %r' % (last_batch,))
        self._pad_ragged = {
            name: (sizes,) if np.ndim(sizes) == 0 else tuple(sizes)
            for name, sizes in (pad_ragged or {}).items()}
        for name, sizes in self._pad_ragged.items():
            if not all(isinstance(s, (int, np.integer)) and s > 0
                       for s in sizes):
                raise ValueError('pad_ragged[%r] must be a positive int or '
                                 'tuple of positive ints; got %r'
                                 % (name, sizes))
        self._bucket_field = None
        self._bucket_bounds = None
        if bucket_boundaries:
            if len(bucket_boundaries) != 1:
                raise ValueError('bucket_boundaries supports exactly one '
                                 'field; got %s'
                                 % sorted(bucket_boundaries))
            ((name, bounds),) = bucket_boundaries.items()
            bounds = [int(b) for b in bounds]
            if not bounds or bounds != sorted(set(bounds)) or bounds[0] <= 0:
                raise ValueError('bucket_boundaries[%r] must be strictly '
                                 'ascending positive ints; got %r'
                                 % (name, bounds))
            if name in self._pad_ragged:
                raise ValueError('field %r cannot be in both pad_ragged and '
                                 'bucket_boundaries (the boundaries define '
                                 'its padding)' % name)
            self._bucket_field = name
            self._bucket_bounds = np.asarray(bounds, np.int64)
        if not getattr(reader, 'batched_output', True):
            raise ValueError(
                'JaxLoader requires a batched reader (make_batch_reader); '
                'make_batch_reader decodes codec fields too, so a row reader '
                'is never needed here')
        self._reader = reader
        self._batch_size = batch_size
        self._mesh = mesh
        self._last_batch = last_batch
        self._dtypes = dict(dtypes or {})
        self._prefetch = max(1, prefetch)
        self._seed = seed
        self._shuffle_rows = shuffle_rows
        self._shuffling_queue_capacity = shuffling_queue_capacity
        self._min_after_retrieve = min_after_retrieve
        self._extra_capacity = extra_capacity
        self._sharding = self._resolve_sharding(mesh, data_axes, batch_size)
        # per-batch-length sharded row plans (see _shard_plan_for); None
        # values pin the make_array_from_process_local_data fallback
        self._shard_plans = {}
        # per-pass staging arena (stage thread only); __iter__ makes a
        # fresh one for every pass
        self._stager = self._new_stager()
        # staging autotuner (jax/autotune.py): created lazily at the
        # first pass when the knob allows, kept across passes so its
        # learned settings and decision log survive replays
        self._autotuner = None
        self._stage_thread = None
        self._out_queue = None
        self._stop_event = threading.Event()
        self._stage_error = None
        self._exhausted = False
        self._drain_lock = threading.Lock()
        # items drained out of the queue by __iter__'s boundary probe that
        # turned out to be real batches; __next__ serves these FIRST.
        # Putting them BACK into the bounded queue instead would race the
        # producer's possibly-still-blocked sentinel put (overflow, or the
        # sentinel ordered ahead of real batches).
        self._leftovers = []
        self._epoch = 0
        self._produce_done = threading.Event()
        # delivery-accurate checkpoint provenance (see state_dict): the
        # reader marks a row-group consumed when the STAGE thread pulls it,
        # but rows can sit in the shuffling buffer / prefetch queue long
        # after — so we track per-pull delivered-row counts and only count
        # a row-group once every row reached the consumer.
        self._prov_lock = threading.Lock()
        self._pull_info = {}        # pull_id -> [epoch, item_index, n_rows]
        self._pull_delivered = {}   # pull_id -> rows delivered to consumer
        self._delivered_by_epoch = {}   # epoch -> {item_index, ...}
        self._next_pull_id = 0
        self._uses_provenance = hasattr(reader, 'next_batch_info')
        # trace context of the most recent reader pull (staging thread
        # only): batches mix rows across pulls, so staging-side trace
        # events (collate, stage_fill, h2d_*) attribute to the pull being
        # folded in — the honest approximation for a batching stage
        self._last_pull_ctx = None
        # staging gauges (see diagnostics): who is waiting on whom? The
        # seconds of this loader's loader_next and stage_backpressure spans
        self._consumer_wait_s = 0.0   # consumer blocked on get → input-bound
        self._stage_blocked_s = 0.0   # producer blocked on put → compute-bound
        # loader-local stall attributor: the same two clocks, bucketed into
        # sampling windows (telemetry.StallAttributor) — what
        # autotune_report classifies from. Reset at each pass's FIRST
        # delivered batch, so spin-up (reader/decoder startup) latency
        # never misattributes a compute-bound pipeline as input-bound.
        self._attributor = StallAttributor()
        self._awaiting_first_delivery = True
        self._batches_delivered = 0
        # batches handed to the queue; equal to the delivered count at
        # delivery (FIFO), so it numbers the batch the stager works on
        self._batches_staged = 0
        # registry snapshot taken at each pass's first delivery: scopes
        # autotune's decoded-cache verdict to the CURRENT pass (lifetime
        # counters would blend epoch 1's full decode cost into every
        # later epoch's hit rate and misread healthy warm passes)
        self._pass_baseline = None
        # fused decode (petastorm_tpu/fused.py): the reason this loader
        # last MATERIALIZED a deferred column instead of letting the
        # arena fuse it (None = never declined); feeds fused_decode_mode
        self._fused_fallback = None
        # live observability plane (docs/telemetry.md): the loader
        # contributes its staging-side gauges to /health and the live
        # autotune verdict to /report; no-op when unarmed
        from petastorm_tpu.telemetry import obs_server
        self._obs_mount = obs_server.mount(
            'jax-loader', health=self._obs_health,
            report=self._obs_report)

    # -- sharding ------------------------------------------------------------

    @staticmethod
    def _resolve_sharding(mesh, data_axes, batch_size):
        if mesh is None:
            return None
        import jax
        from jax.sharding import NamedSharding, PartitionSpec
        axes = tuple(data_axes) if data_axes is not None else tuple(mesh.axis_names)
        n_shards = 1
        for a in axes:
            n_shards *= mesh.shape[a]
        if batch_size * jax.process_count() % max(1, n_shards):
            raise ValueError(
                'global batch (%d per host x %d hosts) must divide evenly '
                'over the %d data shards of mesh axes %s'
                % (batch_size, jax.process_count(), n_shards, axes))
        return NamedSharding(mesh, PartitionSpec(axes))

    # -- iteration -----------------------------------------------------------

    def __iter__(self):
        """Start a pass — or, per the iterator protocol, resume the pass in
        progress (``iter(it) is it``), replaying the dataset only when the
        previous pass is exhausted.

        .. note:: a mid-pass ``iter()`` whose prefetch queue is momentarily
           empty blocks until the staging thread either stages a batch
           (resume) or finishes the pass (replay) — it cannot tell which
           side of the epoch boundary it is on until one happens. With a
           stalled reader that wait is unbounded; ``stop()`` unblocks it.
        """
        if self._stage_thread is not None:
            if self._stop_event.is_set():
                raise RuntimeError('JaxLoader was stopped; construct a new '
                                   'loader to iterate again')
            if not self._exhausted:
                # The pass may have ended with its sentinel unobserved and
                # still in flight — the NORMAL state right after consuming
                # exactly to the boundary (iter_steps, or a drop-tail
                # batch): the producer is only now unblocking to enqueue
                # the sentinel. Wait for the pass state to settle: either a
                # real batch lands (mid-pass → resume) or the producer
                # finishes. _produce_done is set BEFORE the sentinel put,
                # so sentinel-visible implies done-visible: "queue
                # non-empty while done is unset" deterministically means
                # real batches. The lock keeps drain + put-back atomic
                # w.r.t. a consumer's exhaustion check in __next__.
                while True:
                    with self._drain_lock:
                        if (self._produce_done.is_set()
                                or not self._stage_thread.is_alive()):
                            pending = list(self._leftovers)
                            self._leftovers = []
                            try:
                                while True:
                                    pending.append(
                                        self._out_queue.get_nowait())
                            except queue.Empty:
                                pass
                            if pending == [_SENTINEL_END]:
                                self._exhausted = True  # boundary: complete
                                break
                            if pending:
                                # unconsumed tail (possibly incl. a
                                # trailing sentinel): park it for __next__
                                # to serve ahead of the queue
                                self._leftovers = pending
                                break
                            if not self._stage_thread.is_alive():
                                # dead without a sentinel (put gave up or
                                # died): __next__ surfaces stop/error
                                break
                            # done set, sentinel put in flight: retry
                        elif self._leftovers or not self._out_queue.empty():
                            # done was unset just above and sentinel puts
                            # strictly follow the done flag, so re-check
                            # before trusting the queue contents
                            if not self._produce_done.is_set():
                                break  # real batches staged: resume below
                            continue  # take the drain branch next round
                    if self._stop_event.is_set():
                        break
                    if self._produce_done.is_set():
                        time.sleep(0.001)  # sentinel put in flight: yield
                    else:
                        self._produce_done.wait(0.05)
                if not self._exhausted:
                    # Same pass resumes: ``iter(it) is it`` per the iterator
                    # protocol, so peek-then-loop (``next(loader)`` then
                    # ``for b in loader``) and ``for b in iter(loader)``
                    # both work. A staging error, if any, surfaces in
                    # __next__ where every consumption style sees it
                    # deterministically.
                    return self
            if self._stage_error is not None:
                raise RuntimeError('JaxLoader cannot restart after a staging '
                                   'error') from self._stage_error
            # The consumer can observe the end sentinel a beat before the
            # stage thread finishes its teardown; it is exiting, so join
            # rather than misreading aliveness as an in-progress pass.
            self._stage_thread.join(timeout=10)
            # Epoch replay: restart the (fully consumed) reader and stage a
            # fresh pass — same ergonomics as the torch loaders' re-iteration.
            self._reader.reset()
            self._exhausted = False
            self._epoch += 1
            # each pass's spin-up wait is excluded from autotune's
            # steady-state attribution (the attributor resets at the new
            # pass's first delivery)
            self._awaiting_first_delivery = True
            # reset() restarts the reader's epoch numbering from 0; stale
            # provenance would corrupt the delivery-accurate checkpoint
            with self._prov_lock:
                self._pull_info.clear()
                self._pull_delivered.clear()
                self._delivered_by_epoch = {}
            with self._drain_lock:
                self._leftovers = []  # exhausted implies empty; belt+braces
        # fresh event per pass: a predecessor thread in teardown may still
        # set the previous pass's event after this point
        self._produce_done = threading.Event()
        # fresh arena per pass, created BEFORE the thread starts so
        # diagnostics never observe a started pass without its stager: a
        # replay must not inherit slots whose in-flight transfers
        # belonged to the previous pass, and knob changes (after
        # staging.refresh_staging()) take effect here
        self._stager = self._new_stager()
        from petastorm_tpu.jax import autotune
        if self._autotuner is None and autotune.autotune_enabled():
            self._autotuner = autotune.StagingAutotuner(self)
        if self._autotuner is not None:
            # the new pass's stager starts at the depth the tuner
            # already learned (decisions survive epoch replays)
            self._autotuner.apply_learned(self._stager)
        self._out_queue = queue.Queue(maxsize=self._prefetch)
        self._stage_thread = threading.Thread(target=self._stage_loop,
                                              daemon=True,
                                              name='petastorm-tpu-stager')
        self._stage_thread.start()
        return self

    def _new_stager(self):
        return staging.make_stager(self._batch_size, self._dtypes,
                                   self._last_batch, self._put_to_device)

    def __next__(self):
        if self._out_queue is None:
            iter(self)
        if self._exhausted:
            raise StopIteration
        tracing.tag_batch(self._batches_delivered)
        waiting = timed('loader_next')
        try:
            with waiting:
                batch, pull_counts = self._next_item()
        finally:
            self._consumer_wait_s += waiting.seconds
        if pull_counts:
            self._record_delivery(pull_counts)
        self._batches_delivered += 1
        if self._awaiting_first_delivery:
            # spin-up over: drop the startup waits so autotune's
            # attribution covers steady state of the current pass only
            self._attributor.reset()
            self._awaiting_first_delivery = False
            from petastorm_tpu.telemetry import get_registry
            self._pass_baseline = get_registry().snapshot()
        return batch

    def _next_item(self):
        """The next staged ``(batch, pull_counts)``, leftovers first;
        raises StopIteration at the pass's end, or the staging error."""
        while True:
            with self._drain_lock:
                item = (self._leftovers.pop(0) if self._leftovers
                        else _NO_ITEM)
            if item is _NO_ITEM:
                try:
                    t0 = time.monotonic()
                    try:
                        item = self._out_queue.get(timeout=0.1)
                    finally:
                        waited = time.monotonic() - t0
                        if waited > STALL_NOTE_FLOOR_S:
                            # the wait is loader_next's seconds AND
                            # producer-bound evidence (both the loader's
                            # own attributor and the process-wide one)
                            self._attributor.note_consumer_wait(waited)
                            note_consumer_wait(waited)
                except queue.Empty:
                    if self._stage_error is not None:
                        raise self._stage_error
                    # stop() may race an in-flight iteration: _put_blocking
                    # gives up on delivering _SENTINEL_END once the stop
                    # event is set, so a consumer blocked here would
                    # otherwise spin forever. Same if next() is called
                    # after stop(), or the stage thread died without
                    # managing to enqueue the sentinel.
                    if self._stop_event.is_set():
                        self._exhausted = True
                        raise StopIteration
                    with self._drain_lock:
                        if (self._stage_thread is not None
                                and not self._stage_thread.is_alive()
                                and not self._leftovers
                                and self._out_queue.empty()):
                            self._exhausted = True
                            raise StopIteration
                    continue
            if item is _SENTINEL_END:
                self._exhausted = True
                if self._stage_error is not None:
                    raise self._stage_error
                raise StopIteration
            return item

    def _record_delivery(self, pull_counts):
        """Credit delivered rows to their pulls; a pull whose every row has
        reached the consumer marks its row-group delivered-for-checkpoint."""
        with self._prov_lock:
            for pull_id, n in pull_counts.items():
                info = self._pull_info.get(pull_id)
                if info is None:
                    continue  # stale (pre-replay) sidecar
                seen = self._pull_delivered.get(pull_id, 0) + n
                if seen >= info[2]:
                    epoch, item_index, _ = info
                    if epoch is not None:
                        self._delivered_by_epoch.setdefault(
                            epoch, set()).add(item_index)
                    del self._pull_info[pull_id]
                    self._pull_delivered.pop(pull_id, None)
                else:
                    self._pull_delivered[pull_id] = seen

    def iter_steps(self, num_steps):
        """Yield exactly ``num_steps`` batches, continuing across calls.

        The multi-host-safe epoch idiom (see the warning on
        :func:`make_jax_loader`): over an infinite loader
        (``num_epochs=None``), every host steps the same fixed count per
        "epoch" regardless of shard imbalance, so collectives stay aligned::

            steps = global_rows // (batch_size * jax.process_count())
            for epoch in range(epochs):
                for batch in loader.iter_steps(steps):
                    ...

        Raises :class:`RuntimeError` if the loader runs dry before
        ``num_steps`` (finite ``num_epochs`` with too little data) — on a
        pod that would mean a silent divergence of step counts across hosts.
        """
        if self._out_queue is None or self._exhausted:
            iter(self)  # start — or replay, matching plain iteration
        for step in range(num_steps):
            try:
                yield next(self)
                continue
            except StopIteration:
                pass
            # A prior call may have consumed the pass exactly to its end,
            # leaving the end sentinel unobserved (_exhausted was False
            # until just now). That is an epoch boundary, not running dry:
            # replay and retry, consistent with a fresh iter_steps call.
            if (step == 0 and not self._stop_event.is_set()
                    and self._stage_error is None):
                iter(self)
                try:
                    yield next(self)
                    continue
                except StopIteration:
                    pass
            if self._stop_event.is_set():
                raise RuntimeError(
                    'loader was stopped after %d of %d steps' % (step, num_steps))
            raise RuntimeError(
                'loader exhausted after %d of %d steps; use '
                'num_epochs=None so fixed-step epochs never run dry'
                % (step, num_steps)) from None

    # -- staging pipeline (background thread) --------------------------------

    def _make_buffer(self):
        from petastorm_tpu.buffers import (
            BatchedNoopShufflingBuffer, BatchedRandomShufflingBuffer,
        )
        if not self._shuffle_rows:
            return BatchedNoopShufflingBuffer(self._batch_size)
        capacity = self._shuffling_queue_capacity or 4 * self._batch_size
        min_after = (self._min_after_retrieve
                     if self._min_after_retrieve is not None
                     else capacity // 2)
        # Extra capacity absorbs one whole row-group on top of capacity.
        # It is EAGERLY preallocated per column, so the default stays
        # proportional to capacity (not a huge constant) — datasets with
        # row-groups larger than `capacity` rows should pass extra_capacity
        # explicitly (the overflow error says so).
        extra = (self._extra_capacity if self._extra_capacity is not None
                 else capacity)
        # seed offset by epoch: replay must not repeat epoch 0's order
        seed = (None if self._seed is None
                else (self._seed + self._epoch) % (2 ** 32))
        return BatchedRandomShufflingBuffer(
            capacity, min_after, self._batch_size,
            extra_capacity=extra, seed=seed)

    def _pull_batches(self):
        """Yield column dicts from the reader, tagging each row with its
        pull id when the reader exposes provenance (next_batch_info)."""
        if not self._uses_provenance:
            for batch in self._reader:
                yield dict(batch._asdict() if hasattr(batch, '_asdict')
                           else batch)
            return
        while True:
            try:
                columns, item_index, epoch = self._reader.next_batch_info()
            except StopIteration:
                return
            if tracing.trace_enabled():
                self._last_pull_ctx = tracing.ctx_for(
                    item_index, epoch, getattr(self._reader, 'cur_shard',
                                               None))
            n = len(next(iter(columns.values()))) if columns else 0
            with self._prov_lock:
                pull_id = self._next_pull_id
                self._next_pull_id += 1
                self._pull_info[pull_id] = (epoch, item_index, n)
            columns[_PULL_FIELD] = np.full(n, pull_id, np.int64)
            yield columns

    def _stage_loop(self):
        tracing.tag_batch(self._batches_staged)
        try:
            if self._bucket_field is not None:
                self._stage_loop_bucketed()
                return
            buf = self._make_buffer()
            for columns in self._pull_batches():
                # staging-side trace events (collate, stage_fill, h2d_*)
                # attach to the pull just folded in; no-op when untraced
                with tracing.activate(self._last_pull_ctx, track='stager'):
                    # deferred image columns that THIS pass cannot fuse
                    # (dtype recast) decode now, in one vectorized pass
                    # per column; the others ride the buffer still encoded
                    columns = self._materialize_encoded(columns)
                    with span('collate'):
                        # densify BEFORE the buffer: a variable field
                        # arrives as a dense (n, ...) array from a uniform
                        # row-group but as an object array from a ragged
                        # one, and the buffers cannot mix the two forms
                        # (nor two dense widths); after this, every chunk
                        # has ONE static shape and the shuffle buffer
                        # preallocates correctly
                        if self._pad_ragged:
                            columns = self._densify_ragged(columns)
                        buf.add_many(columns)
                    while buf.can_retrieve:
                        self._retrieve_and_emit(buf)
                        if self._stop_event.is_set():
                            return
                if self._stop_event.is_set():
                    return
            buf.finish()
            while buf.can_retrieve:
                self._retrieve_and_emit(buf)
                if self._stop_event.is_set():
                    return
        except Exception as e:  # noqa: BLE001 - surfaced to consumer
            self._stage_error = e
        finally:
            # drop slot slabs + their in-flight device refs: a ring kept
            # across the epoch gap would pin host and device memory the
            # post-epoch consumer (eval, checkpointing) may need
            self._stager.release()
            # set happens-before put: a sentinel can only be OBSERVED in
            # the queue after _produce_done is visible, which is what lets
            # __iter__'s probe distinguish "real mid-pass batches" from "a
            # just-landed sentinel" deterministically (no timing
            # heuristics). No lock here — holding _drain_lock across a
            # blocking put deadlocks against __iter__'s probe when the
            # queue is full.
            self._produce_done.set()
            self._put_blocking(_SENTINEL_END)

    def _stage_loop_bucketed(self):
        """Length-bucketed staging (the ``bucket_by_sequence_length``
        shape of tf.data, re-designed for XLA): each bucket keeps its own
        fixed-``batch_size`` buffer, every chunk is split by the bucket
        field's per-row length and densified to the bucket's bound, and a
        batch emits whenever any bucket fills. Emitted shapes are static
        PER BUCKET — jit compiles one step per bucket (bounded by the
        boundary count), and padding waste drops from pad-to-max to
        pad-to-bucket."""
        buffers = {}
        for columns in self._pull_batches():
            with tracing.activate(self._last_pull_ctx, track='stager'):
                # bucketed batching gathers per-row — always materialize
                columns = self._materialize_encoded(columns)
                with span('collate'):
                    if self._pad_ragged:
                        columns = self._densify_ragged(columns)
                    split = list(self._split_by_bucket(columns))
                for bound, subcols in split:
                    buf = buffers.get(bound)
                    if buf is None:
                        buf = buffers[bound] = self._make_buffer()
                    with span('collate'):
                        buf.add_many(subcols)
                    while buf.can_retrieve:
                        self._retrieve_and_emit(buf)
                        if self._stop_event.is_set():
                            return
            if self._stop_event.is_set():
                return
        for buf in buffers.values():
            buf.finish()
            while buf.can_retrieve:
                self._retrieve_and_emit(buf)
                if self._stop_event.is_set():
                    return

    @staticmethod
    def _object_cells(col, name, policy):
        """Normalize an object column to per-row ndarrays (None kept) and
        return ``(cells, first_non_none)`` — shared None/dtype-inference
        contract of ``pad_ragged`` and ``bucket_boundaries``."""
        cells = [None if c is None else np.asarray(c) for c in col]
        first = next((c for c in cells if c is not None), None)
        if first is None:
            raise ValueError(
                '%s[%r]: every cell in this batch is None; cell dtype/'
                'trailing shape cannot be inferred. Filter all-null '
                'batches with a predicate, or drop the field'
                % (policy, name))
        return cells, first

    @staticmethod
    def _reserve_len_column(columns, name, policy):
        """The ``<name>_len`` companion column's name, after checking the
        batch does not already carry one."""
        len_name = name + LEN_SUFFIX
        if len_name in columns:
            raise ValueError(
                '%s would add column %r but the batch already has one; '
                'rename the source column' % (policy, len_name))
        return len_name

    def _split_by_bucket(self, columns):
        """Split one chunk by the bucket field's per-row leading length.
        Yields ``(bound, subcolumns)`` with the bucket field densified to
        ``(n_rows, bound, *trailing)`` plus its true-length column; rows
        longer than the largest boundary truncate into the last bucket
        (true length preserved, same contract as ``pad_ragged``)."""
        name = self._bucket_field
        if name not in columns:
            raise ValueError(
                'bucket_boundaries field %r is not in the batch '
                '(available: %s); check the name against fields=/the '
                'schema' % (name, sorted(n for n in columns
                                         if n != _PULL_FIELD)))
        len_name = self._reserve_len_column(columns, name,
                                            'bucket_boundaries')
        col = columns[name]
        n = len(col)
        if n == 0:
            return
        if col.dtype == object:
            cells, first = self._object_cells(col, name,
                                              'bucket_boundaries')
            if first.ndim < 1:
                raise ValueError(
                    'bucket_boundaries[%r]: cells are scalars; bucketing '
                    'needs a leading sequence dim' % name)
            lens = np.asarray([0 if c is None else c.shape[0]
                               for c in cells], np.int32)
            trailing = first.shape[1:]
            dtype = first.dtype
        else:
            if col.ndim < 2:
                raise ValueError(
                    'bucket_boundaries[%r]: column is scalar per row; '
                    'bucketing needs a leading sequence dim' % name)
            cells = None  # uniform dense chunk: one length for all rows
            lens = np.full(n, col.shape[1], np.int32)
            trailing = col.shape[2:]
            dtype = col.dtype
        bounds = self._bucket_bounds
        # searchsorted('left') → index of the smallest bound >= len;
        # longer-than-largest rows clamp into the last bucket (truncate)
        bucket_idx = np.minimum(np.searchsorted(bounds, lens, side='left'),
                                len(bounds) - 1)
        for b in np.unique(bucket_idx):
            bound = int(bounds[b])
            rows = np.flatnonzero(bucket_idx == b)
            dense = np.zeros((len(rows), bound) + trailing, dtype)
            if cells is None:
                keep = min(col.shape[1], bound)
                dense[:, :keep] = col[rows, :keep]
            else:
                for j, i in enumerate(rows):
                    cell = cells[i]
                    if cell is None:
                        continue
                    keep = min(cell.shape[0], bound)
                    dense[j, :keep] = cell[:keep]
            subcols = {k: (v[rows] if k != name else dense)
                       for k, v in columns.items()}
            subcols[len_name] = lens[rows]
            yield bound, subcols

    def _materialize_encoded(self, columns):
        """Decode deferred image columns the CURRENT pass cannot fuse —
        batching is bucketed (rows are split and padded per bucket), or a
        ``dtypes=`` policy retargets the column's dtype (the fused fill
        writes the codec's native dtype only). Shuffled rows fuse: the
        random buffer gathers encoded cells, and the arena decodes the
        drawn rows into the slot. One vectorized ``materialize()`` per
        column (native batch decoders, internal thread pool) — still the
        batched regime, just not fused into the destination; each decline
        is counted in ``petastorm_tpu_fused_decode_fallbacks_total`` so
        the troubleshoot runbook can name the reason."""
        out = None
        for name, column in columns.items():
            if not isinstance(column, EncodedImageColumn):
                continue
            if self._bucket_field is not None:
                reason = 'bucketed'
            else:
                want = self._dtypes.get(name)
                if want is None or np.dtype(want) == column.dtype:
                    continue  # fusable: the arena fill decodes it
                reason = 'dtype-cast'
            if out is None:
                out = dict(columns)
            with span('decode'):
                out[name] = column.materialize()
            fused.count_fallback(reason)
            self._fused_fallback = reason
        return out if out is not None else columns

    def _retrieve_and_emit(self, buf):
        """Pull one batch from ``buf`` and emit it. With a buffer that
        can hand out parts (the noop re-batcher), the batch travels as a
        LIST of chunk views — the arena fills its slot from the parts
        directly, skipping the concatenated intermediate the plain
        ``retrieve()`` would allocate."""
        with span('collate'):
            if hasattr(buf, 'retrieve_parts'):
                batch = buf.retrieve_parts()
            else:
                batch = buf.retrieve()
        self._emit(batch)

    def _emit(self, host_batch):
        with span('collate'):
            if isinstance(host_batch, list):
                # parts form (see _retrieve_and_emit)
                parts = [dict(p) for p in host_batch]
                pulls = [p.pop(_PULL_FIELD, None) for p in parts]
                if pulls[0] is None:
                    pull_col = None
                elif len(pulls) == 1:
                    pull_col = pulls[0]   # common aligned case: no copy
                else:
                    pull_col = np.concatenate(pulls)
                n = sum(len(next(iter(p.values()))) for p in parts)
                host_batch = parts
            else:
                host_batch = dict(host_batch)
                pull_col = host_batch.pop(_PULL_FIELD, None)
                n = len(next(iter(host_batch.values())))
            if n < self._batch_size and self._last_batch == 'drop':
                # dropped rows: their pulls stay incomplete (sound)
                return
            if pull_col is None:
                pull_counts = None
            else:
                ids, counts = np.unique(np.asarray(pull_col),
                                        return_counts=True)
                pull_counts = dict(zip(ids.tolist(), counts.tolist()))
        # cast/pad/mask write into a preallocated slot and the transfer is
        # dispatched async (stage_fill/h2d_dispatch/h2d_ready spans) — the
        # consumer of batch N computes while batch N+1's transfer is in
        # flight
        device_batch = self._stager.stage(host_batch, n)
        # provenance rides the queue as a sidecar: rows count as delivered
        # only when the consumer actually receives this item in __next__
        self._put_blocking((device_batch, pull_counts))
        self._batches_staged += 1
        tracing.tag_batch(self._batches_staged)
        if self._autotuner is not None:
            # staging-thread cadence gate: a no-op monotonic compare
            # until the next autotune window is due
            self._autotuner.maybe_tick()

    def _densify_ragged(self, columns):
        """Apply the ``pad_ragged`` policy to one reader chunk: variable
        -shape columns become static-shape dense arrays plus a
        ``<name>_len`` TRUE-size column (a truncated row's stored length
        exceeds the padded extent — that is how truncation stays
        detectable; masks built as ``arange(L) < len`` saturate correctly).

        Runs BEFORE the staging buffer (see ``_stage_loop``): a variable
        field arrives as a 1-d OBJECT array from a ragged row-group but as
        an already-dense ``(n, ...)`` array from a uniform one, and the
        buffers can mix neither the two forms nor two dense widths."""
        out = dict(columns)
        for name, targets in self._pad_ragged.items():
            if name not in out:
                # unconditional (every chunk): readers yield a stable
                # schema, so a field absent mid-stream is a bug upstream —
                # silently skipping would emit batches with inconsistent
                # column sets that fail later with an unrelated error
                raise ValueError(
                    'pad_ragged field %r is not in the batch (available: '
                    '%s); check the name against fields=/the schema'
                    % (name, sorted(n for n in columns
                                    if n != _PULL_FIELD)))
            len_name = self._reserve_len_column(out, name, 'pad_ragged')
            col = out[name]
            k = len(targets)
            n = len(col)
            if n == 0:
                continue
            if col.dtype == object:
                # None cells (nullable fields) densify as all-zero rows
                # with true size 0 — the natural mask value downstream
                cells, first = self._object_cells(col, name, 'pad_ragged')
                trailing = first.shape[k:]
                dense = np.zeros((n,) + targets + trailing, first.dtype)
                lens = np.zeros((n, k), np.int32)
                for i, cell in enumerate(cells):
                    if cell is None:
                        continue  # lens stay 0, dense row stays zeros
                    if cell.ndim != k + len(trailing):
                        raise ValueError(
                            'pad_ragged[%r]: row has %d dims but the policy '
                            'names %d variable dim(s) over trailing shape %r'
                            % (name, cell.ndim, k, trailing))
                    lens[i] = cell.shape[:k]
                    clipped = tuple(min(cell.shape[d], targets[d])
                                    for d in range(k))
                    region = (i,) + tuple(slice(0, c) for c in clipped)
                    dense[region] = cell[tuple(slice(0, c) for c in clipped)]
            else:
                # pre-stacked dense chunk: every row shares one shape, so
                # one vectorized slice assignment replaces the row loop
                if col.ndim < 1 + k:
                    raise ValueError(
                        'pad_ragged[%r]: dense chunk has %d row dims but '
                        'the policy names %d variable dim(s)'
                        % (name, col.ndim - 1, k))
                trailing = col.shape[1 + k:]
                dense = np.zeros((n,) + targets + trailing, col.dtype)
                clipped = tuple(min(col.shape[1 + d], targets[d])
                                for d in range(k))
                region = (slice(None),) + tuple(slice(0, c) for c in clipped)
                dense[region] = col[region]
                lens = np.broadcast_to(
                    np.asarray(col.shape[1:1 + k], np.int32), (n, k)).copy()
            out[name] = dense
            out[len_name] = lens[:, 0] if k == 1 else lens
        return out

    def _put_to_device(self, host_batch):
        """Dispatch one host batch to the device(s) — the staging
        engine's ``put_fn`` (validation and dtype casting already
        happened in the engine)."""
        import jax
        if self._sharding is not None:
            return self._dispatch_sharded(host_batch)
        # one device_put of the whole pytree: a single dispatch covering
        # every field's transfer, instead of one runtime round trip each
        return jax.device_put(host_batch)

    def _dispatch_sharded(self, host_batch):
        """Sharded (mesh) dispatch, one covering the whole pytree: every
        field's local shard slices ride a single batched
        ``jax.device_put`` (one runtime round trip) and reassemble into
        global ``jax.Array``s via ``make_array_from_single_device_arrays``
        — instead of one ``make_array_from_process_local_data`` round
        trip per field. Falls back to the per-field build when the row
        plan cannot be proven sound for this sharding (always correct,
        never fast). The engine wraps this call in ``h2d_dispatch`` and
        counts ``petastorm_tpu_h2d_bytes_total`` (shard-slice bytes: what
        THIS host puts on the wire, not the global batch)."""
        import jax
        if not host_batch:
            return {}
        n_local = len(next(iter(host_batch.values())))
        plan = self._shard_plan_for(n_local)
        if plan is None:
            return {name: jax.make_array_from_process_local_data(
                        self._sharding, arr)
                    for name, arr in host_batch.items()}
        slices = []
        devices = []
        for arr in host_batch.values():
            for device, lo, hi in plan:
                slices.append(arr[lo:hi])
                devices.append(device)
        shards = jax.device_put(slices, devices)
        out = {}
        k = len(plan)
        global_rows = n_local * jax.process_count()
        for i, (name, arr) in enumerate(host_batch.items()):
            out[name] = jax.make_array_from_single_device_arrays(
                (global_rows,) + arr.shape[1:], self._sharding,
                shards[i * k:(i + 1) * k])
        return out

    def _shard_plan_for(self, n_local):
        """Cached per-batch-length row plan (short tails get their own);
        None pins the ``make_array_from_process_local_data`` fallback for
        that length."""
        if n_local in self._shard_plans:
            return self._shard_plans[n_local]
        from petastorm_tpu.parallel.sharding import local_shard_plan
        plan = local_shard_plan(self._sharding, n_local)
        if plan is None:
            logger.debug(
                'sharded staging: no sound row plan for %d local rows on '
                '%r; using the per-field make_array_from_process_local_'
                'data fallback', n_local, self._sharding)
        self._shard_plans[n_local] = plan
        return plan

    def _set_prefetch(self, depth):
        """Autotuner seam: deepen the prefetch queue mid-pass. Writing
        ``queue.Queue.maxsize`` is safe here — the producer retries its
        bounded put every 0.1s (``_put_blocking``), so a raised bound is
        observed on the next attempt without waking any waiter."""
        depth = max(1, int(depth))
        self._prefetch = depth
        if self._out_queue is not None:
            self._out_queue.maxsize = depth
        return depth

    def _put_blocking(self, item):
        waiting = timed('stage_backpressure')
        try:
            with waiting:
                while not self._stop_event.is_set():
                    try:
                        self._out_queue.put(item, timeout=0.1)
                        return
                    except queue.Full:
                        continue
        finally:
            # time the producer spent blocked on a full queue: back-pressure
            # from a consumer that is NOT input-bound
            blocked = waiting.seconds
            self._stage_blocked_s += blocked
            if blocked > STALL_NOTE_FLOOR_S:
                self._attributor.note_producer_wait(blocked)
                note_producer_wait(blocked)

    # -- lifecycle -----------------------------------------------------------

    @property
    def schema(self):
        return self._reader.schema

    @property
    def reader(self):
        return self._reader

    @property
    def batch_size(self):
        return self._batch_size

    @property
    def last_batch(self):
        """The last-batch policy: ``'drop'``, ``'pad'`` or ``'short'``."""
        return self._last_batch

    @property
    def shuffle_rows(self):
        return self._shuffle_rows

    @property
    def bucket_field(self):
        """The ``bucket_boundaries`` field name, or None."""
        return self._bucket_field

    @property
    def sharding(self):
        """The resolved :class:`~jax.sharding.NamedSharding` batches are
        staged with, or None without a mesh."""
        return self._sharding

    @property
    def epoch(self):
        """Number of completed replay passes (0 during the first pass)."""
        return self._epoch

    @property
    def diagnostics(self):
        """Reader pool gauges plus the staging layer's own: queue depth,
        delivered-batch count, and the two wait clocks that say WHO is the
        bottleneck — high ``consumer_wait_s`` means the input pipeline is
        too slow (add decode workers / prefetch); high
        ``stage_backpressure_s`` means the training step is (keep prefetch
        small, the input side is not the problem)."""
        diag = dict(self._reader.diagnostics)
        stager = self._stager
        diag.update({
            'stage_queue_depth': (self._out_queue.qsize()
                                  if self._out_queue is not None else 0),
            'stage_leftovers': len(self._leftovers),
            'batches_delivered': self._batches_delivered,
            'consumer_wait_s': round(self._consumer_wait_s, 3),
            'stage_backpressure_s': round(self._stage_blocked_s, 3),
            'pulls_in_flight': len(self._pull_info),
            # staging arena (docs/telemetry.md "Host→device staging"):
            # slot slabs only grow at startup / on a new bucket shape —
            # steady growth here means the arena is not being reused
            'staging_slots_allocated': stager.slabs_allocated,
            # fused decode (docs/troubleshoot.md "decode is batched but
            # not fused"): where decode ran for this pass's batches
            'fused_decode_mode': self._fused_decode_mode(),
            'fused_decode_rows': stager.fused_rows,
            # staging autotuner (jax/autotune.py): live depth settings +
            # how many adjustments this loader has made
            'staging_prefetch': self._prefetch,
            'staging_slot_depth': stager.num_slots,
            'staging_autotune': self._autotuner is not None,
            'staging_autotune_decisions': (self._autotuner.decisions
                                           if self._autotuner is not None
                                           else 0),
        })
        if self._fused_fallback is not None:
            diag['fused_decode_fallback'] = self._fused_fallback
        return diag

    def _fused_decode_mode(self):
        """Where image decode ran for this pass: ``'fused-into-slot'``
        (arena slot ring — the zero-extra-copy regime),
        ``'fused-into-slab'`` (host-backed fresh assembly, still one
        decode-to-destination pass), ``'batched'`` (classic worker-side
        or loader-materialized batch decode), or ``'pending'`` before
        the first delivery says which."""
        if self._stager.fused_rows:
            return self._stager.fused_mode
        if self._fused_fallback is not None or self._batches_delivered:
            return 'batched'
        return 'pending'

    def pipeline_report(self, wall_time_s=None):
        """Process-wide per-stage breakdown + stall attribution
        (:func:`petastorm_tpu.telemetry.pipeline_report`); includes the
        reader's worker stages for every pool flavor via the pools' metric
        delta channels."""
        from petastorm_tpu.telemetry import pipeline_report
        return pipeline_report(wall_time_s=wall_time_s)

    def dump_trace(self, path):
        """Export the per-item trace (ventilate → worker stages →
        queue_wait → collate/h2d_dispatch, across every pool flavor) as
        Chrome trace-event JSON; needs ``PETASTORM_TPU_TRACE=1`` during
        the run (docs/telemetry.md). Returns the number of events
        written."""
        from petastorm_tpu.telemetry import dump_trace
        return dump_trace(path)

    def autotune_report(self):
        """Bottleneck attribution + concrete tuning advice, tf.data-style
        (its AUTOTUNE observes the same signals: who waits on whom).

        Consumes this loader's stall-attribution windows
        (:class:`~petastorm_tpu.telemetry.StallAttributor`): the two wait
        clocks — consumer blocked on the prefetch queue (input-bound
        evidence) vs stage blocked pushing into it (compute-bound
        evidence) — bucketed into sampling windows and classified per
        window. The attributor resets at each pass's first delivered
        batch, so spin-up (reader/decoder startup) is pipeline latency,
        never contention, and the report covers the CURRENT pass's steady
        state only — the right scope for tuning (the current pass reflects
        the current settings) but not a whole-run accumulator. Returns
        ``{'bottleneck': 'input'|'compute'|'balanced'|'undetermined',
        'input_stall_fraction': float, 'window_verdicts': {verdict: n},
        'advice': [str, ...], ...}`` — advisory only; nothing is
        changed."""
        from petastorm_tpu.telemetry import (
            BALANCED, CONSUMER_BOUND, PRODUCER_BOUND,
        )
        from petastorm_tpu.telemetry.stall import classify_window
        # everything below reads ONE source — the attributor's window set
        # (bounded deque, so a very long pass reports its recent ~minutes)
        # — so the fraction, the verdict and the advice can never
        # contradict each other
        windows = self._attributor.windows()
        stage = sum(w['producer_wait_s'] for w in windows)
        consumer = sum(w['consumer_wait_s'] for w in windows)
        total = consumer + stage
        verdict_counts = {}
        for w in windows:
            verdict_counts[w['verdict']] = \
                verdict_counts.get(w['verdict'], 0) + 1
        report = {
            'consumer_wait_s': round(consumer, 3),
            'stage_backpressure_s': round(stage, 3),
            'batches_delivered': self._batches_delivered,
            'window_verdicts': verdict_counts,
        }
        if self._batches_delivered < 4 or total < 0.05:
            report['bottleneck'] = 'undetermined'
            report['input_stall_fraction'] = 0.0
            report['advice'] = ['not enough iteration observed yet; '
                                'consume more batches before tuning']
            # the cache section is observational, not verdict-derived —
            # a short pass still shows whether the decoded tier served
            self._add_decoded_cache_advice(report)
            if self._autotuner is not None:
                report['staging_autotune'] = self._autotuner.summary()
            return report
        frac = consumer / total
        report['input_stall_fraction'] = round(frac, 3)
        # aggregate verdict over the same windows (summed clocks with the
        # attributor's dominance threshold — robust to one noisy window)
        verdict = classify_window(
            stage, consumer, self._attributor.window_s * len(windows))
        if verdict == PRODUCER_BOUND:
            report['bottleneck'] = 'input'
            report['advice'] = [
                'the consumer waits on data %.0f%% of contended time: add '
                'decode workers (workers_count), raise prefetch, move '
                'heavy TransformSpec work off the row path, or switch '
                "GIL-heavy transforms to reader_pool_type='process'"
                % (frac * 100),
            ]
            # tf.data-service-style escalation: when the host's own CPUs
            # are the wall, scale the DECODE FLEET, not this host
            pool_diag = {}
            try:
                pool_diag = dict(self._reader.diagnostics)
            except Exception:  # noqa: BLE001 - custom readers may lack it
                pass
            if 'workers_registered' in pool_diag:
                report['advice'].append(
                    'the remote decode fleet (%d live worker server(s)) is '
                    'the lagging stage: start more worker servers — they '
                    'register with the running dispatcher, no restart '
                    'needed (docs/service.md)'
                    % pool_diag.get('workers_alive', 0))
            else:
                report['advice'].append(
                    'if this host is out of CPU, disaggregate decode to '
                    "remote CPU hosts with reader_pool_type='service' "
                    '(docs/service.md)')
        elif verdict == CONSUMER_BOUND:
            report['bottleneck'] = 'compute'
            report['advice'] = [
                'the training step is the bottleneck (staging blocked '
                '%.0f%% of contended time): the input pipeline is NOT the '
                'problem; keep prefetch small to save host RAM'
                % ((1 - frac) * 100),
            ]
        else:
            assert verdict == BALANCED
            report['bottleneck'] = 'balanced'
            report['advice'] = ['producer and consumer are balanced; '
                                'tune the model step first']
        self._add_decoded_cache_advice(report)
        if self._autotuner is not None:
            # the closed loop's own record: current depths + the recent
            # decision log, so "what changed and why" rides the report
            report['staging_autotune'] = self._autotuner.summary()
        return report

    def _add_decoded_cache_advice(self, report):
        """Cache-aware autotune: when the materialized decoded-row-group
        cache is live, the right advice changes — a cache-bound pass
        makes io/decode tuning pointless, and a warm-epoch pass that is
        NOT cache-bound points at fingerprint churn or an undersized
        tier (docs/troubleshoot.md has the runbook). The section comes
        from the full pipeline_report so the verdict sees the stage
        timings — a bare hit-rate verdict could claim 'cache-bound'
        while the misses' decode time dominates the wall, directly
        contradicting the attributor's 'add decode workers' advice —
        and is baselined at this pass's first delivery so epoch 1's
        fill cost never dilutes a healthy warm pass's hit rate."""
        from petastorm_tpu.telemetry import pipeline_report
        section = pipeline_report(
            baseline=self._pass_baseline).get('decoded_cache')
        if section is None:
            return
        report['decoded_cache'] = section
        advice = report.setdefault('advice', [])
        if section['verdict'] == 'cache-bound':
            advice.append(
                'the decoded row-group cache serves this pass (%.0f%% '
                'hits): epoch 2+ is cache-bound as designed, so decode '
                'workers/io tuning will not help — look at collate/H2D '
                'and the model step' % (100 * section['hit_rate']))
        elif section['hit_rate'] < 0.5 and report.get('bottleneck') == \
                'input':
            advice.append(
                'a decoded cache is configured but only %.0f%% of reads '
                'hit: if this is epoch 2+, check for cache-key churn '
                '(unstable TransformSpec closure, rewritten dataset '
                'files) or an undersized tier evicting the working set '
                "(docs/troubleshoot.md, 'epoch 2 is not cache-bound')"
                % (100 * section['hit_rate']))

    def state_dict(self):
        """Row-group-granular, at-least-once checkpoint of the DATA
        POSITION AS DELIVERED to the consumer.

        Unlike the raw reader's ``state_dict`` (which marks a row-group
        consumed when the staging thread pulls it), this counts a
        row-group only once every one of its rows has left the shuffling
        buffer and prefetch queue and reached ``__next__`` — rows still
        in flight are re-read on resume, never skipped.
        """
        if self._uses_provenance:
            with self._prov_lock:
                delivered = {epoch: set(items) for epoch, items
                             in self._delivered_by_epoch.items()}
            return self._reader.resume_state_from(delivered)
        return self._reader.state_dict()

    def load_state_dict(self, state):
        self._reader.load_state_dict(state)
        # mirror the reader: later checkpoints must account the restored
        # position (earlier epochs complete, resume epoch partly consumed)
        with self._prov_lock:
            self._delivered_by_epoch = \
                self._reader.consumption_record_for_resume(state)

    def _obs_health(self):
        """The loader's /health contribution: who waits on whom, right
        now (the reader mounts its own section with the pool gauges)."""
        return {
            'epoch': self._epoch,
            'exhausted': self._exhausted,
            'batches_delivered': self._batches_delivered,
            'stage_queue_depth': (self._out_queue.qsize()
                                  if self._out_queue is not None else 0),
            'prefetch': self._prefetch,
            'consumer_wait_s': round(self._consumer_wait_s, 3),
            'stage_backpressure_s': round(self._stage_blocked_s, 3),
            'fused_decode_mode': self._fused_decode_mode(),
            # per-host staging view (the "One host starves the mesh"
            # runbook reads these across every host's /health endpoint)
            'h2d_overlap_share': self._h2d_overlap_share(),
            'staging_prefetch': self._prefetch,
            'staging_slot_depth': self._stager.num_slots,
            'staging_autotune_decisions': (self._autotuner.decisions
                                           if self._autotuner is not None
                                           else 0),
        }

    def _h2d_overlap_share(self):
        """THIS host's live fill/transfer overlap share (None before the
        arena has staged anything) — the per-host member of the mesh-wide
        overlap picture. Computed from the three stage counters directly
        (sharing the report's formula), never by building a whole
        pipeline_report: /health is polled, and must stay cheap."""
        from petastorm_tpu.telemetry import get_registry
        from petastorm_tpu.telemetry.export import _h2d_overlap_share
        from petastorm_tpu.telemetry.registry import metric_key
        from petastorm_tpu.telemetry.spans import STAGE_SECONDS
        counters = get_registry().counters_with_prefix(STAGE_SECONDS)
        stages = {
            stage: {'seconds': counters.get(
                metric_key(STAGE_SECONDS, {'stage': stage}), 0.0)}
            for stage in ('stage_fill', 'h2d_dispatch', 'h2d_ready')}
        return _h2d_overlap_share(stages)

    def _obs_report(self):
        """The loader's /report contribution: the live autotune verdict
        + advice, so "what should I change" is scrapeable mid-run."""
        return {'autotune': self.autotune_report()}

    def stop(self):
        self._obs_mount.close()
        if self._autotuner is not None:
            # drops the in-process decoder-thread override so a stopped
            # loader's learned setting cannot leak into later readers
            self._autotuner.close()
        self._stop_event.set()
        # Stop the reader FIRST: it is what a staging thread blocked in
        # reader.__next__ is actually waiting on; the stop event alone
        # cannot wake it.
        self._reader.stop()
        if self._stage_thread is not None:
            self._stage_thread.join(timeout=10)
        self._reader.join()

    def __enter__(self):
        return self

    def __exit__(self, exc_type, exc_val, exc_tb):
        self.stop()


class InMemoryCachedLoader:
    """Epoch replay from device memory: decode once, train many epochs.

    Wraps a single-epoch :class:`JaxLoader`. The first pass streams
    normally while retaining every delivered device batch; subsequent
    passes serve those arrays directly — no Parquet read, no codec decode,
    no host→device transfer — in a per-epoch reshuffled batch order. The
    flagship-loader counterpart of the torch bridge's
    ``BatchedDataLoader(inmemory_cache_all=True)`` (reference:
    ``petastorm/pytorch.py:344-407``), with the cache living in HBM as
    ``jax.Array``s instead of torch tensors.

    Fit check is the caller's job: the whole epoch must fit in device (or
    host, for CPU-backed arrays) memory. Iteration state checkpointing is
    unsupported — replay epochs have no reader position (resume by
    replaying the cached epoch from its start).

    **Replay shuffling.** When the wrapped loader row-shuffles
    (``shuffle_rows=True``), replay epochs re-draw BATCH MEMBERSHIP too:
    the cached epoch's rows are pooled on device (one concatenated array
    per field, built lazily at the first replay) and re-batched under a
    fresh permutation each epoch — matching the reference torch loader's
    behavior of re-feeding cached rows through a fresh shuffling buffer
    (``petastorm/pytorch.py:344-407``), but as device-side gathers instead
    of a host-side buffer. Without ``shuffle_rows`` only the batch ORDER
    is shuffled (row composition is frozen after epoch 1). Row-level
    replay is single-host only: on a multi-process run the cached arrays'
    local shards cannot be re-gathered host-locally, so it degrades to
    batch-order shuffling with a warning.
    """

    def __init__(self, loader, seed=0):
        self._loader = loader
        self._seed = seed
        self._cache = []
        self._row_cache = None     # field -> one concatenated device array
        self._row_count = 0
        self._cache_epoch = None
        self._complete = False
        self._produced_any = False
        self._stopped = False
        self._replay_epoch = 0
        self._steps_iter = None

    # -- iteration -----------------------------------------------------------

    def __iter__(self):
        if self._stopped:
            raise RuntimeError('InMemoryCachedLoader was stopped (its cache '
                               'is released); construct a new loader to '
                               'iterate again')
        if not self._complete:
            return self._first_pass()
        return self._replay()

    def _first_pass(self):
        it = iter(self._loader)
        if self._loader.epoch != self._cache_epoch:
            # the underlying loader began a NEW pass (e.g. the previous
            # first-pass generator was abandoned exactly at the epoch
            # boundary, so its epilogue never ran): batches cached from the
            # stale pass would otherwise duplicate every row
            self._cache = []
            self._cache_epoch = self._loader.epoch
        for batch in it:
            self._cache.append(batch)
            self._produced_any = True
            yield batch
        self._complete = True

    def _replay(self):
        self._replay_epoch += 1
        rng = np.random.RandomState(
            None if self._seed is None
            else (self._seed + self._replay_epoch) % (2 ** 32))
        if self._loader.shuffle_rows and self._row_replay_supported():
            yield from self._replay_rows(rng)
            return
        cache = self._cache
        order = np.arange(len(cache))
        rng.shuffle(order)
        for i in order:
            if self._stopped:
                raise RuntimeError('InMemoryCachedLoader was stopped (its '
                                   'cache is released) while a replay '
                                   'iterator was live')
            yield cache[i]

    def _row_replay_supported(self):
        import jax
        if self._loader.bucket_field is not None:
            # bucketed batches carry per-bucket widths; pooling them into
            # one array per field cannot concatenate. Batch-order replay
            # (shapes preserved per batch) is the sound fallback.
            if not getattr(self, '_warned_bucketed', False):
                logger.warning(
                    'inmemory_cache_all: bucket_boundaries batches have '
                    'per-bucket shapes; replay reshuffles batch order only')
                self._warned_bucketed = True
            return False
        if jax.process_count() == 1:
            return True
        if not getattr(self, '_warned_multiprocess', False):
            logger.warning(
                'inmemory_cache_all: row-level replay shuffling needs the '
                'whole epoch addressable on this host; on a %d-process run '
                'replay reshuffles batch order only',
                jax.process_count())
            self._warned_multiprocess = True
        return False

    def _ensure_row_cache(self):
        """Pool the cached epoch into one device array per field (valid
        rows only), releasing the per-batch cache — the pooled copy
        replaces it, keeping peak HBM at ~one epoch (plus one field's
        pooled copy while it concatenates)."""
        if self._row_cache is not None:
            return
        if not self._cache:
            self._row_cache = {}
            self._row_count = 0
            return
        import jax.numpy as jnp
        names = [n for n in self._cache[0] if n != MASK_FIELD]
        parts = {n: [] for n in names}
        for b in self._cache:
            mask = b.get(MASK_FIELD)
            for n in names:
                arr = b[n]
                if mask is not None:
                    arr = arr[np.asarray(mask)]
                parts[n].append(arr)
        # drop the per-batch refs BEFORE materializing pooled copies
        # (`parts` keeps the arrays alive) and release each field's pieces
        # as its pooled copy lands, so peak HBM stays ~one epoch. Publish
        # to self only on success: a mid-pooling failure (device OOM) must
        # leave the loader observably broken (retry re-raises), not with
        # an empty row cache that silently replays zero batches.
        self._cache = []
        pooled = {}
        try:
            for n in names:
                pooled[n] = jnp.concatenate(parts.pop(n), axis=0)
        except Exception:
            # per-batch refs are gone; poison further replays explicitly
            self._stopped = True
            raise
        self._row_cache = pooled
        self._row_count = int(next(iter(pooled.values())).shape[0])

    def _replay_rows(self, rng):
        import jax
        import jax.numpy as jnp
        self._ensure_row_cache()
        n = self._row_count
        if n == 0:
            return
        bs = self._loader.batch_size
        policy = self._loader.last_batch
        sharding = self._loader.sharding
        # snapshot: stop() nulls _row_cache under a live generator; the
        # per-batch _stopped check below turns that into the intended
        # RuntimeError instead of an AttributeError mid-comprehension
        row_cache = self._row_cache
        perm = rng.permutation(n)
        stop = n - (n % bs) if policy == 'drop' else n
        for start in range(0, stop, bs):
            if self._stopped:
                raise RuntimeError('InMemoryCachedLoader was stopped (its '
                                   'cache is released) while a replay '
                                   'iterator was live')
            idx = jnp.asarray(perm[start:start + bs])
            k = int(idx.shape[0])
            batch = {name: jnp.take(arr, idx, axis=0)
                     for name, arr in row_cache.items()}
            if policy == 'pad':
                if k < bs:
                    batch = {name: jnp.concatenate(
                        [a, jnp.zeros((bs - k,) + a.shape[1:], a.dtype)])
                        for name, a in batch.items()}
                mask = np.zeros(bs, dtype=bool)
                mask[:k] = True
                batch[MASK_FIELD] = jnp.asarray(mask)
            if sharding is not None:
                batch = {name: jax.device_put(a, sharding)
                         for name, a in batch.items()}
            yield batch

    def iter_steps(self, num_steps):
        """Exactly ``num_steps`` batches, continuing across calls and epoch
        boundaries (see :meth:`JaxLoader.iter_steps`)."""
        if self._stopped:
            raise RuntimeError('InMemoryCachedLoader was stopped (its cache '
                               'is released); construct a new loader to '
                               'iterate again')
        it = self._steps_iter
        for _ in range(num_steps):
            while True:
                if it is None:
                    it = iter(self)
                try:
                    yield next(it)
                    break
                except StopIteration:
                    if not self._produced_any:
                        raise RuntimeError(
                            'inmemory_cache_all loader produced no batches; '
                            'the dataset is empty (or every batch was '
                            "dropped by last_batch='drop')") from None
                    it = None
        self._steps_iter = it

    # -- passthrough ---------------------------------------------------------

    @property
    def schema(self):
        return self._loader.schema

    @property
    def reader(self):
        return self._loader.reader

    @property
    def diagnostics(self):
        # the full JaxLoader merge (pool + staging gauges), so the
        # tpu_guide's consumer_wait_s/backpressure advice applies here too
        return self._loader.diagnostics

    def dump_trace(self, path):
        """See :meth:`JaxLoader.dump_trace` (replay epochs add no events —
        they never touch the reader)."""
        return self._loader.dump_trace(path)

    def state_dict(self):
        raise RuntimeError(
            'inmemory_cache_all loaders have no checkpointable reader '
            'position (replay epochs never touch the reader); checkpoint '
            'the train state alone and replay the cached epoch on resume')

    def load_state_dict(self, state):
        raise RuntimeError(
            'inmemory_cache_all loaders have no checkpointable reader '
            'position to restore; replay the cached epoch from its start '
            'instead')

    def stop(self):
        self._stopped = True
        self._loader.stop()
        self._cache = []
        self._row_cache = None
        # a saved iter_steps cursor over the now-released cache must not
        # survive: resuming it would IndexError instead of the intended
        # 'was stopped' RuntimeError above
        self._steps_iter = None

    def __enter__(self):
        return self

    def __exit__(self, exc_type, exc_val, exc_tb):
        self.stop()
