"""Per-item distributed tracing: trace contexts, activation, dump hooks.

PR 3's telemetry says *which stage* is slow on average; this layer says
*which row-group, on which worker, spent its time where* — the per-element
event traces the tf.data papers use to localize input-bound stalls on a
timeline. Every ventilated work item gets a :class:`TraceContext` (trace
id, item sequence, epoch, shard) minted at the ventilator; the context
rides the channels the pipeline already has — the pools' ventilate
queues and the service protocol's WORK frames, as a reserved
``_trace_ctx`` kwarg (:data:`TRACE_CTX_KEY`) the pools strip before
``worker.process`` — and worker-side events travel back piggybacked on
the same metric-delta frames the pools already ship (process-pool
markers, service DONE messages). Consumer-side events (``queue_wait``,
``collate``, ``stage_fill``, ``h2d_dispatch``, the dispatcher's dispatch/re-ventilation/dedup
instants) land in the same per-process flight recorder
(:mod:`~petastorm_tpu.telemetry.recorder`), so one export shows the whole
distributed life of an item — including both attempts of a re-ventilated
item after a worker death, with the single deduped completion marked.

No-op discipline (the overhead contract): with ``PETASTORM_TPU_TRACE``
unset/0 — the default — :func:`mint` is one cached-boolean check
returning None, :func:`activate`/:func:`attempt` on a None context return
a shared do-nothing singleton, and the metrics spans never see a trace
hook; the hot path pays exactly what it paid before this module existed
(enforced by ``tests/test_tracing.py``). Sampling
(``PETASTORM_TPU_TRACE_SAMPLE=1/N``) is deterministic on the item
sequence number, so the consumer can re-derive a result's context
(:func:`ctx_for`) without any wire change on the result path.

The timeline (:func:`timeline`) is the other use of the same span hook:
armed from code for a block, it records EVERY stage span of this process
— start, end and CPU, on ``time.perf_counter_ns()`` — into the flight
recorder, whether or not an item is traced, so a profile taken on the
same clock (a device trace) can be read against what each thread of the
pipeline was doing.
"""

import atexit
import collections
import logging
import os
import threading
import time
import uuid

from petastorm_tpu.analysis.contracts import EVENT_NAMES  # noqa: F401
from petastorm_tpu.telemetry import knobs, spans
from petastorm_tpu.telemetry.recorder import export_chrome_trace, get_recorder

logger = logging.getLogger(__name__)

#: reserved kwarg the ventilator injects into sampled work items and every
#: pool flavor strips (and activates) before calling ``worker.process``
TRACE_CTX_KEY = '_trace_ctx'

TraceContext = collections.namedtuple(
    'TraceContext', ('trace_id', 'item_seq', 'epoch', 'shard'))

# knob caches (refresh_trace() re-reads); None = not yet resolved
_enabled = None
_stride = None
# per-process run id: part of every minted trace id, so two readers (or a
# rerun) in one process never collide
_run_id = uuid.uuid4().hex[:8]

_state = threading.local()     # .ctx / .track of the active item, if any;
                               # .batch: the loader batch the thread serves


def trace_enabled():
    """True when ``PETASTORM_TPU_TRACE`` turns per-item tracing on."""
    global _enabled
    if _enabled is None:
        _enabled = knobs.is_enabled('PETASTORM_TPU_TRACE')
        if _enabled:
            _install_dump_hooks()
    return _enabled


def sample_stride():
    """N of ``PETASTORM_TPU_TRACE_SAMPLE=1/N`` (accepts a plain ``N``
    too): every N-th ventilated item is traced. Default 1 (every item)."""
    global _stride
    if _stride is None:
        raw = knobs.get_str('PETASTORM_TPU_TRACE_SAMPLE')
        stride = 1
        if raw:
            try:
                stride = int(raw.split('/', 1)[1] if '/' in raw else raw)
            except ValueError:
                logger.warning('Unparseable PETASTORM_TPU_TRACE_SAMPLE=%r; '
                               'tracing every item', raw)
            if stride < 1:
                stride = 1
        _stride = stride
    return _stride


def refresh_trace():
    """Re-read every trace knob (tests, long-lived processes flipping the
    env). Part of :func:`petastorm_tpu.telemetry.refresh`."""
    global _enabled, _stride
    _enabled = None
    _stride = None
    global _autodump_fired, _autodump_last_check
    _autodump_fired = False
    _autodump_last_check = 0.0
    global _hook_installed, _timeline_pid
    _hook_installed = False
    _timeline_pid = None
    spans.set_trace_hook(None)
    # refresh() is a main-thread call in real entry points: the chance to
    # (re)arm the SIGUSR1/atexit dump hooks for a just-set dump path
    _install_dump_hooks()


def _reset_for_tests():
    """Fresh run id + knob caches + deactivated span hook."""
    global _run_id
    refresh_trace()
    _run_id = uuid.uuid4().hex[:8]
    _state.ctx = None
    _state.track = None


# -- context mint / rederivation ---------------------------------------------


def _trace_id(item_seq, epoch):
    return '%s-e%s-i%s' % (_run_id, 0 if epoch is None else epoch, item_seq)


def mint(item_seq, epoch=None, shard=None):
    """Trace context for one ventilated item, or None when tracing is off
    or the item is not sampled. Called by the ventilator, consumer side."""
    if not trace_enabled():
        return None
    if item_seq % sample_stride():
        return None
    return TraceContext(_trace_id(item_seq, epoch), item_seq, epoch, shard)


def ctx_for(item_seq, epoch=None, shard=None):
    """Re-derive the context :func:`mint` produced for ``item_seq`` in the
    SAME process (sampling is deterministic on the sequence number, and
    the trace id is arithmetic over the process run id) — how the
    consumer tags ``queue_wait``/staging events with the trace id minted
    at ventilation without the result path carrying anything extra."""
    if item_seq is None:
        return None
    return mint(item_seq, epoch, shard)


def current_context():
    return getattr(_state, 'ctx', None)


def current_trace_id():
    ctx = current_context()
    return ctx.trace_id if ctx is not None else None


# -- activation ---------------------------------------------------------------


class _NoopActivation:
    """Shared do-nothing context manager for untraced items."""

    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, exc_type, exc_val, exc_tb):
        return False


_NOOP_ACTIVATION = _NoopActivation()


class _Activation:
    __slots__ = ('_ctx', '_track', '_prev')

    def __init__(self, ctx, track):
        self._ctx = ctx
        self._track = track

    def __enter__(self):
        self._prev = (getattr(_state, 'ctx', None),
                      getattr(_state, 'track', None))
        _state.ctx = self._ctx
        _state.track = self._track if self._track is not None \
            else self._prev[1]
        return self

    def __exit__(self, exc_type, exc_val, exc_tb):
        _state.ctx, _state.track = self._prev
        return False


class _Attempt(_Activation):
    """Activation that also records one ``attempt`` complete event — the
    per-worker span covering the whole ``worker.process`` call."""

    __slots__ = ('_t0',)

    def __enter__(self):
        super().__enter__()
        self._t0 = time.perf_counter_ns()
        return self

    def __exit__(self, exc_type, exc_val, exc_tb):
        end = time.perf_counter_ns()
        ctx, track = self._ctx, _state.track
        super().__exit__(exc_type, exc_val, exc_tb)
        # worker rides in args too (not just the track label): consumers
        # of the event list — slowest_items, the benchmark's printout —
        # need it without reconstructing the track interning
        record_complete('attempt', self._t0, end, ctx, track,
                        worker=track,
                        error=exc_type.__name__ if exc_type else None)
        return False


def activate(ctx, track=None):
    """Make ``ctx`` the thread's active trace context for the block: stage
    spans (io/decode/...) executed inside attach their events to it. A
    None ``ctx`` returns a shared no-op."""
    if ctx is None:
        return _NOOP_ACTIVATION
    _ensure_span_hook()
    return _Activation(ctx, track)


def attempt(ctx, worker_label):
    """:func:`activate` plus an ``attempt`` event spanning the block —
    what every pool flavor wraps ``worker.process`` in. ``worker_label``
    becomes the timeline track (one track per worker)."""
    if ctx is None:
        return _NOOP_ACTIVATION
    _ensure_span_hook()
    return _Attempt(ctx, worker_label)


# -- event recording ----------------------------------------------------------


def _ctx_args(ctx, extra):
    args = {'trace_id': ctx.trace_id, 'item': ctx.item_seq}
    if ctx.epoch is not None:
        args['epoch'] = ctx.epoch
    if ctx.shard is not None:
        args['shard'] = ctx.shard
    for key, value in extra.items():
        if value is not None:
            args[key] = value
    return args


def record_complete(name, start_ns, end_ns, ctx=None, track=None, **extra):
    """One Chrome 'X' (complete) event on ``ctx``'s trace, from
    ``start_ns`` to ``end_ns`` of ``time.perf_counter_ns()``; no-op
    without a context."""
    ctx = ctx if ctx is not None else current_context()
    if ctx is None:
        return
    if track is None:
        track = getattr(_state, 'track', None) or 'main'
    get_recorder().add({
        'name': name, 'ph': 'X', 'cat': 'petastorm_tpu',
        'ts': start_ns / 1e3, 'dur': (end_ns - start_ns) / 1e3,
        'pid': os.getpid(), 'tid': track,
        'args': _ctx_args(ctx, extra),
    })


def record_instant(name, ctx, track, **extra):
    """One Chrome 'i' (instant) event — dispatcher lifecycle markers
    (dispatch / reventilate / done / duplicate_done)."""
    if ctx is None:
        return
    get_recorder().add({
        'name': name, 'ph': 'i', 's': 'p', 'cat': 'petastorm_tpu',
        'ts': time.perf_counter_ns() / 1e3,
        'pid': os.getpid(), 'tid': track,
        'args': _ctx_args(ctx, extra),
    })


#: ``cat`` of the events the armed timeline records
TIMELINE_CAT = 'timeline'

# this process's pid while the timeline is armed, else None
_timeline_pid = None


def _span_trace_hook(stage, start_ns, end_ns, cpu_ns, thread):
    """Installed into :mod:`spans` while a trace context has been active in
    this process or the timeline is armed: a span lands a trace event on
    the active item's trace, and every span does while the timeline is
    armed."""
    ctx = getattr(_state, 'ctx', None)
    pid = _timeline_pid
    if pid is None:
        if ctx is not None:
            record_complete(stage, start_ns, end_ns, ctx)
        return
    args = {'cpu_ns': cpu_ns}
    batch = getattr(_state, 'batch', None)
    if batch is not None:
        args['batch'] = batch
    if ctx is not None:
        args = _ctx_args(ctx, args)
    get_recorder().add({
        'name': stage, 'ph': 'X', 'cat': TIMELINE_CAT,
        'ts': start_ns / 1e3, 'dur': (end_ns - start_ns) / 1e3,
        'pid': pid, 'tid': (getattr(_state, 'track', None) if ctx is not None
                            else None) or thread,
        'args': args,
    })


_hook_installed = False


def _ensure_span_hook():
    global _hook_installed
    if not _hook_installed or spans._trace_hook is None:
        spans.set_trace_hook(_span_trace_hook)
        _hook_installed = True


# -- timeline -----------------------------------------------------------------


def tag_batch(seq):
    """Name the loader batch this thread now works for: timeline events of
    its spans carry it as ``args['batch']`` (the staging thread's fill,
    dispatch and decode spans, the consumer's ``loader_next``)."""
    _state.batch = seq


class timeline:
    """Context manager arming the timeline for its block: every stage span
    of this process — not only sampled items — lands in the flight
    recorder as an 'X' event (``cat`` :data:`TIMELINE_CAT`, ``ts``/``dur``
    in µs of ``time.perf_counter_ns()``, ``tid`` the thread's name,
    ``args`` ``cpu_ns`` and, where the thread serves a loader batch,
    ``batch``). Disarmed, the span hook is what it was before (None unless
    per-item tracing is active). Not an environment knob: a profiler run
    arms it around the stretch it traces. Arming does not nest.
    ``start_ns`` / ``end_ns`` bound the block on
    ``time.perf_counter_ns()``."""

    def __init__(self):
        self.start_ns = self.end_ns = None

    def __enter__(self):
        global _timeline_pid
        _timeline_pid = os.getpid()
        spans.set_trace_hook(_span_trace_hook)
        self.start_ns = time.perf_counter_ns()
        return self

    def __exit__(self, exc_type, exc_val, exc_tb):
        global _timeline_pid
        self.end_ns = time.perf_counter_ns()
        _timeline_pid = None
        spans.set_trace_hook(_span_trace_hook if _hook_installed else None)
        return False

    def events(self):
        """The timeline events of the spans of this process that ended in
        the block (a span that began before it included), from the flight
        recorder, oldest first; the ring keeps the most recent
        ``DEFAULT_CAPACITY`` events of every kind."""
        end = self.end_ns if self.end_ns is not None \
            else time.perf_counter_ns()
        lo, hi = self.start_ns / 1e3, end / 1e3
        pid = os.getpid()
        return [e for e in get_recorder().snapshot()
                if e.get('cat') == TIMELINE_CAT and e.get('pid') == pid
                and lo <= e['ts'] + e['dur'] <= hi]


# -- dumps --------------------------------------------------------------------


def dump_trace(path):
    """Export the process-wide flight recorder as Chrome trace-event JSON
    at ``path`` (``Reader.dump_trace`` / ``JaxLoader.dump_trace`` and the
    benchmark's ``--trace-out`` land here). Returns the event count."""
    count = export_chrome_trace(path)
    logger.info('Wrote %d trace event(s) to %s', count, path)
    return count


def _dump_path():
    return knobs.get_str('PETASTORM_TPU_TRACE_DUMP') or None


_atexit_installed = False
_signal_installed = False
_autodump_fired = False
_autodump_last_check = 0.0


def _dump_if_any(signum=None, frame=None):
    path = _dump_path()
    if path and len(get_recorder()):
        try:
            dump_trace(path)
        except Exception:  # noqa: BLE001 - a dump must never crash
            logger.warning('Trace dump to %s failed', path, exc_info=True)


def _install_dump_hooks():
    """Crash-dump plumbing, armed when ``PETASTORM_TPU_TRACE_DUMP`` names
    a path: an ``atexit`` dump plus a SIGUSR1 handler (dump NOW, without
    stopping the run — poke a live job with ``kill -USR1 <pid>``).

    Signal handlers can only be installed from the MAIN thread, and the
    first ``trace_enabled()`` evaluation usually happens on a ventilator
    or staging thread — so this runs once at module import (the package
    import is main-thread in every real entry point) and is retried from
    later main-thread calls; until it lands, an unhandled SIGUSR1 would
    KILL the process, which is why set-at-start is the documented
    contract for ``PETASTORM_TPU_TRACE_DUMP``."""
    global _atexit_installed, _signal_installed
    if _dump_path() is None:
        return
    if not _atexit_installed:
        _atexit_installed = True
        atexit.register(_dump_if_any)
    if not _signal_installed:
        try:
            import signal
            signal.signal(signal.SIGUSR1, _dump_if_any)
            _signal_installed = True
        except (ValueError, OSError, AttributeError):
            # not the main thread, or no SIGUSR1 on this platform: the
            # atexit dump still fires; retried on later main-thread calls
            logger.debug('SIGUSR1 trace-dump handler not installed yet')


_install_dump_hooks()


def autodump_windows():
    return knobs.get_int('PETASTORM_TPU_TRACE_AUTODUMP_WINDOWS', 6, floor=1)


def maybe_autodump():
    """Dump the flight recorder once, automatically, when the stall
    attributor has flagged N consecutive producer-bound windows
    (``PETASTORM_TPU_TRACE_AUTODUMP_WINDOWS``, default 6 ≈ 3s at the
    default window) — the "my TPU is idle" artifact captured from inside
    the run, without re-running. Armed only while tracing is enabled AND
    ``PETASTORM_TPU_TRACE_DUMP`` names a path; throttled to one
    windows-scan per second. Called from the reader's pull path."""
    global _autodump_fired, _autodump_last_check
    if _autodump_fired or not trace_enabled():
        return False
    path = _dump_path()
    if path is None:
        return False
    now = time.monotonic()
    if now - _autodump_last_check < 1.0:
        return False
    _autodump_last_check = now
    from petastorm_tpu.telemetry.stall import PRODUCER_BOUND, get_attributor
    need = autodump_windows()
    windows = get_attributor().windows(include_current=False)[-need:]
    if len(windows) < need or any(w['verdict'] != PRODUCER_BOUND
                                  for w in windows):
        return False
    _autodump_fired = True
    logger.warning('%d consecutive producer-bound windows: auto-dumping '
                   'trace to %s (the pipeline is input-bound; see '
                   'docs/troubleshoot.md)', need, path)
    try:
        dump_trace(path)
    except Exception:  # noqa: BLE001 - telemetry is advisory
        logger.warning('Trace auto-dump to %s failed', path, exc_info=True)
    return True
