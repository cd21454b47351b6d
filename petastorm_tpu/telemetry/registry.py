"""Process-wide metrics registry: counters, gauges, fixed-bucket histograms.

Design constraints (docs/telemetry.md):

* **Hot-path cheap.** One ``threading.Lock`` per metric instance; an
  ``inc``/``observe`` is a lock round-trip plus an add — well under the
  per-call budget ``tests/test_telemetry.py::test_overhead_budget``
  enforces. Metric lookups (``registry.counter(...)``) are dict hits;
  callers on tight loops should still hold the returned object.
* **Thread-safe and pool-mergeable.** Worker processes keep their own
  process-wide registry and ship monotonic deltas back over the existing
  result channels (:meth:`MetricsRegistry.collect_delta` on the worker,
  :meth:`MetricsRegistry.merge_delta` on the consumer); deltas are plain
  dicts of primitives, so any codec the channel already uses can carry
  them.
* **Dependency-free.** stdlib only.
"""

import bisect
import threading
import time

#: user + system CPU seconds of a process, by its ``role``: ``consumer`` for
#: the process that reads (refreshed whenever its registry is snapshotted),
#: ``worker`` for a decode worker process (refreshed just before each delta
#: frame it ships, so its process CPU and its stage CPU arrive together)
PROCESS_CPU = 'petastorm_tpu_process_cpu_seconds_total'
_process_role = 'consumer'


def set_process_role(role):
    """Name the role this process's CPU is counted under
    (:data:`PROCESS_CPU`); a decode worker process calls it once at
    start."""
    global _process_role
    _process_role = role

#: default histogram buckets (seconds): spans from ~0.1ms row-group ops to
#: multi-second stalls; the +Inf bucket is implicit.
DEFAULT_DURATION_BUCKETS = (
    0.0001, 0.00025, 0.0005, 0.001, 0.0025, 0.005, 0.01, 0.025, 0.05,
    0.1, 0.25, 0.5, 1.0, 2.5, 5.0, 10.0,
)


def metric_key(name, labels=None):
    """Canonical string identity of a metric: ``name`` or
    ``name{k="v",...}`` with label keys sorted (promql-style). Used as the
    snapshot/delta dict key, so cross-process merges address the same
    series regardless of label insertion order."""
    if not labels:
        return name
    inner = ','.join('%s="%s"' % (k, _escape_label(str(v)))
                     for k, v in sorted(labels.items()))
    return '%s{%s}' % (name, inner)


def _escape_label(value):
    """Prometheus label-value escaping (backslash, quote, newline)."""
    return (value.replace('\\', '\\\\').replace('"', '\\"')
            .replace('\n', '\\n'))


class Counter:
    """Monotonic float counter."""

    __slots__ = ('_value', '_lock')

    def __init__(self):
        self._value = 0.0
        self._lock = threading.Lock()

    def inc(self, amount=1.0):
        if amount < 0:
            raise ValueError('counters only go up; got %r' % (amount,))
        with self._lock:
            self._value += amount

    @property
    def value(self):
        return self._value


class Gauge:
    """Settable instantaneous value."""

    __slots__ = ('_value', '_lock')

    def __init__(self):
        self._value = 0.0
        self._lock = threading.Lock()

    def set(self, value):
        with self._lock:
            self._value = float(value)

    def inc(self, amount=1.0):
        with self._lock:
            self._value += amount

    def dec(self, amount=1.0):
        self.inc(-amount)

    @property
    def value(self):
        return self._value


class Histogram:
    """Fixed-bucket histogram (cumulative-on-export, per-bucket counts
    internally; the +Inf bucket is the trailing slot)."""

    __slots__ = ('buckets', '_counts', '_sum', '_count', '_lock')

    def __init__(self, buckets=DEFAULT_DURATION_BUCKETS):
        buckets = tuple(float(b) for b in buckets)
        if not buckets or list(buckets) != sorted(set(buckets)):
            raise ValueError('histogram buckets must be strictly ascending; '
                             'got %r' % (buckets,))
        self.buckets = buckets
        self._counts = [0] * (len(buckets) + 1)
        self._sum = 0.0
        self._count = 0
        self._lock = threading.Lock()

    def observe(self, value):
        idx = bisect.bisect_left(self.buckets, value)
        with self._lock:
            self._counts[idx] += 1
            self._sum += value
            self._count += 1

    @property
    def sum(self):
        return self._sum

    @property
    def count(self):
        return self._count

    def state(self):
        """``{'buckets': [...], 'counts': [...], 'sum': s, 'count': n}``
        (per-bucket counts, NOT cumulative — exporters cumulate)."""
        with self._lock:
            return {'buckets': list(self.buckets),
                    'counts': list(self._counts),
                    'sum': self._sum, 'count': self._count}


class MetricsRegistry:
    """Named metrics with optional labels, snapshot/delta/merge support."""

    def __init__(self):
        self._lock = threading.Lock()
        self._counters = {}
        self._gauges = {}
        self._histograms = {}
        # per-key baselines for collect_delta (worker-side flush cursor)
        self._delta_counters = {}
        self._delta_histograms = {}
        self._delta_gauges = {}
        # this process's CPU seconds already counted into PROCESS_CPU
        self._process_cpu_lock = threading.Lock()
        self._process_cpu_seen = 0.0

    # -- metric accessors (create on first use) ------------------------------

    def counter(self, name, **labels):
        key = metric_key(name, labels)
        metric = self._counters.get(key)
        if metric is None:
            with self._lock:
                metric = self._counters.setdefault(key, Counter())
        return metric

    def gauge(self, name, **labels):
        key = metric_key(name, labels)
        metric = self._gauges.get(key)
        if metric is None:
            with self._lock:
                metric = self._gauges.setdefault(key, Gauge())
        return metric

    def histogram(self, name, buckets=DEFAULT_DURATION_BUCKETS, **labels):
        key = metric_key(name, labels)
        metric = self._histograms.get(key)
        if metric is None:
            with self._lock:
                metric = self._histograms.setdefault(key, Histogram(buckets))
        return metric

    # -- read access ----------------------------------------------------------

    def counter_value(self, name, **labels):
        metric = self._counters.get(metric_key(name, labels))
        return metric.value if metric is not None else 0.0

    def gauge_value(self, name, **labels):
        metric = self._gauges.get(metric_key(name, labels))
        return metric.value if metric is not None else 0.0

    def counters_with_prefix(self, prefix):
        """``{key: value}`` of every counter whose key starts with
        ``prefix`` (label'd series of one name share its prefix)."""
        return {k: c.value for k, c in list(self._counters.items())
                if k.startswith(prefix)}

    def gauges_with_prefix(self, prefix):
        return {k: g.value for k, g in list(self._gauges.items())
                if k.startswith(prefix)}

    def refresh_process_cpu(self):
        """Add this process's CPU since the last refresh to
        :data:`PROCESS_CPU` under the process's role. Only the process-wide
        registry counts it, and only with telemetry on."""
        if self is not _global_registry:
            return
        from petastorm_tpu.telemetry.spans import metrics_disabled
        if metrics_disabled():
            return
        used = time.process_time()   # user + system, every thread
        with self._process_cpu_lock:
            spent = used - self._process_cpu_seen
            self._process_cpu_seen = max(used, self._process_cpu_seen)
        if spent > 0:
            self.counter(PROCESS_CPU, role=_process_role).inc(spent)

    def snapshot(self):
        """Full state as a JSON-serializable dict."""
        self.refresh_process_cpu()
        return {
            'counters': {k: c.value for k, c in list(self._counters.items())},
            'gauges': {k: g.value for k, g in list(self._gauges.items())},
            'histograms': {k: h.state()
                           for k, h in list(self._histograms.items())},
        }

    # -- cross-process aggregation -------------------------------------------

    def collect_delta(self):
        """Monotonic increments since the previous ``collect_delta`` call
        (worker-side flush). Counters/histograms ship increments; gauges
        ship their current value (last-writer-wins on merge). Returns None
        when nothing changed — callers piggybacking deltas on existing
        messages can skip the payload entirely."""
        if _process_role == 'worker':
            # a worker's process CPU rides the frame that ships its stage
            # CPU; a consumer's is refreshed by snapshot() alone, so its
            # deltas still read None when nothing else moved
            self.refresh_process_cpu()
        delta = {'counters': {}, 'gauges': {}, 'histograms': {}}
        for key, c in list(self._counters.items()):
            value = c.value
            base = self._delta_counters.get(key, 0.0)
            if value != base:
                delta['counters'][key] = value - base
                self._delta_counters[key] = value
        for key, h in list(self._histograms.items()):
            state = h.state()
            base = self._delta_histograms.get(key)
            if base is None or base['count'] != state['count']:
                if base is None:
                    inc = state
                else:
                    inc = {'buckets': state['buckets'],
                           'counts': [a - b for a, b
                                      in zip(state['counts'],
                                             base['counts'])],
                           'sum': state['sum'] - base['sum'],
                           'count': state['count'] - base['count']}
                delta['histograms'][key] = inc
                self._delta_histograms[key] = state
        for key, g in list(self._gauges.items()):
            value = g.value
            if self._delta_gauges.get(key) != value:
                delta['gauges'][key] = value
                self._delta_gauges[key] = value
        if not (delta['counters'] or delta['gauges'] or delta['histograms']):
            return None
        return delta

    def merge_delta(self, delta):
        """Fold a worker's :meth:`collect_delta` payload into this registry
        (consumer-side aggregate). Safe to call from any thread."""
        if not delta:
            return
        for key, inc in delta.get('counters', {}).items():
            self._counter_by_key(key).inc(inc)
        for key, value in delta.get('gauges', {}).items():
            self._gauge_by_key(key).set(value)
        for key, inc in delta.get('histograms', {}).items():
            hist = self._histogram_by_key(key, inc['buckets'])
            with hist._lock:
                if len(hist._counts) == len(inc['counts']):
                    hist._counts = [a + b for a, b
                                    in zip(hist._counts, inc['counts'])]
                    hist._sum += inc['sum']
                    hist._count += inc['count']
                # mismatched bucket layouts (config drift between worker
                # and consumer builds) drop the histogram increment rather
                # than corrupt the series; counters above still merged

    def _counter_by_key(self, key):
        metric = self._counters.get(key)
        if metric is None:
            with self._lock:
                metric = self._counters.setdefault(key, Counter())
        return metric

    def _gauge_by_key(self, key):
        metric = self._gauges.get(key)
        if metric is None:
            with self._lock:
                metric = self._gauges.setdefault(key, Gauge())
        return metric

    def _histogram_by_key(self, key, buckets):
        metric = self._histograms.get(key)
        if metric is None:
            with self._lock:
                metric = self._histograms.setdefault(
                    key, Histogram(tuple(buckets)))
        return metric


_global_lock = threading.Lock()
_global_registry = None
# callbacks run on reset_registry(): modules caching metric OBJECTS of the
# process-wide registry (spans' per-stage cache) register here so a swap
# can never leave them recording into the replaced instance
_reset_hooks = []


def on_registry_reset(hook):
    _reset_hooks.append(hook)


def get_registry():
    """The process-wide registry every pipeline layer records into. Worker
    processes each have their own (it is per-process by construction); the
    pools merge worker deltas back into the consumer process's one."""
    global _global_registry
    if _global_registry is None:
        with _global_lock:
            if _global_registry is None:
                _global_registry = MetricsRegistry()
    return _global_registry


def reset_registry():
    """Swap in a fresh process-wide registry (test isolation only)."""
    global _global_registry
    with _global_lock:
        _global_registry = MetricsRegistry()
    for hook in _reset_hooks:
        hook()


def dump_delta_frame():
    """The process-wide registry's increments since the previous call,
    dill-framed for a pool's result channel (b'' when nothing changed).
    Telemetry must never fail a completion: errors degrade to b''. The one
    owner of delta framing — the process pool's markers and the service's
    DONE messages both call it.

    Per-item trace events piggyback here too: when the flight recorder
    holds events (worker-side stage/attempt events of traced items), the
    frame carries them under ``trace_events`` with the sender's clock
    (``trace_clock``, :func:`~petastorm_tpu.telemetry.recorder.clock_stamp`)
    and the recorder is drained — the trace layer reuses the metrics'
    channel instead of adding one."""
    import dill
    try:
        delta = get_registry().collect_delta()
        from petastorm_tpu.telemetry.recorder import clock_stamp, get_recorder
        recorder = get_recorder()
        if len(recorder):
            delta = delta or {'counters': {}, 'gauges': {},
                              'histograms': {}}
            delta['trace_events'] = recorder.drain()
            delta['trace_clock'] = clock_stamp()
        return dill.dumps(delta) if delta else b''
    except Exception:  # noqa: BLE001 - telemetry is advisory
        return b''


def load_delta_frame(frame):
    """Inverse of :func:`dump_delta_frame`; None for empty, undecodable,
    or non-delta-shaped frames (a dropped delta loses some gauge
    freshness, nothing more — it must never take a data channel down).

    The shape check is strict — EXACTLY the three delta keys (plus an
    optional ``trace_events`` LIST and its ``trace_clock``), the three all
    dicts, at least one of
    the fields non-empty — because the service dispatcher uses it to
    tell a metrics frame from a result frame sent by a pre-telemetry
    worker build (the wire has no version marker); a permissive check
    would let arbitrary pickled results masquerade as deltas and vanish."""
    if not frame:
        return None
    import dill
    try:
        delta = dill.loads(frame)
    except Exception:  # noqa: BLE001 - telemetry is advisory
        return None
    if not isinstance(delta, dict):
        return None
    base_keys = {'counters', 'gauges', 'histograms'}
    if set(delta) not in (base_keys, base_keys | {'trace_events'},
                          base_keys | {'trace_events', 'trace_clock'}):
        return None
    if not all(isinstance(delta[k], dict) for k in base_keys):
        return None
    if not isinstance(delta.get('trace_events', []), list):
        return None
    if not any(delta.values()):
        return None
    return delta


def merge_worker_delta(delta):
    """Consumer-side entry point for a delta that arrived over a pool's
    result channel: fold it into the process-wide registry AND replay its
    stall-wait increments into the process-wide attributor, so remote
    producer-side back-pressure participates in window classification.
    Never raises (telemetry is advisory; callers sit on data paths)."""
    if not delta:
        return
    try:
        _merge_worker_delta(delta)
    except Exception:  # noqa: BLE001 - telemetry is advisory
        import logging
        logging.getLogger(__name__).debug('Dropping unmergeable metrics '
                                          'delta', exc_info=True)


def _merge_worker_delta(delta):
    get_registry().merge_delta(delta)
    events = delta.get('trace_events')
    if events:
        # a remote worker's flight-recorder batch: fold it into THIS
        # process's recorder, where the whole distributed timeline
        # accumulates for export (dump_trace / --trace-out)
        from petastorm_tpu.telemetry.recorder import get_recorder, rebase
        get_recorder().add_many(rebase(
            [e for e in events if isinstance(e, dict)],
            delta.get('trace_clock')))
    counters = delta.get('counters', {})
    # import here: registry must stay importable before the package's
    # __init__ finishes binding the sibling modules
    from petastorm_tpu.telemetry import (
        STALL_CONSUMER_WAIT, STALL_PRODUCER_WAIT,
    )
    from petastorm_tpu.telemetry.stall import get_attributor
    producer = counters.get(STALL_PRODUCER_WAIT, 0.0)
    consumer = counters.get(STALL_CONSUMER_WAIT, 0.0)
    if producer > 0.0:
        get_attributor().note_producer_wait(producer)
    if consumer > 0.0:
        get_attributor().note_consumer_wait(consumer)
