"""Flight recorder: a bounded, lock-light per-process trace-event buffer.

Every process on the pipeline (consumer, ZMQ decode workers, service
worker servers) holds one ring of recent trace events
(:func:`get_recorder`). Worker processes drain theirs into the metrics
delta frames that already ride the pool result channels
(:func:`~petastorm_tpu.telemetry.registry.dump_delta_frame`), so by the
time anyone asks for a dump the CONSUMER's ring holds the whole
distributed timeline — bounded, always-on once tracing is enabled, and
exportable after the fact: the "why was my TPU idle two minutes ago"
artifact without re-running anything.

Events are plain dicts already shaped like Chrome trace events
(https://docs.google.com/document/d/1CvAClvFfyA5R-PhYUmn5OOQtYMH4h6I0nSsKchNAySU):
``{'name', 'ph', 'ts', 'dur', 'pid', 'tid', 'args'}`` with ``ts``/``dur``
in microseconds and ``tid`` a TRACK LABEL string (e.g.
``worker-3``/``consumer``). ``ts`` is on this process's monotonic clock
(``time.perf_counter_ns()``, the clock the stage spans and the timeline
stamp): a worker's events are moved onto it when they arrive
(:func:`rebase`; a no-op from the same host, whose monotonic clock is
shared). :func:`export_chrome_trace` converts to wall time through the
offset each process reads once (:data:`CLOCK_OFFSET_US`), so events from
different hosts land on one comparable timeline, and interns labels to
integer tids with ``thread_name`` metadata, giving Perfetto one named
track per worker/stage.
"""

import collections
import json
import socket
import threading
import time

#: default ring capacity (events per process); at ~10 events per row-group
#: this covers the most recent ~2k items — minutes of timeline at
#: production rates, a few MB of small dicts
DEFAULT_CAPACITY = 20000


#: this process's wall clock minus its monotonic clock, in µs, read once
CLOCK_OFFSET_US = time.time() * 1e6 - time.perf_counter_ns() / 1e3
_HOST = socket.gethostname()


def clock_stamp():
    """What a worker sends beside its events so the receiver can put them
    on its own clock: ``[host, CLOCK_OFFSET_US]``."""
    return [_HOST, CLOCK_OFFSET_US]


def rebase(events, stamp):
    """Move ``events`` recorded by the process that sent ``stamp`` onto
    this process's monotonic clock, in place. Events from this host are on
    it already; from another host they shift by the two processes' wall
    offsets. A missing ``stamp`` marks a sender that stamped wall time."""
    if stamp is None:
        shift = -CLOCK_OFFSET_US
    else:
        host, offset = stamp
        shift = 0.0 if host == _HOST else offset - CLOCK_OFFSET_US
    if shift:
        for event in events:
            if 'ts' in event:
                event['ts'] += shift
    return events


class FlightRecorder:
    """Bounded ring of trace events.

    Lock-light by construction: ``deque.append`` with a ``maxlen`` is a
    single atomic operation under the GIL, so the hot path (``add``) takes
    no lock at all; only the cold paths (``drain``, ``snapshot``) lock to
    get a consistent cut against concurrent appends.
    """

    def __init__(self, capacity=DEFAULT_CAPACITY):
        self._events = collections.deque(maxlen=capacity)
        self._lock = threading.Lock()

    def add(self, event):
        self._events.append(event)

    def add_many(self, events):
        self._events.extend(events)

    def __len__(self):
        return len(self._events)

    def snapshot(self):
        """All buffered events, oldest first (the ring keeps them)."""
        with self._lock:
            return list(self._events)

    def drain(self):
        """Pop every buffered event (worker-side flush: the batch ships on
        the pool's delta channel and must not ship twice)."""
        with self._lock:
            events = list(self._events)
            self._events.clear()
        return events

    def clear(self):
        with self._lock:
            self._events.clear()


_global_lock = threading.Lock()
_global_recorder = None


def get_recorder():
    """The process-wide flight recorder trace events accumulate in."""
    global _global_recorder
    if _global_recorder is None:
        with _global_lock:
            if _global_recorder is None:
                _global_recorder = FlightRecorder()
    return _global_recorder


def reset_recorder():
    """Swap in a fresh process-wide recorder (test isolation only)."""
    global _global_recorder
    with _global_lock:
        _global_recorder = FlightRecorder()


# -- Chrome trace-event export ------------------------------------------------


def export_chrome_trace(path_or_file, events=None):
    """Write ``events`` (default: the process-wide recorder's snapshot) as
    Chrome trace-event JSON, viewable in Perfetto (ui.perfetto.dev) or
    ``chrome://tracing``, with ``ts`` in µs of wall time.

    Track-label ``tid`` strings are interned to integers per ``pid`` and
    announced with ``thread_name`` metadata events, so the viewer shows one
    named track per worker/stage. Returns the number of data events
    written."""
    if events is None:
        events = get_recorder().snapshot()
    tids = {}          # (pid, label) -> int tid
    out = []
    for event in events:
        pid = event.get('pid', 0)
        label = str(event.get('tid', 'main'))
        tid = tids.get((pid, label))
        if tid is None:
            tid = tids[(pid, label)] = len(tids) + 1
        record = dict(event, pid=pid, tid=tid)
        if 'ts' in record:
            record['ts'] = record['ts'] + CLOCK_OFFSET_US
        out.append(record)
    meta = [{'name': 'thread_name', 'ph': 'M', 'pid': pid, 'tid': tid,
             'args': {'name': label}}
            for (pid, label), tid in sorted(tids.items(),
                                            key=lambda kv: kv[1])]
    doc = {'traceEvents': meta + out, 'displayTimeUnit': 'ms'}
    if hasattr(path_or_file, 'write'):
        json.dump(doc, path_or_file)
    else:
        with open(path_or_file, 'w') as f:
            json.dump(doc, f)
    return len(out)


def slowest_items(events=None, n=3):
    """The ``n`` traces with the largest summed worker-side duration —
    "which row-groups were slow", straight off the recorder.

    Sums ``dur`` over complete (``ph == 'X'``) ``attempt`` events per
    trace id (one per worker-side processing of one ventilated item);
    when no attempt events exist (e.g. thread-pool runs before any pool
    wiring) it falls back to summing every complete event of the trace.
    Returns ``[(trace_id, seconds, last_args), ...]`` slowest first."""
    if events is None:
        events = get_recorder().snapshot()
    totals = {}
    args_by_id = {}
    have_attempts = any(e.get('name') == 'attempt' and e.get('ph') == 'X'
                        for e in events)
    for event in events:
        if event.get('ph') != 'X':
            continue
        if have_attempts and event.get('name') != 'attempt':
            continue
        trace_id = (event.get('args') or {}).get('trace_id')
        if trace_id is None:
            continue
        totals[trace_id] = totals.get(trace_id, 0.0) + event.get('dur', 0.0)
        args_by_id[trace_id] = event.get('args') or {}
    ranked = sorted(totals.items(), key=lambda kv: -kv[1])[:n]
    return [(tid, dur / 1e6, args_by_id[tid]) for tid, dur in ranked]
