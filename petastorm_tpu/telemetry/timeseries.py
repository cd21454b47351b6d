"""Windowed time-series rollups + anomaly detection over the registry.

The live half of the telemetry subsystem (docs/telemetry.md, "Live
observability plane"). Everything telemetry exported before this module
was post-hoc — ``pipeline_report()`` at end of run, JSONL/Prometheus
*file* snapshots — while the operational questions ("did throughput just
collapse?", "is the fleet flapping between producer- and
consumer-bound?") need *windowed rates observable while the job runs*.
The tf.data service paper (Audibert et al., 2022) makes exactly this
case: disaggregated input processing is only operable with continuous
per-worker/per-job visibility.

Three pieces, all stdlib-only:

* :class:`WindowedRollup` — a bounded ring of fixed-width windows over
  the process-wide :class:`~petastorm_tpu.telemetry.registry
  .MetricsRegistry`. Each closed window carries per-counter **rates**,
  per-histogram **p50/p95/p99** (from the existing fixed buckets'
  count increments), current gauges, the window's producer/consumer wait
  deltas and the stall **verdict** they classify to. Because the pools
  merge remote worker deltas into the same registry (process-pool
  markers, service DONE messages), a consumer-side rollup absorbs the
  whole fleet's increments without any extra channel.
* :class:`ObsCollector` — the sampler: one daemon thread per process
  that closes a window every ``PETASTORM_TPU_OBS_WINDOW_SEC`` and feeds
  it to the detector. Created ONLY when the observability plane is armed
  (``PETASTORM_TPU_OBS_PORT`` set and metrics on): with the knob unset
  or ``PETASTORM_TPU_METRICS=0`` no thread ever starts
  (``tests/test_obs.py`` asserts this structurally).
* :class:`AnomalyDetector` — consumes the window stream and emits the
  canonical structured events of
  :data:`petastorm_tpu.analysis.contracts.ANOMALY_KINDS`
  (``throughput_collapse``, ``stall_flap``, ``queue_saturated``,
  ``heartbeat_gap``, ``h2d_starvation``), each naming its
  docs/troubleshoot.md runbook. Events land in a bounded in-process
  ring, the ``petastorm_tpu_anomaly_events_total{kind=…}`` counter (so
  worker-side events aggregate fleet-wide over the existing delta
  channels), ``pipeline_report()['anomalies']`` and the JSONL exporter.

:class:`HeartbeatSummarizer` is the thread-free sibling for service
worker servers: called once per heartbeat, it returns the worker's
headline counter rates since the previous call, so the dispatcher's
endpoint can serve a per-worker fleet breakdown without the worker
needing its own sampler thread.
"""

import collections
import logging
import os
import threading
import time

from petastorm_tpu.analysis.contracts import ANOMALY_KINDS
from petastorm_tpu.telemetry import knobs
from petastorm_tpu.telemetry.registry import get_registry, metric_key
from petastorm_tpu.telemetry.spans import (
    STAGE_CALLS, STAGE_SECONDS, metrics_disabled,
)
from petastorm_tpu.telemetry.stall import (
    CONSUMER_BOUND, PRODUCER_BOUND, classify_window,
)

logger = logging.getLogger(__name__)

#: anomaly events per kind; worker processes' increments ride the pool
#: delta channels, so the consumer-side counter is the fleet aggregate
ANOMALY_EVENTS = 'petastorm_tpu_anomaly_events_total'
#: rollup windows closed by this process's sampler (sampler liveness)
OBS_WINDOWS = 'petastorm_tpu_obs_windows_total'

_DEFAULT_WINDOW_SEC = 1.0
_DEFAULT_WINDOWS = 120

# the two stall wait-clock counters (telemetry/__init__.py defines the
# same literals; re-importing the package root here would be circular)
_PRODUCER_WAIT = 'petastorm_tpu_stall_producer_wait_seconds_total'
_CONSUMER_WAIT = 'petastorm_tpu_stall_consumer_wait_seconds_total'
# service fleet-health series (mirrored by the dispatcher; see
# service/dispatcher.py — canonical members of contracts.METRIC_NAMES)
_SERVICE_ALIVE = 'petastorm_tpu_service_workers_alive'
_SERVICE_REGISTERED = 'petastorm_tpu_service_workers_registered'
_SERVICE_REVENTILATED = 'petastorm_tpu_service_reventilated_total'

#: events kept in the in-process ring (oldest dropped)
_EVENT_RING_CAPACITY = 200

#: throughput proxy, in priority order: result pulls (one per row-group
#: batch reaching the consumer), then worker-side decode/io calls (the
#: only rate a worker-server process sees locally)
_THROUGHPUT_KEYS = (
    metric_key(STAGE_CALLS, {'stage': 'queue_wait'}),
    metric_key(STAGE_CALLS, {'stage': 'decode'}),
    metric_key(STAGE_CALLS, {'stage': 'io'}),
)


def window_sec():
    return knobs.get_float('PETASTORM_TPU_OBS_WINDOW_SEC',
                           _DEFAULT_WINDOW_SEC, floor=0.05)


def max_windows():
    return knobs.get_int('PETASTORM_TPU_OBS_WINDOWS', _DEFAULT_WINDOWS,
                         floor=2)


def obs_enabled():
    """The observability plane's arming condition: an
    ``PETASTORM_TPU_OBS_PORT`` value is present AND metrics are on."""
    return (not metrics_disabled()
            and knobs.get_str('PETASTORM_TPU_OBS_PORT') != '')


_H2D_READY_KEY = metric_key(STAGE_SECONDS, {'stage': 'h2d_ready'})


def h2d_ready_share(window):
    """Seconds-per-second one closed window spent blocked in the staging
    arena's ``h2d_ready`` gate — the h2d-starvation signal, defined ONCE
    here for both consumers: the anomaly detector's ``h2d_starvation``
    event and the staging autotuner's deepen policy
    (:mod:`petastorm_tpu.jax.autotune`)."""
    return window['rates'].get(_H2D_READY_KEY, 0.0)


_IO_SECONDS_KEY = metric_key(STAGE_SECONDS, {'stage': 'io'})


def io_wait_share(window):
    """Seconds-per-second one closed window spent inside the blocking
    ``io`` stage (fleet-merged: worker increments ride the pool delta
    channels, so the share can exceed 1.0 across parallel workers) —
    the io-starvation signal the staging autotuner's readahead-deepen
    policy reads, defined ONCE here next to its h2d sibling."""
    return window['rates'].get(_IO_SECONDS_KEY, 0.0)


_DECODE_FUSED_KEY = metric_key(STAGE_SECONDS, {'stage': 'decode_fused'})


def decode_fused_share(window):
    """Seconds-per-second one closed window spent in the staging fill's
    ``decode_fused`` stage (the consumer decoding encoded cells straight
    into arena slots; 0.0 where nothing fused) — the staging autotuner's
    floor for shedding decoder threads (:mod:`petastorm_tpu.jax.
    autotune`)."""
    return window['rates'].get(_DECODE_FUSED_KEY, 0.0)


# -- windowed rollup ----------------------------------------------------------


def _quantiles(buckets, count_deltas):
    """p50/p95/p99 upper-bound estimates from one window's per-bucket
    count increments (prometheus-style: the quantile is the bound of the
    bucket the cumulative count crosses in; the +Inf bucket clamps to the
    largest finite bound)."""
    total = sum(count_deltas)
    if total <= 0:
        return None
    out = {}
    for label, q in (('p50', 0.5), ('p95', 0.95), ('p99', 0.99)):
        target = q * total
        cumulative = 0
        for i, count in enumerate(count_deltas):
            cumulative += count
            if cumulative >= target:
                out[label] = buckets[min(i, len(buckets) - 1)]
                break
    return out


class WindowedRollup:
    """Bounded ring of fixed-width windows over registry snapshots.

    Feed it full ``registry.snapshot()`` dicts (:meth:`sample`); each
    call after the first closes one window holding the rates/quantiles/
    verdict of the interval since the previous sample. Thread-safe: the
    sampler thread writes, scrape handlers read.
    """

    def __init__(self, max_windows=_DEFAULT_WINDOWS):
        self._lock = threading.Lock()
        self._windows = collections.deque(maxlen=max_windows)
        self._prev = None
        self._prev_t = None
        self._prev_wall = None
        self._closed_total = 0

    def sample(self, snapshot, now=None, wall=None):
        """Close one window against the previous sample; the first call
        primes the baseline and returns None."""
        now = time.monotonic() if now is None else now
        wall = time.time() if wall is None else wall
        with self._lock:
            prev, prev_t, prev_wall = self._prev, self._prev_t, \
                self._prev_wall
            self._prev, self._prev_t, self._prev_wall = snapshot, now, wall
            if prev is None:
                return None
            dur = now - prev_t
            if dur <= 0:
                return None
            window = self._close(prev, snapshot, prev_wall, dur)
            self._windows.append(window)
            self._closed_total += 1
            return window

    @staticmethod
    def _close(prev, snap, start_wall, dur):
        prev_counters = prev.get('counters', {})
        rates = {}
        for key, value in snap.get('counters', {}).items():
            delta = value - prev_counters.get(key, 0.0)
            if delta > 0:
                rates[key] = round(delta / dur, 6)
        quantiles = {}
        prev_hists = prev.get('histograms', {})
        for key, state in snap.get('histograms', {}).items():
            base = prev_hists.get(key)
            if base is None:
                deltas = state['counts']
            elif len(base['counts']) == len(state['counts']):
                deltas = [a - b for a, b in zip(state['counts'],
                                                base['counts'])]
            else:
                continue  # bucket-layout drift: skip rather than corrupt
            q = _quantiles(state['buckets'], deltas)
            if q is not None:
                quantiles[key] = q
        producer_wait = max(
            0.0, snap.get('counters', {}).get(_PRODUCER_WAIT, 0.0)
            - prev_counters.get(_PRODUCER_WAIT, 0.0))
        consumer_wait = max(
            0.0, snap.get('counters', {}).get(_CONSUMER_WAIT, 0.0)
            - prev_counters.get(_CONSUMER_WAIT, 0.0))
        throughput = None
        for key in _THROUGHPUT_KEYS:
            if key in rates:
                throughput = rates[key]
                break
        return {
            'start': start_wall,
            'dur_s': round(dur, 4),
            'rates': rates,
            'quantiles': quantiles,
            'gauges': dict(snap.get('gauges', {})),
            'producer_wait_s': round(producer_wait, 6),
            'consumer_wait_s': round(consumer_wait, 6),
            'verdict': classify_window(producer_wait, consumer_wait, dur),
            'throughput': throughput,
        }

    def windows(self, last_n=None):
        with self._lock:
            out = list(self._windows)
        return out[-last_n:] if last_n is not None else out

    @property
    def closed_total(self):
        return self._closed_total


# -- anomaly events -----------------------------------------------------------


_events_lock = threading.Lock()
_events = collections.deque(maxlen=_EVENT_RING_CAPACITY)


def record_anomaly(kind, detail=None, window_start=None):
    """Record one structured anomaly event: bounded in-process ring +
    the ``petastorm_tpu_anomaly_events_total{kind=…}`` counter (which the
    pool delta channels aggregate fleet-wide). ``kind`` must be a member
    of :data:`~petastorm_tpu.analysis.contracts.ANOMALY_KINDS`; the
    event carries its runbook heading so an operator reading a raw
    JSONL/endpoint dump knows where to go next."""
    if kind not in ANOMALY_KINDS:
        raise ValueError('Unknown anomaly kind %r; register it in '
                         'analysis/contracts.py ANOMALY_KINDS' % (kind,))
    event = {
        'kind': kind,
        'ts': time.time(),
        'window_start': window_start,
        'detail': dict(detail or {}),
        'runbook': 'docs/troubleshoot.md — "%s"' % ANOMALY_KINDS[kind],
    }
    with _events_lock:
        _events.append(event)
    if not metrics_disabled():
        get_registry().counter(ANOMALY_EVENTS, kind=kind).inc()
    logger.warning('Pipeline anomaly %s: %s (see %s)', kind,
                   event['detail'], event['runbook'])
    from petastorm_tpu.telemetry import obslog
    if obslog.log_dir() is not None:
        # every anomaly source funnels through here (the detector, the
        # SLO plane, the service dispatcher), so this is the one spot
        # that guarantees the flight log sees them all; the log line's
        # 'kind' field is the record type, the anomaly's own kind moves
        # to 'anomaly'
        rec = dict(event)
        rec['anomaly'] = rec.pop('kind', None)
        obslog.append('anomaly', rec)
    return event


def recent_anomalies(last_n=20):
    """The most recent structured anomaly events (oldest first)."""
    with _events_lock:
        out = list(_events)
    return out[-last_n:]


def anomaly_counts():
    """``{kind: n}`` of ring-resident events (this process only; the
    registry counter holds the fleet-wide totals)."""
    counts = {}
    with _events_lock:
        for event in _events:
            counts[event['kind']] = counts.get(event['kind'], 0) + 1
    return counts


class AnomalyDetector:
    """Window-stream consumer emitting the canonical anomaly events.

    Detections (thresholds from knobs, docs/env_knobs.md):

    * ``throughput_collapse`` — the throughput proxy fell below
      ``PETASTORM_TPU_OBS_COLLAPSE_FRAC`` of its trailing mean for 2+
      consecutive windows while the consumer was still actively waiting
      (so a finished stream never reads as a collapse).
    * ``stall_flap`` — the per-window stall verdict flipped between
      producer- and consumer-bound ``PETASTORM_TPU_OBS_FLAP_FLIPS``+
      times within the recent horizon.
    * ``queue_saturated`` — producer wait held ≥
      ``PETASTORM_TPU_OBS_SATURATED_SHARE`` of 3 consecutive windows:
      the consumer is the wall and back-pressure has quiesced the
      producers.
    * ``heartbeat_gap`` — service workers fell out of the liveness
      window (``workers_alive`` < ``workers_registered``) or items were
      re-ventilated this window.
    * ``h2d_starvation`` — the staging arena spent ≥ the saturation
      share of 3 consecutive windows blocked in ``h2d_ready``: the
      host→device link itself is starving the device.

    Each detection is edge-triggered with hysteresis: one event when the
    condition establishes, re-armed only after it clears — a persistent
    condition cannot flood the ring.
    """

    _FLAP_HORIZON = 8
    _TRAILING = 6
    _CONSECUTIVE = 3
    _COLLAPSE_CONSECUTIVE = 2
    #: a collapse verdict needs a trailing mean at least this high
    #: (windows/sec) — idle pipelines have nothing to collapse from
    _MIN_THROUGHPUT = 1.0
    #: consumer must still be waiting this share of the window for a
    #: throughput drop to count as a collapse (vs a finished stream)
    _COLLAPSE_WAIT_SHARE = 0.05

    #: consecutive non-bound (balanced/idle) windows after which the
    #: flap horizon resets — without this, a frozen verdict deque would
    #: keep an old flap episode "active" across an arbitrarily long calm
    #: stretch and swallow the next genuine episode's edge
    _CALM_RESET = 4

    def __init__(self, emit=None):
        self._emit = emit or record_anomaly
        self.reload_thresholds()
        self._throughputs = collections.deque(maxlen=self._TRAILING)
        self._verdicts = collections.deque(maxlen=self._FLAP_HORIZON)
        self._sat_streak = 0
        self._h2d_streak = 0
        self._collapse_streak = 0
        self._calm_streak = 0
        self._active = set()

    def reload_thresholds(self):
        """Re-read the threshold knobs IN PLACE (``telemetry.refresh()``
        lands here): hysteresis/streak state survives, so a refresh
        mid-condition cannot re-fire an already-active anomaly."""
        self._collapse_frac = knobs.get_float(
            'PETASTORM_TPU_OBS_COLLAPSE_FRAC', 0.3, floor=0.01)
        self._saturated_share = knobs.get_float(
            'PETASTORM_TPU_OBS_SATURATED_SHARE', 0.5, floor=0.05)
        self._flap_flips = knobs.get_int(
            'PETASTORM_TPU_OBS_FLAP_FLIPS', 3, floor=2)

    def observe(self, window):
        """Feed one closed window; emits any newly-established anomaly
        events and returns them."""
        events = []
        dur = max(window.get('dur_s') or 0.0, 1e-9)
        events += self._check_saturation(window, dur)
        events += self._check_h2d(window, dur)
        events += self._check_collapse(window, dur)
        events += self._check_flap(window)
        events += self._check_heartbeat(window)
        return events

    # -- per-kind checks (edge-triggered via the _active set) ----------------

    def _fire(self, kind, window, active, detail):
        """Hysteresis core: emit only on the inactive→active edge."""
        if not active:
            self._active.discard(kind)
            return []
        if kind in self._active:
            return []
        self._active.add(kind)
        return [self._emit(kind, detail=detail,
                           window_start=window.get('start'))]

    def _check_saturation(self, window, dur):
        share = window.get('producer_wait_s', 0.0) / dur
        saturated = share >= self._saturated_share
        self._sat_streak = self._sat_streak + 1 if saturated else 0
        return self._fire(
            'queue_saturated', window,
            self._sat_streak >= self._CONSECUTIVE,
            {'producer_wait_share': round(share, 4),
             'threshold': self._saturated_share,
             'windows': self._sat_streak})

    def _check_h2d(self, window, dur):
        share = h2d_ready_share(window)  # seconds/sec
        starved = share >= self._saturated_share
        self._h2d_streak = self._h2d_streak + 1 if starved else 0
        return self._fire(
            'h2d_starvation', window,
            self._h2d_streak >= self._CONSECUTIVE,
            {'h2d_ready_share': round(share, 4),
             'threshold': self._saturated_share,
             'windows': self._h2d_streak})

    def _check_collapse(self, window, dur):
        throughput = window.get('throughput')
        trailing = list(self._throughputs)
        collapsed = False
        mean = 0.0
        if len(trailing) >= 3:
            mean = sum(trailing) / len(trailing)
            wait_share = window.get('consumer_wait_s', 0.0) / dur
            collapsed = (mean >= self._MIN_THROUGHPUT
                         and (throughput or 0.0)
                         < self._collapse_frac * mean
                         and wait_share >= self._COLLAPSE_WAIT_SHARE)
        self._collapse_streak = self._collapse_streak + 1 if collapsed \
            else 0
        events = self._fire(
            'throughput_collapse', window,
            self._collapse_streak >= self._COLLAPSE_CONSECUTIVE,
            {'throughput': round(throughput or 0.0, 3),
             'trailing_mean': round(mean, 3),
             'threshold_frac': self._collapse_frac})
        # collapsed windows stay OUT of the trailing mean — otherwise a
        # sustained collapse drags the baseline down to itself and the
        # condition self-clears while the pipeline is still stalled
        if throughput is not None and not collapsed:
            self._throughputs.append(throughput)
        return events

    def _check_flap(self, window):
        verdict = window.get('verdict')
        if verdict in (PRODUCER_BOUND, CONSUMER_BOUND):
            self._verdicts.append(verdict)
            self._calm_streak = 0
        else:
            # a sustained calm stretch ends the episode: drop the frozen
            # verdict history so the NEXT flap re-triggers as a fresh
            # inactive->active edge
            self._calm_streak += 1
            if self._calm_streak >= self._CALM_RESET:
                self._verdicts.clear()
        flips = sum(1 for a, b in zip(list(self._verdicts),
                                      list(self._verdicts)[1:])
                    if a != b)
        return self._fire(
            'stall_flap', window, flips >= self._flap_flips,
            {'flips': flips, 'horizon': len(self._verdicts),
             'threshold': self._flap_flips})

    def _check_heartbeat(self, window):
        gauges = window.get('gauges', {})
        alive = gauges.get(_SERVICE_ALIVE)
        registered = gauges.get(_SERVICE_REGISTERED, 0)
        reventilated = window['rates'].get(_SERVICE_REVENTILATED, 0.0)
        gap = bool(reventilated) or (alive is not None and registered
                                     and alive < registered)
        return self._fire(
            'heartbeat_gap', window, gap,
            {'workers_alive': alive, 'workers_registered': registered,
             'reventilated_per_s': round(reventilated, 3)})


# -- the sampler --------------------------------------------------------------


class ObsCollector:
    """One daemon sampler thread: snapshot → rollup window → detector.

    Because the snapshot reads the process-wide registry — which the
    pools' delta merges already fold remote worker increments into —
    each window absorbs the cross-process merges for free.
    """

    def __init__(self, window_s=None, windows=None, detector=None):
        self.window_s = window_s or window_sec()
        self.rollup = WindowedRollup(windows or max_windows())
        self.detector = detector or AnomalyDetector()
        self._stop = threading.Event()
        self._thread = None
        self._ticks = 0

    def start(self):
        if self._thread is not None:
            return
        self._thread = threading.Thread(
            target=self._run, daemon=True, name='petastorm-tpu-obs-sampler')
        self._thread.start()

    def _run(self):
        while not self._stop.wait(self.window_s):
            try:
                self.tick()
            except Exception:  # noqa: BLE001 - observability is advisory
                logger.debug('Rollup tick failed', exc_info=True)

    #: one critical-path digest lands in the flight log every N ticks —
    #: the sweep over the recorder is the plane's priciest analysis and
    #: per-tick it would eat the <2% overhead budget the bench gates
    _CRITPATH_EVERY = 30

    def tick(self):
        """One sampling step (the thread's body; callable directly from
        tests). get_registry() is re-resolved per tick so a test-reset
        registry swap is picked up instead of sampling a dead one.

        Each closed window additionally flows through the SLO policy
        (when ``PETASTORM_TPU_SLO`` arms one) and — with
        ``PETASTORM_TPU_OBS_LOG_DIR`` set — into the on-disk black box:
        the window itself, any anomalies it raised, the SLO verdicts and
        a periodic critical-path digest."""
        from petastorm_tpu.telemetry import obslog, slo
        window = self.rollup.sample(get_registry().snapshot())
        if window is None:
            return None
        if not metrics_disabled():
            get_registry().counter(OBS_WINDOWS).inc()
        self.detector.observe(window)
        verdict = slo.observe_window(window)
        self._ticks += 1
        if obslog.log_dir() is not None:
            # anomalies (the detector's `events` included) reach the log
            # via record_anomaly itself — every source funnels there
            obslog.append('window', dict(window))
            if verdict is not None:
                obslog.append('slo', dict(verdict))
            if self._ticks % self._CRITPATH_EVERY == 0:
                from petastorm_tpu.telemetry import critpath
                digest = critpath.analyze()
                if digest is not None:
                    digest.pop('stages', None)
                    obslog.append('critpath', digest)
        return window

    def reload_config(self):
        """Re-read the window length and detector thresholds
        (``telemetry.refresh()`` lands here via ``refresh_obs``). The
        detector object is kept — its hysteresis/streak state must
        survive a knob refresh or an active condition would re-fire."""
        self.window_s = window_sec()
        self.detector.reload_thresholds()

    def stop(self):
        self._stop.set()
        if self._thread is not None:
            self._thread.join(timeout=5)
            self._thread = None


_collector_lock = threading.Lock()
_collector = None


def ensure_collector():
    """Start the process-wide sampler if the plane is armed; returns the
    collector or None. The one constructor path — nothing else may start
    observability threads, which is what makes the disabled case
    structurally thread-free."""
    global _collector
    if not obs_enabled():
        return None
    if _collector is None:
        with _collector_lock:
            if _collector is None:
                collector = ObsCollector()
                collector.start()
                _collector = collector
    return _collector


def collector_running():
    return _collector is not None


def rollup_section(last_n=12):
    """The live rollup view the ``/report`` endpoint serves: headline
    (latest throughput/verdict, totals) plus the last ``last_n`` compact
    windows. None when no collector runs in this process."""
    collector = _collector
    if collector is None:
        return None
    windows = collector.rollup.windows()
    last = windows[-1] if windows else None
    headline = {
        'window_s': collector.window_s,
        'windows_sampled': collector.rollup.closed_total,
        'throughput_per_s': (last or {}).get('throughput'),
        'verdict': (last or {}).get('verdict'),
        'anomaly_counts': anomaly_counts(),
    }
    return {
        'window_s': collector.window_s,
        'headline': headline,
        'windows': windows[-last_n:],
    }


def refresh_obs():
    """Re-read every cached observability knob (hooked into
    ``telemetry.refresh()``): live collector reloads its window length
    and detector thresholds; arming/port changes take effect at the next
    mount (the HTTP server binds once per process)."""
    collector = _collector
    if collector is not None:
        collector.reload_config()
    from petastorm_tpu.telemetry import obslog, slo
    slo.refresh_slo()
    obslog.refresh_obslog()


def _reset_for_tests():
    """Stop the sampler and drop the event ring (test isolation only)."""
    global _collector
    with _collector_lock:
        collector, _collector = _collector, None
    if collector is not None:
        collector.stop()
    with _events_lock:
        _events.clear()


# -- worker-server heartbeat summaries ---------------------------------------


class HeartbeatSummarizer:
    """Thread-free per-worker rollup for the service heartbeat channel.

    A worker server calls :meth:`summary` once per heartbeat; the result
    (a small JSON-safe dict: pid/uptime + per-second rates of the
    counters that moved since the previous heartbeat + local anomaly
    counts) piggybacks on the HEARTBEAT frame, and the dispatcher's
    endpoint serves it as the per-worker fleet breakdown. No sampler
    thread is involved — the serve loop's own cadence is the window.
    """

    #: at most this many rate series ride one heartbeat (the busiest
    #: first); the wire frame stays O(1KB) regardless of label explosion
    _MAX_RATES = 24

    def __init__(self, worker_id=None):
        self._worker_id = worker_id
        self._t0 = time.monotonic()
        self._prev = None
        self._prev_t = None

    def summary(self, obs_port=None):
        out = {'pid': os.getpid(),
               'uptime_s': round(time.monotonic() - self._t0, 1)}
        if self._worker_id is not None:
            out['worker_id'] = self._worker_id
        if obs_port:
            out['obs_port'] = obs_port
        if metrics_disabled():
            return out
        # counters only — a full snapshot() would also lock-and-copy
        # every histogram's bucket state once per heartbeat for nothing
        counters = get_registry().counters_with_prefix('')
        now = time.monotonic()
        prev, prev_t = self._prev, self._prev_t
        self._prev, self._prev_t = counters, now
        if prev is not None and now > prev_t:
            dur = now - prev_t
            rates = {}
            for key, value in counters.items():
                delta = value - prev.get(key, 0.0)
                if delta > 0:
                    rates[key] = round(delta / dur, 4)
            if len(rates) > self._MAX_RATES:
                keep = sorted(rates, key=lambda k: -rates[k])
                rates = {k: rates[k] for k in keep[:self._MAX_RATES]}
            out['rates'] = rates
        counts = anomaly_counts()
        if counts:
            out['anomalies'] = counts
        return out
