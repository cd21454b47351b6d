"""Staging autotuner: the closed control loop over the live rollups.

tf.data's core result (PAPERS.md, arxiv 2101.12127) is that static knob
settings always lose to dynamic tuning — and since PR 10 the pipeline
emits exactly the signal dynamic tuning needs: windowed rollups with
per-stage rates and a stall verdict. This module closes the loop for the
staging layer. A :class:`StagingAutotuner` rides the loader's staging
thread (no thread of its own: :meth:`maybe_tick` is a monotonic compare
until a window is due), closes a :class:`~petastorm_tpu.telemetry
.timeseries.WindowedRollup` window per ``PETASTORM_TPU_STAGING_AUTOTUNE_
WINDOW_SEC``, and adjusts three knobs from the window stream:

* **h2d starvation** (``h2d_ready`` share of the window at or above the
  ``PETASTORM_TPU_OBS_SATURATED_SHARE`` threshold — the same signal the
  anomaly detector's ``h2d_starvation`` event fires on — for 3
  consecutive windows) → **deepen**: one more slot per signature ring
  (``StagingEngine.set_num_slots``, bounded by
  ``PETASTORM_TPU_STAGING_AUTOTUNE_MAX_SLOTS``) and one more prefetch
  queue entry (bounded by ``PETASTORM_TPU_STAGING_AUTOTUNE_MAX_
  PREFETCH``): more transfers in flight hide more completion latency.
* **consumer-bound** verdict for 3 consecutive windows (the training
  step is the wall; the producer sits blocked on a full queue) →
  **shed decode threads**: the host's CPUs are not the problem, so hand
  them back — one decoder thread at a time down to 1 (or, where the
  staging fill itself decodes, down to the width that keeps that fill
  under half the consumer's interval between batches), via an in-process
  override of the ``PETASTORM_TPU_IMAGE_DECODER_THREADS`` parse
  (``codecs.set_image_decoder_threads_override``; never a mutation of
  ``os.environ``, which child processes inherit and later readers in
  this process would silently keep).
* **producer-bound** verdict for 3 consecutive windows (the consumer
  waits on data) → **restore decode threads** back toward the
  construction-time baseline, one at a time — and **raise the
  ventilator's in-flight bound** (``Reader.set_ventilate_extra``, the
  remaining knob of the ROADMAP self-tuning item, bounded) so the pool
  never drains between pulls; a consumer-bound streak lowers it back
  toward the construction-time baseline.
* **io-wait saturation** (``io`` stage seconds-per-second at or above
  the saturation share while producer-bound, 3 consecutive windows) →
  **deepen readahead**: one more row-group of prefetch depth on the
  wire-speed I/O plane (:mod:`petastorm_tpu.readahead`; an in-process
  depth override, bounded by ``PETASTORM_TPU_READAHEAD_MAX_DEPTH``) —
  storage latency hides behind decode. Sustained **buffer-pool memory
  pressure** (occupancy ≥ 85% of the pool budget) sheds the depth back
  one step at a time instead.

Every decision lands three ways, so Perfetto and ``pipeline_report()``
show *why* throughput changed: a canonical ``autotune_decision`` trace
instant, the ``petastorm_tpu_staging_autotune_decisions_total{action=…}``
counter (fleet-aggregated over the pool delta channels like every other
metric), and a bounded in-process decision ring served by
``pipeline_report()['staging_autotune']``, the loader's
``autotune_report()``, and bench's ``sharded_staging`` section.

``PETASTORM_TPU_STAGING_AUTOTUNE=0`` disables the loop entirely (the
exact-parity oracle pin: batch VALUES are identical either way — the
tuner only moves buffering depth and thread counts — but a pinned run
also reproduces today's exact timing shape). Depth only ever deepens and
thread shedding is restored when the loader stops, so a tuner can never
wedge a pipeline below its static configuration.
"""

import collections
import logging
import threading
import time

from petastorm_tpu.telemetry import (
    get_registry, knobs, metrics_disabled, register_refresh, span, tracing,
)
from petastorm_tpu.telemetry.stall import CONSUMER_BOUND, PRODUCER_BOUND
from petastorm_tpu.telemetry.timeseries import (
    WindowedRollup, decode_fused_share, h2d_ready_share, io_wait_share,
)

logger = logging.getLogger(__name__)

#: registry counter: autotuner adjustments by action label
AUTOTUNE_DECISIONS = 'petastorm_tpu_staging_autotune_decisions_total'

#: decisions kept in the in-process ring (oldest dropped)
_DECISION_RING_CAPACITY = 100

_decisions_lock = threading.Lock()
_decisions = collections.deque(maxlen=_DECISION_RING_CAPACITY)
_decision_seq = 0

#: the tuner currently holding the process-wide decoder-thread override
#: (codecs.set_image_decoder_threads_override is one slot per process):
#: other tuners leave the threads knob alone while it is held, a closing
#: owner clears only its own setting, and a second loader's tuner can
#: never mistake the tuned-down width for the configured baseline
_override_owner = None

#: same single-owner discipline for the readahead DEPTH override
#: (readahead.set_depth_override is one slot per process too)
_readahead_owner = None

#: pool-occupancy share at which the readahead depth sheds (memory
#: pressure: the pool is the bound, not the wire)
_READAHEAD_POOL_PRESSURE = 0.85

#: the share of the consumer's interval between batches past which a
#: shed would leave the staging fill's decode (``decode_fused``, one
#: thread fewer stretching it by width / (width - 1)) too little slack:
#: below it the fill hides behind the step with room for its jitter
_FILL_SHARE_CEILING = 0.5

#: ceiling on the autotuned ventilator in-flight extra (row-groups kept
#: in flight beyond the pool's worker count; the knobless satellite of
#: the self-tuning item — each step costs one more decoded row-group of
#: consumer-side queue memory at most)
_MAX_INFLIGHT_EXTRA = 16

# knob caches (refresh_autotune() re-reads); None = not yet resolved
_enabled = None


def autotune_enabled():
    """True unless ``PETASTORM_TPU_STAGING_AUTOTUNE`` disables the loop
    (on by default: the tuner changes buffering depth and thread counts,
    never batch values, so enabling it is parity-safe)."""
    global _enabled
    if _enabled is None:
        _enabled = not knobs.is_disabled('PETASTORM_TPU_STAGING_AUTOTUNE')
    return _enabled


def autotune_window_sec():
    return knobs.get_float('PETASTORM_TPU_STAGING_AUTOTUNE_WINDOW_SEC',
                           1.0, floor=0.05)


def autotune_max_slots():
    return knobs.get_int('PETASTORM_TPU_STAGING_AUTOTUNE_MAX_SLOTS', 8,
                         floor=2)


def autotune_max_prefetch():
    return knobs.get_int('PETASTORM_TPU_STAGING_AUTOTUNE_MAX_PREFETCH', 8,
                         floor=1)


def refresh_autotune():
    """Re-read the cached enablement knob (part of
    ``petastorm_tpu.telemetry.refresh()``, the one re-read-every-knob
    entry point); the per-decision bounds are read at each tuner's
    construction."""
    global _enabled
    _enabled = None


register_refresh(refresh_autotune)


def record_decision(action, **detail):
    """Record one autotuner adjustment: bounded in-process ring + the
    ``petastorm_tpu_staging_autotune_decisions_total{action=…}`` counter
    + a canonical ``autotune_decision`` trace instant on the
    ``autotuner`` track (no-op when tracing is off)."""
    global _decision_seq
    entry = {'action': action, 'ts': time.time()}
    entry.update(detail)
    with _decisions_lock:
        _decision_seq += 1
        seq = _decision_seq
        _decisions.append(entry)
    if not metrics_disabled():
        get_registry().counter(AUTOTUNE_DECISIONS, action=action).inc()
    tracing.record_instant('autotune_decision', tracing.mint(seq),
                           'autotuner', action=action, **detail)
    logger.info('staging autotune: %s (%s)', action, detail)
    return entry


def recent_decisions(last_n=20):
    """The most recent decisions (oldest first; this process only)."""
    with _decisions_lock:
        out = list(_decisions)
    return out[-last_n:]


def decision_counts():
    """``{action: n}`` of ring-resident decisions (this process only;
    the registry counter holds the fleet-wide totals)."""
    counts = {}
    with _decisions_lock:
        for entry in _decisions:
            counts[entry['action']] = counts.get(entry['action'], 0) + 1
    return counts


def _reset_for_tests():
    global _override_owner, _readahead_owner
    with _decisions_lock:
        _decisions.clear()
    _override_owner = None
    _readahead_owner = None


class StagingAutotuner:
    """Per-loader control loop; lives on the loader's staging thread.

    Single-threaded by the same contract as the engine it tunes: only
    the staging thread calls :meth:`maybe_tick`, so slot-ring growth,
    prefetch-bound writes and the decoder-thread override all happen
    from the one thread that owns them. The tuner survives epoch
    replays (the loader re-applies its learned depth to each pass's
    fresh engine via :meth:`apply_learned`) and restores the
    decoder-thread override at :meth:`close`.
    """

    #: consecutive windows a condition must hold before acting — the
    #: same streak discipline as the anomaly detector, so one noisy
    #: window can never move a knob
    _CONSECUTIVE = 3

    def __init__(self, loader, window_s=None):
        self._loader = loader
        self.window_s = window_s or autotune_window_sec()
        self._rollup = WindowedRollup(max_windows=32)
        self._next_sample = time.monotonic() + self.window_s
        self._saturated_share = knobs.get_float(
            'PETASTORM_TPU_OBS_SATURATED_SHARE', 0.5, floor=0.05)
        self._max_slots = autotune_max_slots()
        self._max_prefetch = autotune_max_prefetch()
        from petastorm_tpu import codecs
        self._codecs = codecs
        #: restore ceiling for the shed/restore pair: the KNOB's own
        #: width — never another tuner's live override
        self._baseline_threads = codecs.image_decoder_threads_from_knob()
        self._thread_override = None
        #: ring depth carried across passes (each pass gets a fresh
        #: engine; a learned deepening must not reset at the epoch gap)
        self._learned_slots = None
        self._h2d_streak = 0
        self._consumer_streak = 0
        self._producer_streak = 0
        # the largest decode_fused share over the consumer-bound streak:
        # the one window that closes after a consumer pause (the stager
        # ticks only between puts) reads a diluted share, so a shed
        # answers to the whole streak, not to its last window alone
        self._streak_fill_share = 0.0
        # readahead-depth control (petastorm_tpu/readahead.py): deepen
        # while the fleet's io-wait share stays saturated, shed under
        # buffer-pool memory pressure
        from petastorm_tpu import readahead
        self._readahead = readahead
        self._readahead_max_depth = readahead.readahead_max_depth()
        self._readahead_override = None
        self._io_streak = 0
        self._pool_streak = 0
        # ventilator in-flight bound (Reader.set_ventilate_extra): the
        # remaining knob of the ROADMAP self-tuning item — baseline
        # captured lazily at the first adjustment
        self._inflight_baseline = None
        #: total adjustments made by THIS tuner (loader diagnostics)
        self.decisions = 0

    # -- loader integration ---------------------------------------------------

    def apply_learned(self, stager):
        """Carry the learned ring depth into a new pass's fresh engine."""
        if self._learned_slots is not None:
            stager.set_num_slots(self._learned_slots)

    def maybe_tick(self, now=None):
        """The staging-thread cadence gate: one monotonic compare until
        the next window is due, then a full :meth:`tick`."""
        now = time.monotonic() if now is None else now
        if now < self._next_sample:
            return None
        self._next_sample = now + self.window_s
        with span('autotune'):
            return self.tick(now)

    def tick(self, now=None):
        """Close one rollup window against the registry and act on it.
        Returns the actions taken (empty list when none; None while the
        first window primes)."""
        window = self._rollup.sample(get_registry().snapshot(), now=now)
        if window is None:
            return None
        return self.observe(window)

    # -- the policy -----------------------------------------------------------

    def observe(self, window):
        """Feed one closed window; applies any due adjustment and
        returns the decision entries."""
        actions = []
        ready_share = h2d_ready_share(window)
        starved = ready_share >= self._saturated_share
        self._h2d_streak = self._h2d_streak + 1 if starved else 0
        verdict = window.get('verdict')
        self._consumer_streak = (self._consumer_streak + 1
                                 if verdict == CONSUMER_BOUND else 0)
        self._producer_streak = (self._producer_streak + 1
                                 if verdict == PRODUCER_BOUND else 0)
        self._streak_fill_share = (
            max(self._streak_fill_share, decode_fused_share(window))
            if self._consumer_streak else 0.0)
        # readahead: the io-wait share only argues for more depth while
        # the consumer actually starves (producer-bound) — an io-heavy
        # but keeping-up pipeline gains nothing from deeper prefetch.
        # io_wait_share is fleet-SUMMED seconds-per-second (N parallel
        # workers can push it past 1.0), so the 0..1 saturation knob is
        # scaled by the pool's worker count: the trigger means "each
        # worker spends ≥ the share blocked in io", not "the fleet's
        # summed io crumbs add up to it"
        io_share = io_wait_share(window)
        io_starved = (io_share >= self._saturated_share
                      * self._io_share_scale()
                      and verdict == PRODUCER_BOUND)
        self._io_streak = self._io_streak + 1 if io_starved else 0
        used, budget = self._readahead.pool_status()
        pressured = budget > 0 and used / budget >= _READAHEAD_POOL_PRESSURE
        self._pool_streak = self._pool_streak + 1 if pressured else 0
        if self._h2d_streak >= self._CONSECUTIVE:
            self._h2d_streak = 0
            actions += self._deepen(ready_share)
        if self._pool_streak >= self._CONSECUTIVE:
            self._pool_streak = 0
            self._io_streak = 0
            actions += self._shed_readahead(used, budget)
        elif self._io_streak >= self._CONSECUTIVE:
            self._io_streak = 0
            actions += self._deepen_readahead(io_share)
        if self._consumer_streak >= self._CONSECUTIVE:
            self._consumer_streak = 0
            actions += self._shed_decode_threads(self._streak_fill_share)
            self._streak_fill_share = 0.0
            actions += self._lower_inflight()
        elif self._producer_streak >= self._CONSECUTIVE:
            self._producer_streak = 0
            actions += self._restore_decode_threads()
            actions += self._raise_inflight()
        self.decisions += len(actions)
        return actions

    def _deepen(self, ready_share):
        """h2d starvation: more transfers in flight — one more ring slot
        per signature and one more prefetch entry, each within its
        bound."""
        actions = []
        stager = self._loader._stager
        if stager is not None and stager.num_slots < self._max_slots:
            before = stager.num_slots
            after = stager.set_num_slots(before + 1)
            self._learned_slots = after
            actions.append(record_decision(
                'deepen_slots', slots_from=before, slots_to=after,
                h2d_ready_share=round(ready_share, 4)))
        prefetch = self._loader._prefetch
        if prefetch < self._max_prefetch:
            after = self._loader._set_prefetch(prefetch + 1)
            actions.append(record_decision(
                'deepen_prefetch', prefetch_from=prefetch,
                prefetch_to=after, h2d_ready_share=round(ready_share, 4)))
        return actions

    # -- readahead depth (petastorm_tpu/readahead.py) -------------------------

    def _io_share_scale(self):
        """The io-saturation normalizer: the reader pool's worker count
        (re-read each window — service fleets grow live), floor 1 for
        loaders whose reader exposes no pool."""
        reader = self._tunable_reader()
        pool = getattr(reader, '_pool', None) if reader is not None \
            else None
        workers = getattr(pool, 'workers_count', None)
        return max(1, workers) if isinstance(workers, int) else 1

    def _owns_readahead(self):
        """Single-owner guard for the process-wide readahead depth
        override — same discipline as the decoder-thread slot."""
        global _readahead_owner
        if _readahead_owner is None:
            _readahead_owner = self
        return _readahead_owner is self

    def _deepen_readahead(self, io_share):
        """Sustained io-wait while the consumer starves: fetch further
        ahead so storage latency hides behind decode — bounded by
        ``PETASTORM_TPU_READAHEAD_MAX_DEPTH``, and only where a live
        manager can observe the override (thread-pool workers share
        this process; remote fleets tune from their own windows)."""
        if self._readahead.live_manager_count() == 0 \
                or not self._owns_readahead():
            return []
        current = self._readahead.current_depth()
        if current >= self._readahead_max_depth:
            return []
        self._readahead_override = current + 1
        self._readahead.set_depth_override(self._readahead_override)
        return [record_decision('deepen_readahead', depth_from=current,
                                depth_to=self._readahead_override,
                                io_wait_share=round(io_share, 4))]

    def _shed_readahead(self, used, budget):
        """Sustained buffer-pool pressure: the pool, not the wire, is
        the bound — back the depth off one step, never below the KNOB's
        own width (the static configuration is the floor, as with every
        shed), so fetches stop being declined (``pool-exhausted``) at
        the budget edge."""
        if self._readahead.live_manager_count() == 0 \
                or not self._owns_readahead():
            return []
        current = self._readahead.current_depth()
        if current <= self._readahead.readahead_depth():
            return []
        self._readahead_override = current - 1
        self._readahead.set_depth_override(self._readahead_override)
        return [record_decision(
            'shed_readahead', depth_from=current,
            depth_to=self._readahead_override,
            pool_share=round(used / budget, 4) if budget else None)]

    def _release_readahead(self):
        global _readahead_owner
        if self._readahead_override is not None:
            self._readahead.set_depth_override(None)
            self._readahead_override = None
        if _readahead_owner is self:
            _readahead_owner = None

    # -- ventilator in-flight bound (Reader.set_ventilate_extra) --------------

    def _tunable_reader(self):
        reader = getattr(self._loader, 'reader', None)
        if reader is None or not hasattr(reader, 'set_ventilate_extra'):
            return None
        return reader

    def _raise_inflight(self):
        """Producer-bound: the consumer waits on data — let the
        ventilator keep more row-groups in flight so the pool never
        drains between pulls (bounded; each step is at most one more
        decoded row-group queued consumer-side)."""
        reader = self._tunable_reader()
        if reader is None:
            return []
        current = reader.ventilate_extra
        if self._inflight_baseline is None:
            self._inflight_baseline = current
        if current >= _MAX_INFLIGHT_EXTRA:
            return []
        after = reader.set_ventilate_extra(current + 1)
        return [record_decision('raise_inflight', inflight_from=current,
                                inflight_to=after)]

    def _lower_inflight(self):
        """Consumer-bound: the training step is the wall — give queued
        row-group memory back, one step at a time toward the
        construction-time baseline (never below it: the static
        configuration is the floor, as with every shed)."""
        reader = self._tunable_reader()
        if reader is None or self._inflight_baseline is None:
            return []
        current = reader.ventilate_extra
        if current <= self._inflight_baseline:
            return []
        after = reader.set_ventilate_extra(current - 1)
        return [record_decision('lower_inflight', inflight_from=current,
                                inflight_to=after)]

    # -- decoder threads ------------------------------------------------------

    def _owns_override(self):
        """True when THIS tuner may move the process-wide decoder-thread
        override: it already holds it, or the slot is free. The thread
        knob is one per process — two loaders' tuners must not fight
        over it or wipe each other's setting."""
        global _override_owner
        if _override_owner is None:
            _override_owner = self
        return _override_owner is self

    def _shed_decode_threads(self, fill_share=0.0):
        """Consumer-bound: the training step is the wall — hand decoder
        CPUs back, one thread at a time down to 1.

        Where the staging fill decodes (``fill_share``, the largest
        ``decode_fused`` seconds per second of the streak's windows, is
        above 0) the decode sits on the staging thread's path to every
        batch, so a shed stops at the width below which the fill's
        seconds per delivered batch, stretched by ``current / (current -
        1)``, would reach half the consumer's interval between
        ``loader_next`` calls. Both are over the same batches, so the
        ratio is ``fill_share`` itself."""
        if not self._owns_override():
            return []
        current = self._codecs.image_decoder_threads()
        if current <= 1:
            return []
        if fill_share * current / (current - 1) >= _FILL_SHARE_CEILING:
            return []
        self._thread_override = current - 1
        self._codecs.set_image_decoder_threads_override(
            self._thread_override)
        return [record_decision('shed_decode_threads',
                                threads_from=current,
                                threads_to=self._thread_override)]

    def _restore_decode_threads(self):
        """Producer-bound: the consumer waits on data — give shed
        decoder threads back, toward the knob baseline."""
        current = self._codecs.image_decoder_threads()
        if self._thread_override is None \
                or current >= self._baseline_threads:
            return []
        self._thread_override = current + 1
        if self._thread_override >= self._baseline_threads:
            # fully restored: drop the override so the knob rules again
            self._release_override()
            restored_to = self._baseline_threads
        else:
            self._codecs.set_image_decoder_threads_override(
                self._thread_override)
            restored_to = self._thread_override
        return [record_decision('restore_decode_threads',
                                threads_from=current,
                                threads_to=restored_to)]

    def _release_override(self):
        global _override_owner
        self._codecs.set_image_decoder_threads_override(None)
        self._thread_override = None
        if _override_owner is self:
            _override_owner = None

    # -- lifecycle / reporting ------------------------------------------------

    def close(self):
        """Loader stop: drop the decoder-thread and readahead-depth
        overrides — only those THIS tuner holds — so learned settings
        die with the loader instead of leaking into later readers (or
        wiping another live tuner's). The decision log survives in the
        module ring and the counter."""
        global _override_owner
        if self._thread_override is not None:
            self._release_override()
        elif _override_owner is self:
            _override_owner = None
        self._release_readahead()

    def summary(self):
        """The report-facing view: current depths, bounds, streaks and
        the recent decision log."""
        stager = self._loader._stager
        reader = self._tunable_reader()
        return {
            'window_s': self.window_s,
            'slots': stager.num_slots if stager is not None else None,
            'max_slots': self._max_slots,
            'prefetch': self._loader._prefetch,
            'max_prefetch': self._max_prefetch,
            'decoder_threads': self._codecs.image_decoder_threads(),
            'readahead_depth': self._readahead.current_depth(),
            'readahead_max_depth': self._readahead_max_depth,
            'ventilate_extra': (reader.ventilate_extra
                                if reader is not None else None),
            'decisions': self.decisions,
            'recent': recent_decisions(10),
        }
