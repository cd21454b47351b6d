"""Per-stage timing spans over the canonical pipeline stages.

``with span('decode'): ...`` accumulates, per stage, into the process-wide
registry: ``petastorm_tpu_stage_seconds_total{stage=...}`` (counter),
``petastorm_tpu_stage_cpu_seconds_total{stage=...}`` (counter),
``petastorm_tpu_stage_calls_total{stage=...}`` (counter) and
``petastorm_tpu_stage_duration_seconds{stage=...}`` (histogram). Worker-side
spans (io/decode/filter/transform/serialize) record into the WORKER
process's registry and ride the pool's delta channel back to the consumer.

A stage's CPU is its own: the thread's CPU clock over the span, plus the
CPU that native helper threads report through :func:`add_cpu`, less the
CPU of any span nested inside it on the same thread — so stage CPU sums
over stages without counting anything twice. A wait stage
(:data:`WAIT_STAGES`) likewise counts only the time it waits: a span
nested in it (the process pool's ``deserialize`` inside ``queue_wait``)
is taken out of its seconds. Every other stage's seconds are its whole
wall time.

``PETASTORM_TPU_METRICS=0`` (or ``false``/``off``) compiles every span to a
shared no-op singleton — no clock reads, no dict lookups, no metric
updates — so the hot path pays one cached boolean check per span site
(docs/env_knobs.md; enforced by
``tests/test_telemetry.py::test_disabled_spans_are_noops``).
"""

import threading
import time

from petastorm_tpu.analysis.contracts import STAGES  # noqa: F401 - canonical
from petastorm_tpu.analysis.contracts import WAIT_STAGES
from petastorm_tpu.telemetry import knobs
from petastorm_tpu.telemetry.knobs import DISABLED_VALUES  # noqa: F401
from petastorm_tpu.telemetry.registry import get_registry, on_registry_reset

STAGE_SECONDS = 'petastorm_tpu_stage_seconds_total'
STAGE_CPU_SECONDS = 'petastorm_tpu_stage_cpu_seconds_total'
STAGE_CALLS = 'petastorm_tpu_stage_calls_total'
STAGE_DURATION = 'petastorm_tpu_stage_duration_seconds'

# resolved once (refresh_enabled() re-reads, for tests and long-lived
# processes that flip the knob); None = not yet resolved
_disabled = None


def metrics_disabled():
    """True when ``PETASTORM_TPU_METRICS`` disables telemetry."""
    global _disabled
    if _disabled is None:
        _disabled = knobs.is_disabled('PETASTORM_TPU_METRICS')
    return _disabled


def refresh_enabled():
    """Re-read ``PETASTORM_TPU_METRICS`` (next span sees the new value)."""
    global _disabled
    _disabled = None
    _stage_cache.clear()


class _NoopSpan:
    """Shared do-nothing context manager for disabled telemetry."""

    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, exc_type, exc_val, exc_tb):
        return False


_NOOP_SPAN = _NoopSpan()

# Trace hook: None until per-item tracing activates a context in this
# process or the timeline is armed (tracing.py installs it), after which
# every span exit also offers ``(stage, start_ns, end_ns, cpu_ns, thread)``
# to the flight recorder, on ``time.perf_counter_ns()``. A module-global
# None check is the entire cost when tracing is off (enforced by
# tests/test_tracing.py's overhead guard).
_trace_hook = None


def set_trace_hook(hook):
    global _trace_hook
    _trace_hook = hook

# stage -> (seconds counter, cpu counter, calls counter, duration
# histogram); caches the metric-object lookups so a span's enter/exit is
# clock reads + four adds. Invalidated on registry reset (hook below):
# cached objects of a replaced registry would otherwise keep absorbing
# spans invisibly.
_stage_cache = {}
on_registry_reset(_stage_cache.clear)

# the innermost open span of each thread (``.span``): where a nested span
# hands its time and CPU up, and where add_cpu lands
_open = threading.local()


def _stage_metrics(stage):
    metrics = _stage_cache.get(stage)
    if metrics is None:
        registry = get_registry()
        metrics = (registry.counter(STAGE_SECONDS, stage=stage),
                   registry.counter(STAGE_CPU_SECONDS, stage=stage),
                   registry.counter(STAGE_CALLS, stage=stage),
                   registry.histogram(STAGE_DURATION, stage=stage))
        _stage_cache[stage] = metrics
    return metrics


class _Span:
    __slots__ = ('_stage', '_metrics', '_parent', '_t0', '_c0',
                 '_nested_ns', '_nested_cpu', '_native_cpu', 'seconds')

    def __init__(self, stage, metrics):
        self._stage = stage
        self._metrics = metrics
        self.seconds = 0.0

    def __enter__(self):
        self._parent = getattr(_open, 'span', None)
        _open.span = self
        self._nested_ns = self._nested_cpu = self._native_cpu = 0
        self._c0 = time.thread_time_ns()
        self._t0 = time.perf_counter_ns()
        return self

    def __exit__(self, exc_type, exc_val, exc_tb):
        t1 = time.perf_counter_ns()
        cpu = time.thread_time_ns() - self._c0 + self._native_cpu
        elapsed = t1 - self._t0
        parent = self._parent
        _open.span = parent
        if parent is not None:
            parent._nested_ns += elapsed
            parent._nested_cpu += cpu
            parent._native_cpu += self._native_cpu
        own_cpu = max(cpu - self._nested_cpu, 0)
        if self._stage in WAIT_STAGES:
            elapsed -= self._nested_ns
        self.seconds = seconds = elapsed / 1e9
        wall, cpu_total, calls, duration = self._metrics
        wall.inc(seconds)
        cpu_total.inc(own_cpu / 1e9)
        calls.inc()
        duration.observe(seconds)
        if _trace_hook is not None:
            _trace_hook(self._stage, self._t0, t1, own_cpu,
                        threading.current_thread().name)
        return False


class _Stopwatch:
    """What :func:`timed` returns with telemetry disabled: the wall time
    alone, recorded nowhere."""

    __slots__ = ('_t0', 'seconds')

    def __init__(self):
        self.seconds = 0.0

    def __enter__(self):
        self._t0 = time.perf_counter_ns()
        return self

    def __exit__(self, exc_type, exc_val, exc_tb):
        self.seconds = (time.perf_counter_ns() - self._t0) / 1e9
        return False


def span(stage):
    """Context manager timing one ``stage`` occurrence.

    Stage names outside :data:`STAGES` are allowed (library extensions,
    tests) but the canonical names are what :func:`~petastorm_tpu.telemetry
    .pipeline_report` groups by. Returns the shared no-op singleton when
    telemetry is disabled."""
    if metrics_disabled():
        return _NOOP_SPAN
    return _Span(stage, _stage_metrics(stage))


def timed(stage):
    """:func:`span` for a caller that needs the seconds itself (the
    loader's wait clocks, which its diagnostics and autotuner read): the
    returned object's ``seconds`` holds the span's seconds after the
    block, with telemetry disabled too."""
    if metrics_disabled():
        return _Stopwatch()
    return _Span(stage, _stage_metrics(stage))


def add_cpu(seconds):
    """Charge ``seconds`` of CPU that other threads spent on this thread's
    behalf (a native batch decoder's helper threads) to the innermost open
    span of this thread. No-op outside a span."""
    current = getattr(_open, 'span', None)
    if current is not None and seconds > 0:
        current._native_cpu += int(seconds * 1e9)
