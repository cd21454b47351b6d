"""Telemetry unit tests: registry semantics, delta merge, exporter
round-trips (JSONL, Prometheus), stall-window classification, and the
overhead guard the ISSUE's satellite tasks require."""

import io
import json
import re
import time

import pytest

from petastorm_tpu import telemetry as T
from petastorm_tpu.telemetry.registry import MetricsRegistry, metric_key
from petastorm_tpu.telemetry import spans
from petastorm_tpu.telemetry.spans import _NOOP_SPAN
from petastorm_tpu.telemetry.stall import classify_window


@pytest.fixture(autouse=True)
def _fresh_telemetry():
    T.reset_for_tests()
    yield
    T.reset_for_tests()


# -- registry ----------------------------------------------------------------


def test_counter_gauge_histogram_basics():
    reg = MetricsRegistry()
    c = reg.counter('items_total')
    c.inc()
    c.inc(2.5)
    assert reg.counter_value('items_total') == 3.5
    with pytest.raises(ValueError):
        c.inc(-1)

    g = reg.gauge('depth')
    g.set(7)
    g.inc()
    g.dec(3)
    assert reg.gauge_value('depth') == 5

    h = reg.histogram('lat', buckets=(0.1, 1.0))
    for v in (0.05, 0.5, 5.0):
        h.observe(v)
    state = h.state()
    assert state['counts'] == [1, 1, 1]  # one per bucket + overflow
    assert state['count'] == 3
    assert state['sum'] == pytest.approx(5.55)


def test_labels_define_identity():
    reg = MetricsRegistry()
    a = reg.counter('x_total', stage='io')
    b = reg.counter('x_total', stage='decode')
    same = reg.counter('x_total', stage='io')
    assert a is same and a is not b
    a.inc()
    assert reg.counter_value('x_total', stage='io') == 1
    assert reg.counter_value('x_total', stage='decode') == 0
    # label order must not split the series
    assert metric_key('x', {'b': 1, 'a': 2}) == metric_key('x', {'a': 2,
                                                                 'b': 1})


def test_histogram_rejects_bad_buckets():
    with pytest.raises(ValueError):
        MetricsRegistry().histogram('h', buckets=(1.0, 1.0))
    with pytest.raises(ValueError):
        MetricsRegistry().histogram('h2', buckets=(2.0, 1.0))


def test_collect_delta_and_merge():
    worker = MetricsRegistry()
    consumer = MetricsRegistry()
    worker.counter('n_total').inc(3)
    worker.gauge('alive').set(1)
    worker.histogram('d', buckets=(0.1, 1.0)).observe(0.5)

    delta = worker.collect_delta()
    consumer.merge_delta(delta)
    assert consumer.counter_value('n_total') == 3
    assert consumer.gauge_value('alive') == 1
    assert consumer._histograms[metric_key('d')].count == 1

    # nothing changed since the flush → no payload to ship
    assert worker.collect_delta() is None

    # increments accumulate, never replace
    worker.counter('n_total').inc(2)
    worker.histogram('d', buckets=(0.1, 1.0)).observe(0.05)
    consumer.merge_delta(worker.collect_delta())
    assert consumer.counter_value('n_total') == 5
    merged = consumer._histograms[metric_key('d')].state()
    assert merged['count'] == 2
    assert merged['counts'] == [1, 1, 0]


def test_merge_worker_delta_feeds_global_attributor():
    worker = MetricsRegistry()
    worker.counter(T.STALL_PRODUCER_WAIT).inc(0.8)
    T.merge_worker_delta(worker.collect_delta())
    producer, consumer = T.get_attributor().totals()
    assert producer == pytest.approx(0.8)
    assert consumer == 0.0
    assert T.get_registry().counter_value(T.STALL_PRODUCER_WAIT) == \
        pytest.approx(0.8)


def test_load_delta_frame_rejects_non_delta_payloads():
    """The service dispatcher relies on this strictness to tell a metrics
    frame from a RESULT frame sent by a pre-telemetry worker build: only
    an exact {counters, gauges, histograms} dict (all dicts, at least one
    non-empty) may be claimed as a delta — anything else must fall
    through as data."""
    import dill
    reg = MetricsRegistry()
    reg.counter('a_total').inc()
    good = dill.dumps(reg.collect_delta())
    assert T.load_delta_frame(good) is not None
    for payload in (
        b'',                                           # "nothing changed"
        b'\x00not-a-pickle',
        dill.dumps([1, 2, 3]),                         # non-dict result
        dill.dumps({'window': {}, 'item_index': 3}),   # ngram result dict
        dill.dumps({'counters': {}, 'gauges': {},
                    'histograms': {}}),                # empty: not a delta
        dill.dumps({'counters': {}, 'gauges': {},
                    'histograms': {}, 'extra': 1}),    # foreign key
        dill.dumps({'counters': [1]}),                 # wrong field type
    ):
        assert T.load_delta_frame(payload) is None, payload[:40]


# -- exporters ---------------------------------------------------------------


def test_jsonl_roundtrip_equals_registry_state():
    reg = MetricsRegistry()
    reg.counter('a_total', stage='io').inc(2)
    reg.gauge('g').set(1.5)
    reg.histogram('h_seconds', buckets=(0.01, 0.1)).observe(0.02)
    buf = io.StringIO()
    T.write_jsonl_snapshot(buf, reg, extra={'run': 'r1'})
    (line,) = buf.getvalue().splitlines()
    parsed = json.loads(line)
    snap = reg.snapshot()
    assert parsed['counters'] == snap['counters']
    assert parsed['gauges'] == snap['gauges']
    assert parsed['histograms'] == snap['histograms']
    assert parsed['run'] == 'r1'
    assert 'ts' in parsed


def test_jsonl_file_append_and_parse(tmp_path):
    reg = MetricsRegistry()
    reg.counter('a_total').inc()
    path = str(tmp_path / 'metrics.jsonl')
    T.write_jsonl_snapshot(path, reg)
    reg.counter('a_total').inc()
    T.write_jsonl_snapshot(path, reg)
    first, second = T.read_jsonl_snapshots(path)
    assert first['counters']['a_total'] == 1
    assert second['counters'] == reg.snapshot()['counters']


def test_prometheus_text_line_by_line():
    reg = MetricsRegistry()
    reg.counter('petastorm_items_total', stage='io').inc(2)
    reg.gauge('petastorm_depth').set(4)
    reg.histogram('petastorm_lat_seconds', buckets=(0.1, 1.0)).observe(0.5)
    reg.histogram('petastorm_lat_seconds', buckets=(0.1, 1.0)).observe(0.05)
    text = T.prometheus_text(reg)
    lines = text.strip().splitlines()

    # exactly one TYPE line per family, with the right type
    assert lines.count('# TYPE petastorm_items_total counter') == 1
    assert lines.count('# TYPE petastorm_depth gauge') == 1
    assert lines.count('# TYPE petastorm_lat_seconds histogram') == 1
    # every non-comment line is "<series> <number>"
    sample_re = re.compile(r'^[A-Za-z_:][\w:]*(\{[^{}]*\})? \S+$')
    for line in lines:
        if not line.startswith('#'):
            assert sample_re.match(line), line

    assert 'petastorm_items_total{stage="io"} 2' in lines
    assert 'petastorm_depth 4' in lines
    # cumulative buckets, ascending le through +Inf, consistent count/sum
    buckets = [ln for ln in lines
               if ln.startswith('petastorm_lat_seconds_bucket')]
    counts = [int(ln.rsplit(' ', 1)[1]) for ln in buckets]
    assert counts == sorted(counts), 'bucket counts must be cumulative'
    assert buckets[-1] == 'petastorm_lat_seconds_bucket{le="+Inf"} 2'
    assert 'petastorm_lat_seconds_count 2' in lines
    assert any(ln.startswith('petastorm_lat_seconds_sum ')
               for ln in lines)


def test_prometheus_label_escaping():
    reg = MetricsRegistry()
    reg.counter('esc_total', path='a"b\\c\nd').inc()
    text = T.prometheus_text(reg)
    assert 'esc_total{path="a\\"b\\\\c\\nd"} 1' in text
    # the raw newline must never split the sample across exposition lines
    esc_lines = [ln for ln in text.splitlines() if 'esc_total' in ln
                 and not ln.startswith('#')]
    assert len(esc_lines) == 1 and esc_lines[0].endswith(' 1')


# -- stall attribution -------------------------------------------------------


def test_classify_window_thresholds():
    # consumer starving → producer-bound; producer blocked → consumer-bound
    assert classify_window(0.0, 0.4, 0.5) == T.PRODUCER_BOUND
    assert classify_window(0.4, 0.0, 0.5) == T.CONSUMER_BOUND
    assert classify_window(0.2, 0.2, 0.5) == T.BALANCED
    # too quiet to call (< 2% of the window)
    assert classify_window(0.0, 0.005, 0.5) == T.BALANCED


def test_attributor_windows_roll_and_classify():
    att = T.StallAttributor(window_s=0.05)
    att.note_consumer_wait(0.04)
    time.sleep(0.12)
    att.note_consumer_wait(0.04)  # closes the first window
    windows = att.windows()
    assert windows, 'expected at least one window'
    assert windows[0]['verdict'] == T.PRODUCER_BOUND
    assert att.verdict() == T.PRODUCER_BOUND
    producer, consumer = att.totals()
    assert producer == 0.0
    assert consumer == pytest.approx(0.08)
    att.reset()
    assert att.windows() == []
    assert att.totals() == (0.0, 0.0)


def test_attributor_ignores_nonpositive_notes():
    att = T.StallAttributor(window_s=0.05)
    att.note_producer_wait(0.0)
    att.note_consumer_wait(-1.0)
    assert att.totals() == (0.0, 0.0)


# -- env gating + overhead guard --------------------------------------------


def test_disabled_spans_are_noops(monkeypatch):
    monkeypatch.setenv('PETASTORM_TPU_METRICS', '0')
    T.refresh_enabled()
    try:
        assert T.metrics_disabled()
        s1 = T.span('decode')
        s2 = T.span('io')
        assert s1 is s2 is _NOOP_SPAN, 'disabled spans must be one no-op'
        with s1:
            pass
        # the note helpers silence too
        T.note_consumer_wait(1.0)
        T.note_producer_wait(1.0)
        assert T.get_registry().snapshot() == {'counters': {}, 'gauges': {},
                                               'histograms': {}}
        assert T.get_attributor().totals() == (0.0, 0.0)
    finally:
        monkeypatch.delenv('PETASTORM_TPU_METRICS')
        T.refresh_enabled()
    assert not T.metrics_disabled()
    assert T.span('decode') is not _NOOP_SPAN


def test_overhead_budget():
    """Counter inc + span enter/exit stay under a per-call budget, enabled
    AND disabled (disabled must be far cheaper). Budgets are deliberately
    loose for shared CI boxes — the guard catches order-of-magnitude
    regressions (an accidental syscall/allocation on the hot path), not
    single-microsecond noise."""
    n = 20000
    counter = T.get_registry().counter('hot_total')
    start = time.perf_counter()
    for _ in range(n):
        counter.inc()
    counter_per_call = (time.perf_counter() - start) / n

    start = time.perf_counter()
    for _ in range(n):
        with T.span('decode'):
            pass
    span_per_call = (time.perf_counter() - start) / n

    # the span read both clocks: its CPU counter moved with its calls
    registry = T.get_registry()
    assert registry.counter_value(spans.STAGE_CALLS, stage='decode') >= n
    assert registry.counter_value(spans.STAGE_CPU_SECONDS,
                                  stage='decode') > 0

    start = time.perf_counter()
    for _ in range(n):
        with spans.timed('loader_next'):
            pass
    timed_per_call = (time.perf_counter() - start) / n

    # armed, a span also lands one timeline event in the flight recorder
    m = n // 4
    with T.tracing.timeline() as armed:
        start = time.perf_counter()
        for _ in range(m):
            with T.span('decode'):
                pass
        armed_per_call = (time.perf_counter() - start) / m
    assert len(armed.events()) == m
    assert spans._trace_hook is None

    assert counter_per_call < 25e-6, counter_per_call
    assert span_per_call < 50e-6, span_per_call
    assert timed_per_call < 50e-6, timed_per_call
    assert armed_per_call < 100e-6, armed_per_call


def test_overhead_budget_disabled(monkeypatch):
    monkeypatch.setenv('PETASTORM_TPU_METRICS', 'off')
    T.refresh_enabled()
    try:
        n = 20000
        start = time.perf_counter()
        for _ in range(n):
            with T.span('decode'):
                pass
        per_call = (time.perf_counter() - start) / n
        assert per_call < 10e-6, per_call
        # the loader's wait clocks keep the wall clock, and only it
        start = time.perf_counter()
        for _ in range(n):
            with spans.timed('loader_next'):
                pass
        timed_per_call = (time.perf_counter() - start) / n
        assert timed_per_call < 10e-6, timed_per_call
        assert T.get_registry().snapshot()['counters'] == {}
    finally:
        monkeypatch.delenv('PETASTORM_TPU_METRICS')
        T.refresh_enabled()
