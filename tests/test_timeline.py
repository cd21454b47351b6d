"""Stage CPU, process CPU, the loader's wait spans, the armed timeline and
the flight recorder's clock (petastorm_tpu/telemetry/spans.py, registry.py,
tracing.py, recorder.py)."""

import json
import socket
import threading
import time

import numpy as np
import pytest

from petastorm_tpu import telemetry as T
from petastorm_tpu.telemetry import recorder, spans, tracing
from petastorm_tpu.telemetry.registry import PROCESS_CPU, load_delta_frame
from petastorm_tpu.telemetry.spans import STAGE_CPU_SECONDS, STAGE_SECONDS

STAGE_CALLS = spans.STAGE_CALLS


@pytest.fixture(autouse=True)
def fresh():
    T.reset_for_tests()
    yield
    T.reset_for_tests()


def _cpu(stage):
    return T.get_registry().counter_value(STAGE_CPU_SECONDS, stage=stage)


def _seconds(stage):
    return T.get_registry().counter_value(STAGE_SECONDS, stage=stage)


def _busy(seconds):
    end = time.thread_time() + seconds
    while time.thread_time() < end:
        pass


# -- stage CPU ----------------------------------------------------------------


def test_span_cpu_grows_under_a_busy_loop_and_not_under_a_sleep():
    with T.span('decode'):
        _busy(0.05)
    with T.span('io'):
        time.sleep(0.05)
    assert _cpu('decode') >= 0.045
    assert _seconds('io') >= 0.05
    assert _cpu('io') < 0.01


def test_a_nested_span_is_taken_out_of_its_parents_cpu_and_of_a_waits_time():
    with T.span('queue_wait'):
        time.sleep(0.03)
        with T.span('deserialize'):
            _busy(0.03)
            time.sleep(0.02)
    with T.span('collate'):
        with T.span('decode'):
            _busy(0.02)
    # the wait keeps its own wait only; the work span keeps its wall
    assert 0.03 <= _seconds('queue_wait') < 0.045
    assert _seconds('deserialize') >= 0.05
    assert _seconds('collate') >= _seconds('decode') >= 0.02
    # CPU is each span's own, so the stages sum without double counting
    assert _cpu('deserialize') >= 0.025 and _cpu('queue_wait') < 0.01
    assert _cpu('decode') >= 0.015 and _cpu('collate') < 0.01


def test_add_cpu_lands_on_the_innermost_span_and_its_parent_leaves_it():
    spans.add_cpu(5.0)   # outside any span: nowhere
    with T.span('collate'):
        with T.span('decode'):
            spans.add_cpu(0.5)
    assert 0.5 <= _cpu('decode') < 0.55
    assert _cpu('collate') < 0.01
    assert sum(T.get_registry().counters_with_prefix(
        STAGE_CPU_SECONDS).values()) < 0.6


def test_timed_gives_its_seconds_with_metrics_on_and_off(monkeypatch):
    with spans.timed('loader_next') as on:
        time.sleep(0.01)
    assert on.seconds >= 0.01 and _seconds('loader_next') == on.seconds
    monkeypatch.setenv('PETASTORM_TPU_METRICS', '0')
    T.refresh_enabled()
    try:
        assert T.span('decode') is spans._NOOP_SPAN
        with spans.timed('loader_next') as off:
            time.sleep(0.01)
        assert off.seconds >= 0.01
        assert _seconds('loader_next') == on.seconds   # recorded nowhere
    finally:
        monkeypatch.delenv('PETASTORM_TPU_METRICS')
        T.refresh_enabled()


def test_decode_cpu_covers_the_native_pool_threads(monkeypatch):
    """A 64-image batch through the native JPEG decoder's pool: the decode
    span's CPU holds the pool threads' CPU, so it exceeds the span's wall
    time (the best of three batches, against a busy host)."""
    from petastorm_tpu.native import get_jpeg_module
    if get_jpeg_module() is None:
        pytest.skip('native jpeg extension could not be built')
    import cv2

    from petastorm_tpu.codecs import CompressedImageCodec
    from petastorm_tpu.unischema import UnischemaField
    monkeypatch.setenv('PETASTORM_TPU_IMAGE_DECODER_THREADS', '4')
    rng = np.random.RandomState(3)
    cells = [cv2.imencode('.jpeg', rng.randint(0, 255, (224, 224, 3),
                                               dtype=np.uint8))[1].tobytes()
             for _ in range(64)]
    codec = CompressedImageCodec('jpeg')
    field = UnischemaField('image', np.uint8, (224, 224, 3), codec, False)
    codec.decode_batch(field, cells)   # calibration and load outside
    ratios = []
    for _ in range(3):
        cpu0, wall0 = _cpu('decode'), _seconds('decode')
        with T.span('decode'):
            out = codec.decode_batch(field, cells)
        ratios.append((_cpu('decode') - cpu0) / (_seconds('decode') - wall0))
    assert len(out) == 64
    assert max(ratios) > 1.0, ratios


def test_worker_serialize_cpu_and_process_cpu_ride_the_delta_frames(
        scalar_dataset):
    from petastorm_tpu.reader import make_batch_reader
    with make_batch_reader(scalar_dataset.url, reader_pool_type='process',
                           workers_count=2) as reader:
        rows = sum(len(batch.id) for batch in reader)
    assert rows == 100
    registry = T.get_registry()
    calls = registry.counter_value(STAGE_CALLS, stage='serialize')
    assert calls >= 4   # one per row-group result
    assert _cpu('serialize') > 0 and _cpu('deserialize') > 0
    assert registry.counter_value(PROCESS_CPU, role='worker') > 0
    report = T.pipeline_report()
    assert report['stages']['serialize']['cpu_seconds'] > 0
    assert set(report['process_cpu']) == {'consumer', 'worker'}
    # queue_wait keeps the blocking wait; the rebuild is deserialize's
    assert report['stages']['deserialize']['calls'] >= calls


def test_process_cpu_is_refreshed_by_a_snapshot_and_scoped_by_a_baseline():
    baseline = T.get_registry().snapshot()
    _busy(0.05)
    report = T.pipeline_report(baseline=baseline)
    assert report['process_cpu']['consumer'] >= 0.045
    _busy(0.02)
    assert T.get_registry().snapshot()['counters'][
        PROCESS_CPU + '{role="consumer"}'] >= 0.065


# -- the loader's wait spans -------------------------------------------------


def test_the_loader_wait_clocks_are_its_wait_spans_seconds(scalar_dataset):
    from petastorm_tpu.jax import make_jax_loader
    with make_jax_loader(scalar_dataset.url, batch_size=10, fields=['^id$'],
                         last_batch='short', prefetch=1) as loader:
        n = 0
        for _ in loader:
            n += 1
            time.sleep(0.01)   # the stager blocks on the full queue
        diag = loader.diagnostics
    assert n == 10
    assert diag['consumer_wait_s'] == round(_seconds('loader_next'), 3)
    assert diag['stage_backpressure_s'] == \
        round(_seconds('stage_backpressure'), 3)
    assert diag['stage_backpressure_s'] > 0
    # one loader_next per delivered batch, and one for the pass's end
    assert T.get_registry().counter_value(
        STAGE_CALLS, stage='loader_next') == n + 1


# -- the timeline -------------------------------------------------------------


def test_armed_timeline_records_every_span_on_one_clock_with_batch_numbers(
        scalar_dataset):
    from petastorm_tpu.jax import make_jax_loader
    assert spans._trace_hook is None
    with make_jax_loader(scalar_dataset.url, batch_size=10, fields=['^id$'],
                         num_epochs=None) as loader:
        it = iter(loader)
        next(it)
        with tracing.timeline() as armed:
            for _ in range(5):
                next(it)
        assert spans._trace_hook is None
        recorded = len(T.get_recorder())
        next(it)
        assert len(T.get_recorder()) == recorded   # disarmed: nothing more
    events = armed.events()
    assert events and len(events) == recorded
    for e in events:
        assert e['cat'] == tracing.TIMELINE_CAT and e['ph'] == 'X'
        assert armed.start_ns / 1e3 <= e['ts'] + e['dur'] \
            <= armed.end_ns / 1e3
        assert e['dur'] >= 0 and e['args']['cpu_ns'] >= 0
    nexts = [e for e in events if e['name'] == 'loader_next']
    assert [e['args']['batch'] for e in nexts] == [1, 2, 3, 4, 5]
    assert all(e['tid'] == threading.current_thread().name for e in nexts)
    starts = [e['ts'] for e in nexts]
    assert starts == sorted(starts)
    # the stager's spans carry the batch they work for
    staged = [e for e in events if e['tid'] == 'petastorm-tpu-stager']
    assert staged and all('batch' in e['args'] for e in staged)
    assert {e['name'] for e in staged} & {'h2d_dispatch', 'stage_fill',
                                          'collate'}


def test_disarmed_timeline_records_nothing_and_leaves_no_hook():
    with tracing.timeline():
        with T.span('decode'):
            pass
    assert spans._trace_hook is None
    with T.span('decode'):
        pass
    assert len(T.get_recorder()) == 1


# -- the flight recorder's clock ---------------------------------------------


def test_recorder_stamps_monotonic_and_exports_wall_time(tmp_path):
    ctx = tracing.TraceContext('t-1', 1, 0, None)
    start = time.perf_counter_ns()
    tracing.record_complete('decode', start, start + 2_000_000, ctx,
                            track='worker-0')
    (event,) = T.get_recorder().snapshot()
    assert event['ts'] == start / 1e3 and event['dur'] == 2000
    path = str(tmp_path / 'trace.json')
    T.export_chrome_trace(path)
    with open(path) as f:
        (exported,) = [e for e in json.load(f)['traceEvents']
                       if e['ph'] == 'X']
    assert abs(exported['ts'] - time.time() * 1e6) < 5e6
    assert exported['dur'] == 2000


def test_worker_events_move_onto_the_receivers_clock():
    mine = recorder.CLOCK_OFFSET_US
    same = recorder.rebase([{'ts': 10.0}], [socket.gethostname(), mine + 7])
    other = recorder.rebase([{'ts': 10.0}], ['another-host', mine + 7])
    wall = recorder.rebase([{'ts': mine + 10.0}], None)
    assert same[0]['ts'] == 10.0          # one host: one monotonic clock
    assert other[0]['ts'] == pytest.approx(17.0)
    assert wall[0]['ts'] == pytest.approx(10.0)


def test_a_delta_frame_with_events_carries_the_senders_clock():
    ctx = tracing.TraceContext('t-2', 2, 0, None)
    now = time.perf_counter_ns()
    tracing.record_complete('io', now, now + 1000, ctx, track='worker-1')
    delta = load_delta_frame(T.dump_delta_frame())
    assert delta['trace_clock'] == recorder.clock_stamp()
    assert [e['name'] for e in delta['trace_events']] == ['io']
    T.merge_worker_delta(delta)
    (event,) = T.get_recorder().snapshot()
    assert event['ts'] == now / 1e3
