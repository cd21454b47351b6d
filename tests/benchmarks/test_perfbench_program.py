"""What the program says about itself (``lib/program.py``, the
``*_cpu_ms_per_row`` readers, ``timeline_probe.py``): its spans on the
trace's clock, the gaps they name, the clock check and the CPU readers, on
synthetic input and on a toy cell run on the CPU."""

import json
import os
import types

import pytest

from lib import harness, program, trace

from conftest import REPO

CPU_READERS = ('decode_cpu_ms_per_row', 'ipc_cpu_ms_per_row',
               'h2d_cpu_ms_per_row')
ACCEPTED_READERS = ('input_wait_share', 'decode_ms_per_row',
                    'normalize_roofline', 'vit_step_mfu', 'device_idle_share')


def _gap_trace():
    # one device, idle 40-60 and 80-90 of a 0-100 segment
    ops = {'/device:TPU:0': [('fusion.1', 0, 40), ('fusion.2', 60, 80),
                             ('fusion.3', 90, 100)]}
    host = [('bench.next_batch', 30, 38), ('bench.step', 38, 70),
            ('bench.fence', 70, 100)]
    spans = [('h2d_dispatch', 41, 59, 'petastorm-tpu-stager'),
             ('collate', 30, 45, 'petastorm-tpu-stager'),
             ('loader_next', 82, 95, 'MainThread'),
             ('decode', 82, 92, 'petastorm-tpu-stager')]
    return ops, host, spans


def test_a_gap_is_named_by_the_harness_span_and_the_program_span():
    ops, host, spans = _gap_trace()
    r = trace.Reduction(ops, host, 0, 100)
    gaps = program.labelled_gaps(r, spans)
    assert gaps[0] == ['bench.step/h2d_dispatch', pytest.approx(20e-9)]
    # loader_next and decode both cover 8 of 80-90: the shorter wins
    assert gaps[1] == ['bench.fence/decode', pytest.approx(10e-9)]
    # no program span under it: the harness's name as before
    assert program.label_gap((40, 60), host, []) == 'bench.step'
    assert r.breakdown()['idle_gaps'][0][0] == 'bench.step'


def test_idle_loader_share_counts_idle_time_under_work_spans_only():
    ops, _, spans = _gap_trace()
    # idle 40-60 under collate (30-45) and h2d_dispatch (41-59): 19 ns;
    # idle 80-90 under decode (82-92): 8 ns; loader_next is a wait
    share = program.idle_loader_share(ops, spans, 0, 100)
    assert share == pytest.approx(27.0)
    two = dict(ops, **{'/device:TPU:1': [('fusion.9', 0, 100)]})
    assert program.idle_loader_share(two, spans, 0, 100) == \
        pytest.approx(13.5)
    assert program.idle_loader_share({}, spans, 0, 100) is None


def test_program_spans_move_onto_the_trace_clock():
    events = [{'name': 'decode', 'ts': 1000.5, 'dur': 2.0,
               'tid': 'petastorm-tpu-stager'}]
    assert program.program_spans(events, 1_000_000) == [
        ('decode', 500.0, 2500.0, 'petastorm-tpu-stager')]


def test_clock_offset_from_stamps_around_each_run():
    # the trace's zero sits 89 ms after the harness's: a run at trace time
    # t ran at host time t + 89e6 ns, inside its dispatch and return
    runs = [(1e6 * i, 1e6 * i + 1000) for i in range(8)]
    stamps = [(s + 89e6 - 500, e + 89e6 + 300) for s, e in runs]
    offset, bound = program.clock_offset(stamps, runs)
    assert offset == pytest.approx(89e6 - 100)
    assert bound == pytest.approx(400)
    assert program.clock_offset(stamps, runs[:7]) is None
    # a run outside its own stamps: the programs disagree
    bad = [(s + 10, e - 10) for s, e in runs]
    assert program.clock_offset(bad, runs)[1] < 0


def test_the_sync_program_is_found_and_dropped_from_the_ops():
    programs = {'/device:TPU:0': [('jit_trace_sync(3)', 10, 12),
                                  ('jit_step(9)', 20, 90),
                                  ('jit_trace_sync(3)', 95, 97)]}
    ops = {'/device:TPU:0': [('add.1 add', 10, 12), ('fusion.2', 20, 90),
                             ('add.1 add', 95, 97)]}
    assert program.sync_runs(programs) == [(10, 12), (95, 97)]
    assert program.drop_program(ops, programs) == {
        '/device:TPU:0': [('fusion.2', 20, 90)]}


def _run(reduction, **trace_extra):
    vit = harness.load_module('consumers', 'vit')
    config = harness.resolve('vit_s16.shuffled')['config']
    return types.SimpleNamespace(
        trace=types.SimpleNamespace(reduction=reduction, rows=256,
                                    seconds=4.0, wait_s=0.2,
                                    stage_s={'decode': 0.05}, **trace_extra),
        window=None, peaks=(197e12, 819e9), consumer=vit, config=config,
        flops_per_row=vit.flops_per_row(config), chips=1)


def test_the_accepted_readers_read_the_same_with_program_spans():
    ops, host, spans = _gap_trace()
    programs = {'/device:TPU:0': [('jit_bench_normalize(1)', 0, 40),
                                  ('jit_step(2)', 60, 100)]}
    sync = {'/device:TPU:0': programs['/device:TPU:0']
            + [('jit_trace_sync(3)', 120, 130)]}
    with_sync = {'/device:TPU:0': ops['/device:TPU:0']
                 + [('add.1 add', 120, 130)]}
    plain = _run(trace.Reduction(ops, host, 0, 100, programs))
    probed = _run(trace.Reduction(program.drop_program(with_sync, sync),
                                  host, 0, 100, sync),
                  program_spans=spans, stage_cpu_s={'decode': 1.0})
    for name in ACCEPTED_READERS:
        reader = harness.load_module('metrics', name)
        assert reader.read(plain) == reader.read(probed), name


def test_the_cpu_readers_divide_stage_cpu_by_the_rows_delivered():
    from petastorm_tpu import telemetry as T
    T.reset_for_tests()
    try:
        registry = T.get_registry()
        cpu = program.STAGE_CPU
        for stage, seconds in (('decode', 0.5), ('decode_fused', 0.25),
                               ('serialize', 0.1), ('deserialize', 0.3),
                               ('h2d_dispatch', 0.05)):
            registry.counter(cpu, stage=stage).inc(seconds)
        registry.counter(program.STAGE_CALLS, stage='loader_next').inc(10)
        ops, host, _ = _gap_trace()
        run = _run(trace.Reduction(ops, host, 0, 100))
        rows = 10 * run.config['batch_per_chip']
        want = {'decode_cpu_ms_per_row': 750 / rows,
                'ipc_cpu_ms_per_row': 400 / rows,
                'h2d_cpu_ms_per_row': 50 / rows}
        for name in CPU_READERS:
            reader = harness.load_module('metrics', name)
            assert reader.read(run) == pytest.approx(want[name]), name
            # no trace, or a trace with no device: nothing to read
            assert reader.read(types.SimpleNamespace(
                **dict(vars(run), trace=None))) is None
            run_cpu = _run(trace.Reduction({}, host, 0, 100))
            assert reader.read(run_cpu) is None
        # a program without the counters (or that ran no loader) reads None
        T.reset_for_tests()
        for name in CPU_READERS:
            assert harness.load_module('metrics', name).read(run) is None
    finally:
        T.reset_for_tests()


TOY_DIR = os.path.join(REPO, 'tests', 'benchmarks', 'toy')


@pytest.mark.parametrize('pool', ['thread', 'process'])
def test_the_toy_cell_runs_through_the_probe_with_the_new_metrics(
        tmp_path, monkeypatch, pool):
    """The toy cell through ``lib/`` with the traced segment of
    ``timeline_probe.py``: the program's spans on the trace's clock, stage
    and process CPU, both segments' rates, and the CPU readers over what
    the run counted."""
    import jax

    import timeline_probe
    from conftest import tiny_traffic
    from petastorm_tpu import telemetry as T
    T.reset_for_tests()
    monkeypatch.setattr(harness, 'SEARCH_DIRS', [TOY_DIR, harness.BENCH_DIR])
    monkeypatch.setattr(harness, '_traced_segment',
                        timeline_probe.traced_segment)
    with open(os.path.join(REPO, 'BENCHMARK.json')) as f:
        bench = json.load(f)
    bench['configs'] = [{'name': 'toy', 'file': os.path.relpath(
        os.path.join(TOY_DIR, 'configs', 'toy.json'), REPO)}]
    bench['workloads'] = [{'name': 'toy.linear', 'config': 'toy',
                           'traffic': 'shuffled', 'chips': 1}]
    for metric in bench['per_layer']:
        if metric['name'] in CPU_READERS:
            metric['workloads'] = ['toy.linear']
    cell = tiny_traffic(harness.resolve('toy.linear', root=REPO,
                                        benchmark=bench))
    cell['traffic']['loader']['reader_pool_type'] = pool
    result = harness.run_cell(cell, 2 ** 35 + 5, 0.5, True,
                              require=lambda chips: jax.devices()[:chips],
                              root=str(tmp_path), data_workers=2)
    assert result['correct'], result['checks']
    probe = timeline_probe._probe
    assert probe['program_spans'] > 0
    assert probe['armed_rows_per_s'] > 0 and probe['disarmed_rows_per_s'] > 0
    assert probe['stage_cpu_s']['loader_next'] >= 0
    assert probe['stage_cpu_s']['h2d_dispatch'] > 0
    assert probe['process_cpu_s']['consumer'] > 0
    assert 0 < probe['stage_cpu_share'] <= 1.5
    # the CPU has no device plane: no gaps, no clock check, no share
    assert probe['idle_gaps'] == [] and probe['idle_loader_share'] is None
    assert probe['clock_offset_ms']['both'] is None
    # the CPU readers leave a run with no device out, as the device
    # readers do; over the same counters on a device they read
    assert not set(CPU_READERS) & set(result['metrics'])
    ops = {'/device:TPU:0': [('fusion.1', 0, 10)]}
    config = cell['config']
    on_chip = types.SimpleNamespace(
        trace=types.SimpleNamespace(reduction=trace.Reduction(
            ops, [], 0, 10)), config=config, chips=1)
    read = {name: harness.load_module('metrics', name).read(on_chip)
            for name in CPU_READERS}
    assert read['decode_cpu_ms_per_row'] > 0
    assert read['h2d_cpu_ms_per_row'] > 0
    assert (read['ipc_cpu_ms_per_row'] is None) == (pool == 'thread')
    if pool == 'process':
        assert probe['process_cpu_s']['worker'] > 0
        assert probe['stage_cpu_s']['serialize'] > 0
    T.reset_for_tests()
