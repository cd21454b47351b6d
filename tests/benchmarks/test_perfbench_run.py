"""Whole runs: the command without a TPU, and tiny cells in this process
on CPU devices with the timed path sound, replaced by the control, or
broken underneath by each fault the cells can have."""

import os
import subprocess
import sys

import numpy as np
import pytest

from conftest import REPO


def test_run_without_a_tpu_exits_nonzero_and_prints_no_result():
    env = dict(os.environ, JAX_PLATFORMS='cpu')
    proc = subprocess.run(
        [sys.executable, 'benchmarks/run.py', '--workload',
         'vit_s16.shuffled', '--seed', '1', '--seconds', '1', '--trace', '0'],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=120)
    assert proc.returncode != 0
    assert 'metrics' not in proc.stdout and '"correct"' not in proc.stdout


def test_generated_data_follows_the_seed(tmp_path):
    from lib import harness
    data = harness.load_module('datasets', 'jpeg_images')
    dataset = dict(rows=32, rowgroup_rows=8, files=2,
                   image_shape=[16, 16, 3], jpeg_quality=90, n_classes=10)
    read = []
    for name, seed in (('a', 2 ** 40 + 1), ('b', 2 ** 40 + 1), ('c', 5)):
        data.generate(str(tmp_path / name), seed, dataset, workers=2)
        read.append(data.read_rows(str(tmp_path / name), range(32), dataset))
    same = all(np.array_equal(read[0][i][1], read[1][i][1]) for i in range(32))
    differ = any(not np.array_equal(read[0][i][1], read[2][i][1])
                 for i in range(32))
    assert same and differ and sorted(read[0]) == list(range(32))


def _well_formed(result, traced):
    assert list(result)[:5] == ['correct', 'attempted', 'failed', 'metrics',
                                'device']
    assert list(result)[-1] == 'checks'
    assert result['attempted'] > 0 and result['failed'] == 0
    assert result['device']['platform'] == 'cpu'
    for name, metric in result['metrics'].items():
        assert set(metric) == {'value', 'unit'}
        assert np.isfinite(metric['value'])
    if traced:
        assert set(result['breakdown']) == {'device_ops', 'idle_gaps'}
    for check in result['checks'].values():
        assert set(check) == {'value', 'limit'}


def test_tiny_cell_gives_a_well_formed_correct_record(run_tiny):
    result = run_tiny('vit_s16.shuffled')
    _well_formed(result, traced=False)
    assert set(result['metrics']) == {'rows_per_s', 'step_p95_ms',
                                      'host_cpu_ms_per_row', 'setup_s'}
    assert result['correct'], result['checks']


def test_tiny_traced_cell_reports_host_side_layers(run_tiny):
    result = run_tiny('vit_b16.shuffled', trace=True)
    _well_formed(result, traced=True)
    # the CPU has no device plane: device metrics are left out, never 0
    assert set(result['metrics']) == {'input_wait_share', 'decode_ms_per_row'}
    assert result['correct'], result['checks']


def _config(workload):
    from lib import harness
    return harness.resolve(workload)['config']


def test_the_float8_control_fails(run_tiny):
    from lib import faults
    result = run_tiny('vit_s16.shuffled',
                      consumer=faults.control(_config('vit_s16.shuffled')))
    assert not result['correct'], result['checks']


@pytest.mark.parametrize('workload,fault,chips', [
    ('vit_s16.shuffled', 'unchanged_state', 1),
    ('vit_s16.shuffled', 'half_batch', 1),
    ('vit_b16.shuffled', 'no_exchange', 4),
])
def test_a_planted_step_fault_fails(run_tiny, workload, fault, chips):
    from lib import faults
    consumer = faults.step_fault(_config(workload), fault)
    result = run_tiny(workload, consumer=consumer, chips=chips)
    assert not result['correct'], result['checks']


def test_a_sound_cell_on_a_four_device_mesh_is_correct(run_tiny):
    """The sharded path (``data=4`` mesh, normalize under ``shard_map``)
    that a four-chip cell would run."""
    result = run_tiny('vit_b16.shuffled', chips=4)
    _well_formed(result, traced=False)
    assert result['device']['count'] == 4
    assert result['correct'], result['checks']


def test_an_answer_altered_where_it_is_produced_fails(run_tiny, monkeypatch):
    """The loader's batches carry one row's image under another's id."""
    import petastorm_tpu.jax as pjax

    from lib import faults
    monkeypatch.setattr(pjax, 'make_jax_loader',
                        faults.altered_loader(pjax.make_jax_loader))
    result = run_tiny('vit_s16.shuffled')
    assert not result['correct'], result['checks']
    assert result['checks']['luma_gap']['value'] > \
        result['checks']['luma_gap']['limit']


TOY_DIR = os.path.join(REPO, 'tests', 'benchmarks', 'toy')


@pytest.mark.parametrize('trace', [False, True])
def test_a_new_dataset_and_consumer_run_by_name_alone(tmp_path, monkeypatch,
                                                      trace):
    """A toy cell (``tests/benchmarks/toy``: ``.npy`` vectors into softmax
    regression), named only in a manifest and its own files, runs through
    the harness with no edit to ``lib/``."""
    import json

    import jax

    from conftest import tiny_traffic
    from lib import harness
    monkeypatch.setattr(harness, 'SEARCH_DIRS', [TOY_DIR, harness.BENCH_DIR])
    with open(os.path.join(REPO, 'BENCHMARK.json')) as f:
        bench = json.load(f)
    bench['configs'] = [{'name': 'toy', 'file': os.path.relpath(
        os.path.join(TOY_DIR, 'configs', 'toy.json'), REPO)}]
    bench['workloads'] = [{'name': 'toy.linear', 'config': 'toy',
                           'traffic': 'shuffled', 'chips': 1}]
    cell = tiny_traffic(harness.resolve('toy.linear', root=REPO,
                                        benchmark=bench))
    result = harness.run_cell(cell, 2 ** 35 + 3, 0.5, trace,
                              require=lambda chips: jax.devices()[:chips],
                              root=str(tmp_path), data_workers=2)
    _well_formed(result, traced=trace)
    assert result['correct'], result['checks']
    assert {'vector_errors', 'feed_errors'} <= set(result['checks'])
    # metrics kept to other cells (``workloads``) are not read here
    assert 'vit_step_mfu' not in result['metrics']


def _module_name(lowered):
    import re
    return re.search(r'module @([A-Za-z0-9_]+)', lowered.as_text()).group(1)


@pytest.mark.parametrize('chips', [1, 4])
def test_the_vit_consumer_names_its_programs_as_the_trace_will(chips):
    """The readers find the normalize and step programs by these names; on
    a chip a name that matches nothing raises."""
    import jax
    import jax.numpy as jnp

    from conftest import tiny
    from lib import harness
    cell = tiny(harness.resolve('vit_s16.shuffled'))
    config = cell['config']
    vit = harness.load_module('consumers', 'vit')
    mesh = None
    if chips > 1:
        from petastorm_tpu.parallel.mesh import make_mesh
        mesh = make_mesh(data=chips, devices=jax.devices()[:chips])
    normalize = vit._normalize_fn(config, mesh)
    images = jnp.zeros((8 * chips, 32, 32, 3), jnp.uint8)
    assert _module_name(normalize.lower(images)) in vit.NORMALIZE_PROGRAMS
    from jax.sharding import SingleDeviceSharding
    params = vit.init_params(1, config, SingleDeviceSharding(jax.devices()[0]))
    opt_state = vit.optimizer(config['optimizer']).init(params)
    step = vit.train_step(config)
    lowered = step.lower(params, opt_state, images.astype(jnp.bfloat16),
                         jnp.zeros((8 * chips,), jnp.int32))
    assert _module_name(lowered) in vit.STEP_PROGRAMS


@pytest.mark.parametrize('metric', ['vit_step_mfu', 'normalize_roofline'])
def test_device_readers_raise_where_no_program_ran_on_a_chip(metric):
    import types

    from lib import harness, trace
    vit = harness.load_module('consumers', 'vit')
    reader = harness.load_module('metrics', metric)
    config = harness.resolve('vit_s16.shuffled')['config']
    empty = trace.Reduction({'/device:TPU:0': [('fusion.1', 0, 10)]}, [],
                            0, 100, {'/device:TPU:0': [('jit_other(1)', 0,
                                                        10)]})
    run = types.SimpleNamespace(
        trace=types.SimpleNamespace(reduction=empty, rows=128),
        peaks=(197e12, 819e9), consumer=vit, config=config,
        flops_per_row=vit.flops_per_row(config), chips=1)
    with pytest.raises(ValueError):
        reader.read(run)
    # no trace, or no peak (the CPU): nothing to read, never a 0
    assert reader.read(types.SimpleNamespace(**dict(vars(run), trace=None))) \
        is None
    assert reader.read(types.SimpleNamespace(**dict(vars(run), peaks=None))) \
        is None
    # one run of each program, 50 us of normalize and 40 ms of step
    found = trace.Reduction({}, [], 0, 100, {'/device:TPU:0': [
        ('jit_bench_normalize(7)', 0, 50_000),
        ('jit_step(9)', 0, 40_000_000)]})
    run.trace.reduction = found
    value = reader.read(run)
    want = {'vit_step_mfu': 100 * 27.44e9 * 128 / (0.04 * 197e12),
            'normalize_roofline': 100 * (128 * 224 * 224 * 3 * 3 / 819e9)
            / 50e-6}[metric]
    assert value == pytest.approx(want, rel=1e-3)


@pytest.mark.parametrize('metric', ['vit_step_mfu', 'normalize_roofline'])
def test_device_readers_read_a_share_a_chip_on_four_chips(metric):
    """Each chip runs its own copy of a program on its ``batch_per_chip``
    rows: four planes of one run each read the one-plane share, not four
    times it."""
    import types

    from lib import harness, trace
    vit = harness.load_module('consumers', 'vit')
    reader = harness.load_module('metrics', metric)
    config = harness.resolve('vit_s16.shuffled')['config']
    runs = [('jit_bench_normalize_mesh(7)', 0, 100_000),
            ('jit_step(9)', 0, 40_000_000)]

    def read(planes):
        found = trace.Reduction({}, [], 0, 100, {
            '/device:TPU:%d' % i: list(runs) for i in range(planes)})
        return reader.read(types.SimpleNamespace(
            trace=types.SimpleNamespace(reduction=found),
            peaks=(197e12, 819e9), consumer=vit, config=config,
            flops_per_row=vit.flops_per_row(config), chips=planes))

    assert read(4) == pytest.approx(read(1), rel=1e-9)
    assert read(4) < 100


def test_the_vit_consumer_refuses_a_grid_it_cannot_run():
    from lib import harness
    vit = harness.load_module('consumers', 'vit')
    config = harness.resolve('vit_s16.shuffled')['config']
    vit.check_config(config)
    for key, value in (('patch_size', 15), ('channels', 1)):
        bad = dict(config, widths=dict(config['widths'], **{key: value}))
        with pytest.raises(ValueError):
            vit.check_config(bad)
