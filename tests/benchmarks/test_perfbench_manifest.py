"""``BENCHMARK.json`` and the files it names: every cell resolves to its
configuration, traffic, limits and metric readers, every configuration
holds the widths its own ``published`` group states, and every name, unit
and path keeps to the characters and keys the contract allows.

Each check is a function of a manifest and the directory its ``file``
paths are relative to, so that a configuration or a cell added by its own
files alone is checked with no edit here: the tests run every check on
``BENCHMARK.json`` as it stands and on a copy with a toy third
configuration (``tests/benchmarks/toy``)."""

import copy
import json
import os
import re
import shutil

import pytest

from conftest import REPO

with open(os.path.join(REPO, 'BENCHMARK.json')) as _f:
    BENCH = json.load(_f)

NAME = re.compile(r'^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$')
UNIT = re.compile(r'^[A-Za-z0-9_/%.-]{1,16}$')
PATH = re.compile(r'^[A-Za-z0-9_./-]{1,200}$')
CELLS = [w['name'] for w in BENCH['workloads']]
TOY_DIR = os.path.join(REPO, 'tests', 'benchmarks', 'toy')
#: the number that ``lib/harness.py`` compares itself in every cell, beside
#: the training numbers of ``lib/check.py`` and the dataset's and the
#: consumer's own
HARNESS_NUMBERS = {'placement_errors'}


def _metrics(bench):
    return bench['end_to_end'] + bench['per_layer']


def check_top_level_keys_and_command(bench, root):
    assert set(bench) == {'command', 'paths', 'run_seconds', 'configs',
                          'workloads', 'end_to_end', 'per_layer'}
    assert bench['command'] == ['python3', 'benchmarks/run.py']
    assert 1 <= bench['run_seconds'] <= 51
    for path in bench['paths']:
        assert PATH.match(path) and not path.startswith('/')
        assert '..' not in path.split('/')


def check_entries_carry_just_the_allowed_keys(bench, root):
    for c in bench['configs']:
        assert set(c) == {'name', 'source', 'file', 'reduced', 'why'}
    for w in bench['workloads']:
        assert set(w) == {'name', 'config', 'traffic', 'chips', 'why'}
        assert w['chips'] in (1, 4) and len(w['why']) <= 200
    for m in bench['end_to_end']:
        assert set(m) - {'workloads'} == {'name', 'unit', 'better', 'bound',
                                          'source'}
        assert 0.01 <= m['bound'] <= 0.25
        assert m['source'] in ('host_clock', 'device_trace')
    for m in bench['per_layer']:
        assert set(m) - {'workloads'} == {'name', 'unit', 'better', 'source',
                                          'layer', 'moves'}
        assert m['moves'] in {e['name'] for e in bench['end_to_end']}


def check_names_and_units_use_the_allowed_characters(bench, root):
    metrics = _metrics(bench)
    names = ([c['name'] for c in bench['configs']]
             + [w['name'] for w in bench['workloads']]
             + [m['name'] for m in metrics]
             + [w['traffic'] for w in bench['workloads']]
             + [k for c in bench['configs'] for k in c['reduced']])
    assert all(NAME.match(n) for n in names), names
    assert all(UNIT.match(m['unit']) for m in metrics)
    assert all(m['better'] in ('lower', 'higher') for m in metrics)
    for group in (bench['configs'], bench['workloads'], metrics):
        assert len({e['name'] for e in group}) == len(group)


def check_a_metric_kept_to_some_cells_names_cells_that_report_its_moves(
        bench, root):
    cells = {w['name'] for w in bench['workloads']}
    for m in _metrics(bench):
        for cell in m.get('workloads', []):
            assert cell in cells, (m['name'], cell)
    e2e = {m['name']: m for m in bench['end_to_end']}
    for m in bench['per_layer']:
        moved = e2e[m['moves']]
        for cell in m.get('workloads', []):
            assert cell in moved.get('workloads', [cell]), (m['name'], cell)


def check_setup_s_is_an_end_to_end_metric_at_its_bound(bench, root):
    setup = [m for m in bench['end_to_end'] if m['name'] == 'setup_s']
    assert setup and setup[0]['bound'] <= 0.25


def check_at_most_half_the_cells_take_four_chips(bench, root):
    fours = sum(w['chips'] == 4 for w in bench['workloads'])
    assert fours <= max(1, len(bench['workloads']) // 2)


def check_files_under_the_paths_are_named_from_name_characters(bench, root):
    for top in bench['paths']:
        for dirpath, dirnames, filenames in os.walk(os.path.join(root, top)):
            dirnames[:] = [d for d in dirnames if d != '__pycache__']
            for name in filenames:
                rel = os.path.relpath(os.path.join(dirpath, name), root)
                assert PATH.match(rel), rel


def check_cell_resolves_to_its_files(bench, root, workload):
    """The cell's configuration, traffic, limits, dataset, consumer and
    metric readers are found by name, and its limits file holds a limit for
    every number the cell compares and for no other."""
    from lib import check, harness
    cell = harness.resolve(workload, root=root, benchmark=bench)
    config = cell['config']
    dataset = harness.load_module('datasets', config['dataset']['kind'])
    consumer = harness.load_module('consumers', config['consumer'])
    for metric in cell['end_to_end'] + cell['per_layer']:
        harness.find('metrics', metric['name'], '.py')
    assert {m['name'] for m in cell['end_to_end']} >= {'setup_s'}
    assert len(cell['end_to_end']) >= 2 and cell['per_layer']
    one = {'leaf': 1.0}
    compared = set(check.training_numbers(
        {'losses': [1.0], 'mu_norms': one, 'change_norms': one},
        {'losses': [1.0], 'grad_norms': one, 'change_norms': one}, 0.9))
    compared |= set(dataset.compare_rows([], {}))
    compared |= set(consumer.feed_numbers([], config))
    assert set(cell['limits']) == compared | HARNESS_NUMBERS


def check_configuration_holds_its_published_widths(bench, root, config):
    """``widths`` equals the file's ``published`` widths on every key its
    ``reduced`` does not list, the manifest's ``reduced`` lists the file's
    keys, and the consumer takes the configuration (its ``check_config``,
    where it has one)."""
    from lib import harness
    with open(os.path.join(root, config['file'])) as f:
        body = json.load(f)
    published, reduced = body['published'], set(body['reduced'])
    assert published['table'].strip()
    assert set(config['reduced']) == reduced
    departed = {key: (body['widths'].get(key), value)
                for key, value in published['widths'].items()
                if key not in reduced and body['widths'].get(key) != value}
    assert not departed, departed
    consumer = harness.load_module('consumers', body['consumer'])
    if hasattr(consumer, 'check_config'):
        consumer.check_config(body)


MANIFEST_CHECKS = (
    check_top_level_keys_and_command,
    check_entries_carry_just_the_allowed_keys,
    check_names_and_units_use_the_allowed_characters,
    check_a_metric_kept_to_some_cells_names_cells_that_report_its_moves,
    check_setup_s_is_an_end_to_end_metric_at_its_bound,
    check_at_most_half_the_cells_take_four_chips,
    check_files_under_the_paths_are_named_from_name_characters,
)


def check_every_part(bench, root):
    """Every check above, on every cell and configuration of ``bench``."""
    for each in MANIFEST_CHECKS:
        each(bench, root)
    for w in bench['workloads']:
        check_cell_resolves_to_its_files(bench, root, w['name'])
    for c in bench['configs']:
        check_configuration_holds_its_published_widths(bench, root, c)


def test_top_level_keys_and_command():
    check_top_level_keys_and_command(BENCH, REPO)


def test_entries_carry_just_the_allowed_keys():
    check_entries_carry_just_the_allowed_keys(BENCH, REPO)


def test_names_and_units_use_the_allowed_characters():
    check_names_and_units_use_the_allowed_characters(BENCH, REPO)


def test_a_metric_kept_to_some_cells_names_cells_that_report_its_moves():
    check_a_metric_kept_to_some_cells_names_cells_that_report_its_moves(
        BENCH, REPO)


def test_setup_s_is_an_end_to_end_metric_at_its_bound():
    check_setup_s_is_an_end_to_end_metric_at_its_bound(BENCH, REPO)


def test_at_most_half_the_cells_take_four_chips():
    check_at_most_half_the_cells_take_four_chips(BENCH, REPO)


def test_files_under_the_paths_are_named_from_name_characters():
    check_files_under_the_paths_are_named_from_name_characters(BENCH, REPO)


@pytest.mark.parametrize('workload', CELLS)
def test_every_cell_resolves_to_its_files(workload):
    check_cell_resolves_to_its_files(BENCH, REPO, workload)


@pytest.mark.parametrize('config', BENCH['configs'],
                         ids=[c['name'] for c in BENCH['configs']])
def test_configuration_files_hold_the_published_widths(config):
    check_configuration_holds_its_published_widths(BENCH, REPO, config)


@pytest.fixture
def with_toy(tmp_path, monkeypatch):
    """``(bench, root)``: ``BENCHMARK.json`` with the toy configuration
    and its cell added, as a later PR would add them: its files copied
    into ``root`` beside the benchmark's own, found by name alone."""
    from lib import harness
    for top in ('benchmarks', 'tests'):
        os.symlink(os.path.join(REPO, top), str(tmp_path / top))
    toy = tmp_path / 'toy'
    shutil.copytree(TOY_DIR, str(toy))
    monkeypatch.setattr(harness, 'SEARCH_DIRS', [str(toy), harness.BENCH_DIR])
    bench = copy.deepcopy(BENCH)
    bench['paths'] = bench['paths'] + ['toy']
    bench['configs'].append({
        'name': 'toy', 'source': 'https://example.org/toy',
        'file': 'toy/configs/toy.json', 'reduced': [],
        'why': 'a toy configuration for the harness tests'})
    bench['workloads'].append({
        'name': 'toy.linear', 'config': 'toy', 'traffic': 'shuffled',
        'chips': 1, 'why': 'vectors into softmax regression'})
    return bench, str(tmp_path)


def test_a_toy_third_configuration_passes_every_check(with_toy):
    check_every_part(*with_toy)


@pytest.mark.parametrize('listed', [False, True],
                         ids=['unlisted-fails', 'listed-passes'])
def test_a_configuration_off_its_published_widths(with_toy, listed):
    """A width that departs from ``published`` fails unless ``reduced``
    lists it, in the file and in the manifest alike."""
    bench, root = with_toy
    path = os.path.join(root, 'toy', 'configs', 'toy.json')
    with open(path) as f:
        body = json.load(f)
    body['widths']['dim'] += 8
    body['dataset']['dim'] += 8
    if listed:
        body['reduced'] = {'dim': 'cut for the test'}
        bench['configs'][-1]['reduced'] = ['dim']
    with open(path, 'w') as f:
        json.dump(body, f)
    toy = bench['configs'][-1]
    if listed:
        check_configuration_holds_its_published_widths(bench, root, toy)
    else:
        with pytest.raises(AssertionError, match='dim'):
            check_configuration_holds_its_published_widths(bench, root, toy)
