"""Shared test configuration.

Forces JAX onto a virtual 8-device CPU platform *before* jax is imported
anywhere, so sharding/mesh tests exercise real multi-device code paths without
TPU hardware (SURVEY.md §4: multi-node stand-in strategy).
"""

import os

# Force CPU even when the environment preselects a TPU platform: the test
# suite must exercise the virtual 8-device mesh, never the real chip.
os.environ['JAX_PLATFORMS'] = 'cpu'
_flags = os.environ.get('XLA_FLAGS', '')
if '--xla_force_host_platform_device_count' not in _flags:
    os.environ['XLA_FLAGS'] = (_flags + ' --xla_force_host_platform_device_count=8').strip()

import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax  # noqa: E402

jax.config.update('jax_platforms', 'cpu')

import numpy as np  # noqa: E402
import pytest  # noqa: E402

# Process-pool-parametrized variants each pay a multi-second ZMQ worker
# spawn (spawn-not-fork, full interpreter + pyarrow import per worker), so
# the full dummy/thread/process matrix dominates the suite's wall time. The
# quick profile (-m "not slow") keeps ONE representative process variant per
# behavior family; process-pool-SPECIFIC tests (worker error propagation,
# checkpoint-across-process) are unparametrized, never match '[process',
# and so always stay in the quick profile. The rest of the matrix runs in
# the full suite, mirroring the reference's all-flavors parametrization
# (petastorm/tests/test_end_to_end.py:42-58).
_FAST_PROCESS_KEEP = frozenset([
    'tests/test_end_to_end.py::test_simple_read_all_fields[process]',
    'tests/test_workers_pool.py::test_identity_roundtrip[process-2]',
    'tests/test_ngram.py::TestNGramEndToEnd::test_basic[process]',
    'tests/test_pushdown.py::TestExactParity::'
    'test_row_drop_partition_parity[process]',
])

# pool param id component, wherever it lands in a (possibly stacked)
# parametrize id: '[process]', '[process-2]', '[2-process]'
_PROCESS_ID_RE = __import__('re').compile(r'\[(?:[^\]]*-)?process\b')


def pytest_collection_modifyitems(config, items):
    kept = set()
    for item in items:
        if item.nodeid in _FAST_PROCESS_KEEP:
            kept.add(item.nodeid)
            continue
        if (_PROCESS_ID_RE.search(item.name)
                and not any(m.name == 'slow' for m in item.iter_markers())):
            item.add_marker(pytest.mark.slow)
    # A rename/reparametrize must not silently drop process coverage from
    # the quick profile: a keep entry is STALE when a *process* variant of
    # its test function was collected but none matched the pinned nodeid.
    # Runs that collect no process sibling (single-id selections, partial
    # files) prove nothing either way and stay silent.
    process_funcs = {i.nodeid.split('[', 1)[0] for i in items
                     if _PROCESS_ID_RE.search(i.name)}
    stale = [n for n in _FAST_PROCESS_KEEP - kept
             if n.split('[', 1)[0] in process_funcs]
    assert not stale, 'stale _FAST_PROCESS_KEEP entries: %s' % sorted(stale)


@pytest.fixture(scope='session')
def synthetic_dataset(tmp_path_factory):
    """Session-scoped canonical petastorm_tpu dataset (rich 14-field schema).

    Mirrors the reference's ``synthetic_dataset`` fixture strategy
    (``petastorm/tests/conftest.py:89-98``) without Spark: rows generated with
    :func:`tests.test_common.create_test_dataset`.
    """
    from tests.test_common import create_test_dataset
    path = str(tmp_path_factory.mktemp('synthetic')) + '/dataset'
    url = 'file://' + path
    data = create_test_dataset(url, range(100), num_files=4, rowgroup_size=10)

    # Index it like the reference's fixture does (its test_common.py builds
    # SingleField + FieldNotNull indexes right after materialization).
    from petastorm_tpu.etl.rowgroup_indexers import (
        FieldNotNullIndexer, SingleFieldIndexer,
    )
    from petastorm_tpu.etl.rowgroup_indexing import build_rowgroup_index
    build_rowgroup_index(url, [
        SingleFieldIndexer('id_index', 'id'),
        SingleFieldIndexer('partition_index', 'partition_key'),
        FieldNotNullIndexer('string_arr_not_null', 'string_array_nullable'),
    ])

    class _Dataset:
        pass

    d = _Dataset()
    d.url = url
    d.path = path
    d.data = data
    return d


@pytest.fixture(scope='session')
def scalar_dataset(tmp_path_factory):
    """Plain (non-petastorm) parquet store for make_batch_reader paths."""
    from tests.test_common import create_test_scalar_dataset
    path = str(tmp_path_factory.mktemp('scalar')) + '/dataset'
    url = 'file://' + path
    data = create_test_scalar_dataset(url, num_rows=100, num_files=4)

    class _Dataset:
        pass

    d = _Dataset()
    d.url = url
    d.path = path
    d.data = data
    return d
