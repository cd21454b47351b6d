"""Tests of the benchmark (``benchmarks/``): its yardstick on synthetic
input, its manifest, and tiny cells run in-process on the CPU."""

import copy
import os
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
BENCH_DIR = os.path.join(REPO, 'benchmarks')
if BENCH_DIR not in sys.path:
    sys.path.insert(0, BENCH_DIR)


def tiny(cell):
    """``cell`` cut to a size a test run holds, by the ``tiny`` of its
    dataset's file and of its consumer's: each configuration's files bring
    their own cut."""
    from lib import harness
    cell = copy.deepcopy(cell)
    config = cell['config']
    dataset = harness.load_module('datasets', config['dataset']['kind'])
    config['dataset'] = dataset.tiny(config['dataset'])
    cell['config'] = harness.load_module(
        'consumers', config['consumer']).tiny(config)
    return tiny_traffic(cell)


def tiny_traffic(cell):
    """``cell``'s traffic cut to a test run: a thread pool, two warm-up
    steps, a half-second traced segment, two sampled batches."""
    cell['traffic'].update(warmup_steps=2, trace_seconds=0.5,
                           sample_every=4, max_samples=2)
    cell['traffic']['loader']['reader_pool_type'] = 'thread'
    return cell


@pytest.fixture
def run_tiny(tmp_path):
    """``run_tiny(workload, seconds=0.5, trace=False, consumer=None,
    chips=None)``: the named cell, cut by :func:`tiny` (on ``chips`` CPU
    devices where given), run in this process."""
    import jax

    from lib import harness

    def run(workload, seconds=0.5, trace=False, consumer=None,
            seed=2 ** 33 + 7, chips=None):
        cell = tiny(harness.resolve(workload))
        cell['chips'] = chips or cell['chips']
        return harness.run_cell(
            cell, seed, seconds, trace,
            require=lambda chips: jax.devices()[:chips], root=str(tmp_path),
            consumer=consumer, data_workers=2)

    return run
