"""Run one cell once with the program's timeline armed in the traced
segment, and print what the program's spans say about it.

    python3 benchmarks/timeline_probe.py --workload <cell> --seed <n> \
        --seconds <s>

The run is ``run.py --trace 1``'s, with the traced segment extended: the
profiler session holds first a segment with the timeline disarmed, as the
harness traces it, then one of the same length with it armed
(``petastorm_tpu.telemetry.tracing.timeline``), and, before the first and
after the second, eight runs each of a tiny program (``jit_trace_sync``)
stamped on the host clock: the check of the trace's clock. The per-layer
metrics read the armed segment. The last line of standard output is one
JSON object: the result record under ``result`` and, beside it, the
armed segment's idle gaps named ``<harness span>/<program span>``, the
idle share under loader work, stage and process CPU, the clock check, and
both segments' rows per second.
"""

import argparse
import json
import os
import sys
import time
import types

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, BENCH_DIR)
sys.path.insert(1, os.path.dirname(BENCH_DIR))  # the program under test

#: runs of the tiny program on each side of the segments
SYNC_RUNS = 8

_probe = {}


def trace_sync(x):
    return x + 1


def _stamp_sync(sync, x, zero):
    """Dispatch ``sync`` :data:`SYNC_RUNS` times, each to its return:
    ``[(dispatched, returned), ...]`` in ns after ``zero``."""
    stamps = []
    for _ in range(SYNC_RUNS):
        dispatched = time.perf_counter()
        sync(x).block_until_ready()
        returned = time.perf_counter()
        stamps.append(((dispatched - zero) * 1e9, (returned - zero) * 1e9))
    return stamps


def _segment(drv, seconds, batch_size):
    from petastorm_tpu.telemetry import get_registry, pipeline_report
    drv.reset()
    drv.spans = []
    baseline = get_registry().snapshot()
    t0 = time.perf_counter()
    drv.run_until(t0 + seconds)
    t1 = time.perf_counter()
    return t0, t1, drv.dispatched * batch_size, \
        pipeline_report(baseline=baseline)


def traced_segment(drv, traffic, tmp, batch_size):
    """Stands in for ``harness._traced_segment``: returns what it returns
    for the armed segment, and keeps the rest in ``_probe``."""
    import jax
    import numpy as np

    from lib import harness, program, trace
    from petastorm_tpu.telemetry import tracing
    sync = jax.jit(trace_sync)
    x = jax.device_put(np.zeros(8, np.float32), jax.devices()[0])
    sync(x).block_until_ready()     # compiled outside the trace
    logdir = os.path.join(tmp, 'trace')
    options = jax.profiler.ProfileOptions()
    options.host_tracer_level = 0
    options.python_tracer_level = 0
    seconds = traffic['trace_seconds']
    zero = time.perf_counter()
    jax.profiler.start_trace(logdir, profiler_options=options)
    try:
        before = _stamp_sync(sync, x, zero)
        a0, a1, rows_off, _ = _segment(drv, seconds, batch_size)
        drv.reset()
        drv.spans = []
        with tracing.timeline() as armed:
            t0, t1, rows, report = _segment(drv, seconds, batch_size)
        after = _stamp_sync(sync, x, zero)
    finally:
        jax.profiler.stop_trace()

    def ns(t):
        return (t - zero) * 1e9

    host_spans = [(name, ns(a), ns(b)) for name, a, b in drv.spans]
    drv.spans = None
    ops, programs = trace.read_profile(trace.find_xplane(logdir))
    runs = program.sync_runs(programs)
    ops = program.drop_program(ops, programs)
    spans = program.program_spans(armed.events(), zero * 1e9)
    reduction = trace.Reduction(ops, host_spans, ns(t0), ns(t1), programs)
    stages = report['stages']
    stage_cpu = {name: s['cpu_seconds'] for name, s in stages.items()}
    offsets = {}
    for side, stamps, found in (('before', before, runs[:SYNC_RUNS]),
                                ('after', after, runs[SYNC_RUNS:]),
                                ('both', before + after, runs)):
        offsets[side] = program.clock_offset(stamps, found)
    _probe.update(
        idle_gaps=program.labelled_gaps(reduction, spans),
        idle_loader_share=program.idle_loader_share(ops, spans, ns(t0),
                                                    ns(t1)),
        stage_cpu_s=stage_cpu,
        process_cpu_s=report.get('process_cpu'),
        stage_cpu_share=(sum(stage_cpu.values())
                         / sum(report['process_cpu'].values())
                         if report.get('process_cpu') else None),
        clock_offset_ms={side: (None if v is None else
                                [v[0] / 1e6, v[1] / 1e6])
                         for side, v in offsets.items()},
        sync_runs_found=len(runs),
        program_spans=len(spans),
        armed_rows_per_s=rows / (t1 - t0),
        disarmed_rows_per_s=rows_off / (a1 - a0),
        segment_ms=[ns(t0) / 1e6, ns(t1) / 1e6])
    harness.log('probe: %s' % json.dumps(
        {k: v for k, v in _probe.items() if k != 'idle_gaps'}))
    return types.SimpleNamespace(
        seconds=t1 - t0, steps=drv.dispatched, rows=rows,
        wait_s=drv.wait_s,
        stage_s={name: s['seconds'] for name, s in stages.items()},
        reduction=reduction)


def main(argv=None):
    parser = argparse.ArgumentParser(description=__doc__.split('\n')[0])
    parser.add_argument('--workload', required=True)
    parser.add_argument('--seed', type=int, required=True)
    parser.add_argument('--seconds', type=float, required=True)
    args = parser.parse_args(argv)

    from lib import device, harness
    harness._traced_segment = traced_segment
    cell = harness.resolve(args.workload)
    try:
        result = harness.run_cell(cell, args.seed, args.seconds, True)
    except device.NoAccelerator as e:
        print('no accelerator: %s' % e, file=sys.stderr)
        return 3
    print(json.dumps(dict(_probe, result=result)), flush=True)
    return 0


if __name__ == '__main__':
    sys.exit(main())
