"""What the program under test says about itself: the CPU its stage spans
count, and its timeline of stage spans put on a device trace's clock.

The CPU readers (``metrics/*_cpu_ms_per_row.py``) read the program's
process-wide registry after the run, by metric name alone, so they read
nothing, and raise nothing, from a program that lacks the counters. The
rest works on plain tuples, ``(name, start_ns, end_ns)`` on the trace's
clock as :mod:`lib.trace` has them, so the tests drive it with synthetic
events: the timeline's events converted to that clock, the idle gaps
named by the harness span and the program span under them, the share of
the segment in which the device idles while the loader works, and the
check of the trace's clock against the host's.
"""

from lib import trace

STAGE_CPU = 'petastorm_tpu_stage_cpu_seconds_total'
STAGE_CALLS = 'petastorm_tpu_stage_calls_total'
#: the program's spans that wait rather than work (its contract's
#: ``WAIT_STAGES``): a device idle under one is not the loader's work
WAITS = frozenset(['loader_next', 'queue_wait', 'stage_backpressure',
                   'h2d_ready'])
#: the name of the tiny program the clock check dispatches; no metric
#: reads it
SYNC_PROGRAM = 'jit_trace_sync'


# -- stage CPU ----------------------------------------------------------------


def stage_cpu_ms_per_row(run, stages):
    """Milliseconds of the ``stages``' own CPU over the run (native decode
    threads included, worker processes merged) per row the loader
    delivered: the rows of every ``loader_next`` span. None without a
    device in the trace, as the device readers (the per-layer set of a run
    off the chip stays theirs), or where the program counts none of it."""
    if run.trace is None or not run.trace.reduction.devices:
        return None
    from petastorm_tpu.telemetry import get_registry
    registry = get_registry()
    cpu = [registry.counter_value(STAGE_CPU, stage=s) for s in stages]
    batches = registry.counter_value(STAGE_CALLS, stage='loader_next')
    if not any(cpu) or not batches:
        return None
    rows = batches * run.config['batch_per_chip'] * run.chips
    return sum(cpu) * 1e3 / rows


# -- the timeline on the trace's clock ----------------------------------------


def program_spans(events, zero_ns):
    """``[(stage, start_ns, end_ns, thread), ...]`` on the trace's clock
    from the program's timeline events (``ts``/``dur`` in µs of
    ``time.perf_counter_ns()``); ``zero_ns`` is the trace's zero on that
    clock."""
    return [(e['name'], e['ts'] * 1e3 - zero_ns,
             (e['ts'] + e['dur']) * 1e3 - zero_ns, e['tid'])
            for e in events]


def label_gap(gap, host_spans, spans):
    """``<harness span>/<program span>``: the harness span that overlaps
    ``gap`` the most (:func:`lib.trace.label_gap`), then the program span
    that does; on a tie the shorter program span wins. The harness's name
    alone where no program span overlaps."""
    label = trace.label_gap(gap, host_spans)
    best, key = None, None
    for stage, start, end, _ in spans:
        overlap = min(end, gap[1]) - max(start, gap[0])
        if overlap > 0 and (key is None or (overlap, start - end) > key):
            best, key = stage, (overlap, start - end)
    return label if best is None else '%s/%s' % (label, best)


def labelled_gaps(reduction, spans, top=10):
    """The longest idle gaps as ``breakdown`` has them, each named by
    :func:`label_gap`."""
    gaps = sorted(reduction.gaps, key=lambda g: g[0] - g[1])[:top]
    return [[label_gap(g, reduction._spans, spans), (g[1] - g[0]) / 1e9]
            for g in gaps]


def idle_loader_share(device_ops, spans, lo, hi):
    """Percent of ``[lo, hi]`` in which a device idles while some thread of
    the program is inside a loader work span (a span not in
    :data:`WAITS`), the mean over the devices; None with no device."""
    if not device_ops:
        return None
    work = trace.merge([(s, e) for stage, s, e, _ in spans
                        if stage not in WAITS], lo, hi)
    shares = []
    for ops in device_ops.values():
        busy = trace.merge([(s, e) for _, s, e in ops], lo, hi)
        idle = trace.idle_gaps(busy, lo, hi)
        shares.append(_overlap(idle, work) / (hi - lo))
    return 100.0 * sum(shares) / len(shares)


def _overlap(a, b):
    """Length of the intersection of two sorted lists of disjoint
    intervals."""
    total, i, j = 0, 0, 0
    while i < len(a) and j < len(b):
        total += max(0, min(a[i][1], b[j][1]) - max(a[i][0], b[j][0]))
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return total


# -- the clock check ----------------------------------------------------------


def drop_program(device_ops, programs, name=SYNC_PROGRAM):
    """``device_ops`` without the ops that ran inside a run of the program
    ``name`` on their device, so what the clock check dispatches adds no
    busy time and no op."""
    runs = {plane: [(s, e) for n, s, e in events
                    if n.partition('(')[0] == name]
            for plane, events in programs.items()}
    return {plane: [op for op in ops
                    if not any(s <= op[1] and op[2] <= e
                               for s, e in runs.get(plane, ()))]
            for plane, ops in device_ops.items()}


def clock_offset(stamps, runs):
    """Where the trace's zero sits on the host clock, from programs
    stamped on both: ``stamps`` the host's ``(dispatched, returned)`` of
    each, ns after the harness's zero; ``runs`` their ``(start, end)`` on
    the trace's clock, in the same order. A run lies inside its stamps, so
    the offset (host = trace + offset) is at least ``dispatched - start``
    and at most ``returned - end`` for every program. Returns ``(offset,
    bound)`` in ns: the middle of the interval every program allows and
    half its width; a negative bound when the programs disagree. None when
    the trace holds another number of runs than the stamps."""
    if not stamps or len(stamps) != len(runs):
        return None
    low = max(d - s for (d, _), (s, _) in zip(stamps, runs))
    high = min(r - e for (_, r), (_, e) in zip(stamps, runs))
    return (low + high) / 2, (high - low) / 2


def sync_runs(programs, name=SYNC_PROGRAM):
    """``(start, end)`` of each run of ``name`` on the first device that
    ran it, oldest first."""
    for plane in sorted(programs):
        runs = sorted((s, e) for n, s, e in programs[plane]
                      if n.partition('(')[0] == name)
        if runs:
            return runs
    return []
