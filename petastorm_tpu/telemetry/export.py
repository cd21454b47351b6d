"""Exporters: JSONL snapshots, Prometheus text format, pipeline report.

All three read a :class:`~petastorm_tpu.telemetry.registry.MetricsRegistry`
(the process-wide one by default) and never mutate it.
"""

import json
import time

from petastorm_tpu.telemetry.registry import PROCESS_CPU, get_registry
from petastorm_tpu.telemetry.spans import (
    STAGE_CALLS, STAGE_CPU_SECONDS, STAGE_SECONDS, STAGES,
)

#: stall-verdict horizon in sampling windows (~30s at the 0.5s default):
#: recent enough that startup/idle phases age out of the verdict quickly
_VERDICT_WINDOWS = 60

# -- JSONL -------------------------------------------------------------------


def write_jsonl_snapshot(path_or_file, registry=None, extra=None):
    """Append one JSON line holding the registry's full state.

    Round-trip contract (``tests/test_telemetry.py``): the parsed line's
    ``counters``/``gauges``/``histograms`` equal ``registry.snapshot()``.
    ``extra`` (a dict) rides along under its own keys for run metadata
    (benchmark args, wall time); reserved keys are not overwritten.
    """
    registry = registry or get_registry()
    record = dict(extra or {})
    record.update(registry.snapshot())
    record.setdefault('ts', time.time())
    # structured anomaly events ride every snapshot line when any were
    # recorded (live-observability plane, telemetry/timeseries.py): the
    # counters alone say HOW MANY fired, the events say WHEN and WHY
    from petastorm_tpu.telemetry import timeseries
    events = timeseries.recent_anomalies()
    if events:
        record.setdefault('anomalies', events)
    line = json.dumps(record, sort_keys=True)
    if hasattr(path_or_file, 'write'):
        path_or_file.write(line + '\n')
    else:
        with open(path_or_file, 'a') as f:
            f.write(line + '\n')


def read_jsonl_snapshots(path):
    """Parse every snapshot line of a JSONL metrics file (oldest first)."""
    out = []
    with open(path) as f:
        for line in f:
            line = line.strip()
            if line:
                out.append(json.loads(line))
    return out


# -- Prometheus text format --------------------------------------------------


def _metric_families(keys):
    """Group snapshot keys (``name`` / ``name{labels}``) by family name,
    preserving each family's series order."""
    families = {}
    for key in sorted(keys):
        name = key.split('{', 1)[0]
        families.setdefault(name, []).append(key)
    return families


def prometheus_text(registry=None):
    """Registry state in the Prometheus text exposition format: one
    ``# TYPE`` line per family, label values already escaped (the registry
    escapes at key-construction time), histograms with CUMULATIVE
    ``_bucket`` series (``le`` ascending through ``+Inf``), ``_sum`` and
    ``_count``."""
    registry = registry or get_registry()
    snap = registry.snapshot()
    lines = []
    for name, keys in _metric_families(snap['counters']).items():
        lines.append('# TYPE %s counter' % name)
        for key in keys:
            lines.append('%s %s' % (key, _fmt(snap['counters'][key])))
    for name, keys in _metric_families(snap['gauges']).items():
        lines.append('# TYPE %s gauge' % name)
        for key in keys:
            lines.append('%s %s' % (key, _fmt(snap['gauges'][key])))
    for name, keys in _metric_families(snap['histograms']).items():
        lines.append('# TYPE %s histogram' % name)
        for key in keys:
            state = snap['histograms'][key]
            cumulative = 0
            for bound, count in zip(state['buckets'] + [float('inf')],
                                    state['counts']):
                cumulative += count
                lines.append('%s %d' % (
                    _series(key, '_bucket', le=_le(bound)), cumulative))
            lines.append('%s %s' % (_series(key, '_sum'),
                                    _fmt(state['sum'])))
            lines.append('%s %d' % (_series(key, '_count'), state['count']))
    return '\n'.join(lines) + '\n'


def _le(bound):
    if bound == float('inf'):
        return '+Inf'
    text = repr(bound)
    return text[:-2] if text.endswith('.0') else text


def _fmt(value):
    if float(value).is_integer():
        return str(int(value))
    return repr(float(value))


def _series(key, suffix, **extra_labels):
    """``name{labels}`` → ``name<suffix>{labels + extra}``."""
    if '{' in key:
        name, labels = key.split('{', 1)
        labels = labels[:-1]
    else:
        name, labels = key, ''
    for k, v in sorted(extra_labels.items()):
        pair = '%s="%s"' % (k, v)
        labels = '%s,%s' % (labels, pair) if labels else pair
    return '%s%s{%s}' % (name, suffix, labels) if labels \
        else '%s%s' % (name, suffix)


# -- pipeline report ---------------------------------------------------------


def _label_of(key, label):
    """Value of one label in a ``name{label="x",...}`` snapshot key, or
    None when the key does not carry it. Anchored on the preceding
    ``{``/``,`` so ``srckind`` never matches a lookup for ``kind``."""
    for marker in ('{%s="' % label, ',%s="' % label):
        i = key.find(marker)
        if i < 0:
            continue
        start = i + len(marker)
        j = key.find('"', start)
        return key[start:j] if j > 0 else None
    return None


def _stage_of(key):
    """Stage label value of a ``...{stage="x"}`` key, or None."""
    return _label_of(key, 'stage')


def pipeline_report(registry=None, wall_time_s=None, baseline=None,
                    attributor=None):
    """Per-stage time breakdown + stall attribution, the rendering the
    ISSUE's acceptance gate reads.

    :param wall_time_s: when given, per-stage ``share`` is seconds/wall and
        ``attributed_fraction`` says how much of the wall the named stages
        explain (the dummy-reader benchmark asserts ≥0.95). Without it,
        shares are relative to the summed stage time (worker stages run in
        parallel threads, so their sum can legitimately exceed any wall).
    :param baseline: an earlier ``registry.snapshot()``; stage seconds,
        stage CPU and process CPU are reported as the increase since it
        (scoping a report to one measurement window).

    Each stage carries ``cpu_seconds`` beside its wall ``seconds`` (its
    own CPU, native pool threads included; telemetry/spans.py), and
    ``process_cpu`` holds the processes' CPU by role (``consumer``,
    ``worker``): the share of the second that the first explains is how
    much of the host's CPU the spans account for.
    :param attributor: stall attributor to read windows from (default: the
        process-wide one).
    """
    from petastorm_tpu.telemetry.stall import get_attributor
    registry = registry or get_registry()
    attributor = attributor or get_attributor()
    registry.refresh_process_cpu()
    seconds = registry.counters_with_prefix(STAGE_SECONDS)
    cpu = registry.counters_with_prefix(STAGE_CPU_SECONDS)
    calls = registry.counters_with_prefix(STAGE_CALLS)
    base_seconds = (baseline or {}).get('counters', {})
    base_calls = base_seconds

    stages = {}
    for key, value in seconds.items():
        stage = _stage_of(key)
        if stage is None:
            continue
        value -= base_seconds.get(key, 0.0)
        stages[stage] = {'seconds': max(value, 0.0)}
    for key, value in calls.items():
        stage = _stage_of(key)
        if stage in stages:
            stages[stage]['calls'] = int(value - base_calls.get(key, 0))
    for key, value in cpu.items():
        stage = _stage_of(key)
        if stage in stages:
            stages[stage]['cpu_seconds'] = max(
                value - base_seconds.get(key, 0.0), 0.0)
    total = sum(s['seconds'] for s in stages.values())
    denominator = wall_time_s if wall_time_s else total
    for stage in stages.values():
        stage.setdefault('calls', 0)
        stage.setdefault('cpu_seconds', 0.0)
        stage['share'] = (stage['seconds'] / denominator
                          if denominator else 0.0)
    process_cpu = {}
    for key, value in registry.counters_with_prefix(PROCESS_CPU).items():
        role = _label_of(key, 'role')
        if role is not None:
            process_cpu[role] = max(value - base_seconds.get(key, 0.0), 0.0)

    producer_wait, consumer_wait = attributor.totals()
    report = {
        'stages': dict(sorted(stages.items(),
                              key=lambda kv: -kv[1]['seconds'])),
        'stage_order': list(STAGES),
        'total_stage_seconds': round(total, 6),
        'process_cpu': process_cpu,
        'wall_time_s': wall_time_s,
        'attributed_fraction': (round(total / wall_time_s, 4)
                                if wall_time_s else None),
        'stall': {
            # lifetime clocks (include spin-up) ...
            'producer_wait_s': round(producer_wait, 6),
            'consumer_wait_s': round(consumer_wait, 6),
            # ... but the VERDICT covers only the recent window horizon:
            # the process-wide attributor has no first-delivery reset
            # (unlike JaxLoader's), and a startup's consumer waits would
            # otherwise read as 'producer-bound' for the whole run
            'verdict': attributor.verdict(last_n=_VERDICT_WINDOWS),
            'windows': attributor.windows()[-20:],
        },
    }
    overlap = _h2d_overlap_share(stages)
    if overlap is not None:
        report['h2d_overlap_share'] = overlap
    cache = _cache_section(registry)
    if cache is not None:
        report['cache'] = cache
    decoded = decoded_cache_section(registry, baseline=baseline,
                                    stages=stages)
    if decoded is not None:
        report['decoded_cache'] = decoded
    service = _service_section(registry)
    if service is not None:
        report['service'] = service
    pushdown = _pushdown_section(registry)
    if pushdown is not None:
        report['pushdown'] = pushdown
    readahead = _readahead_section(registry)
    if readahead is not None:
        report['readahead'] = readahead
    peer = _peer_cache_section(registry)
    if peer is not None:
        report['peer_cache'] = peer
    write = _write_section(registry)
    if write is not None:
        report['write'] = write
    pipesan = _sanitizer_section(registry)
    if pipesan is not None:
        report['pipesan'] = pipesan
    anomalies = _anomalies_section(registry)
    if anomalies is not None:
        report['anomalies'] = anomalies
    autotune = _staging_autotune_section(registry)
    if autotune is not None:
        report['staging_autotune'] = autotune
    critical = _critical_path_section()
    if critical is not None:
        report['critical_path'] = critical
    slo = _slo_section()
    if slo is not None:
        report['slo'] = slo
    return report


def _critical_path_section():
    """Critical-path engine analysis (telemetry/critpath.py) — present
    only when the flight recorder holds stage events (tracing was on),
    so untraced pipelines keep their report shape unchanged."""
    from petastorm_tpu.telemetry import recorder
    if not len(recorder.get_recorder()):
        return None
    from petastorm_tpu.telemetry import critpath
    return critpath.critpath_section()


def _slo_section():
    """SLO burn/budget accounting (telemetry/slo.py) — present only when
    ``PETASTORM_TPU_SLO`` arms a policy, so objective-less pipelines
    keep their report shape unchanged."""
    from petastorm_tpu.telemetry import slo
    return slo.slo_section()


def _h2d_overlap_share(stages):
    """Share of staging-engine time NOT spent blocked on an in-flight
    transfer (``h2d_ready``): 1.0 means every dispatched transfer landed
    while the consumer computed / the next slot filled — fully overlapped;
    low values mean the link itself is the wall. Present only when the
    arena ran (the stages exist)."""
    fill = stages.get('stage_fill', {}).get('seconds', 0.0)
    dispatch = stages.get('h2d_dispatch', {}).get('seconds', 0.0)
    ready = stages.get('h2d_ready', {}).get('seconds', 0.0)
    total = fill + dispatch + ready
    if not total:
        return None
    return round(1.0 - ready / total, 4)


def _cache_section(registry):
    from petastorm_tpu.cache import (
        CACHE_BYTES_EVICTED, CACHE_BYTES_WRITTEN, CACHE_EVICTIONS,
        CACHE_HITS, CACHE_MISSES, CACHE_SIZE_BYTES,
    )
    hits = registry.counter_value(CACHE_HITS)
    misses = registry.counter_value(CACHE_MISSES)
    if not hits and not misses:
        return None
    return {
        'hits': int(hits),
        'misses': int(misses),
        'evictions': int(registry.counter_value(CACHE_EVICTIONS)),
        'bytes_written': int(registry.counter_value(CACHE_BYTES_WRITTEN)),
        'bytes_evicted': int(registry.counter_value(CACHE_BYTES_EVICTED)),
        # one gauge series per process (pid label), because gauge merges
        # are last-writer-wins and interleaved worker updates would
        # flicker. Every process tracks the SAME shared cache directory
        # (each LocalDiskCache's running total covers the whole dir), so
        # the aggregate is the freshest estimate — the max — never a sum,
        # which would overcount by the process count.
        'size_bytes': int(max(
            registry.gauges_with_prefix(CACHE_SIZE_BYTES).values(),
            default=0)),
        'hit_rate': round(hits / (hits + misses), 4),
    }


def classify_cache_phase(stages, hits, misses):
    """'cache-bound' / 'decode-bound' / 'mixed' verdict for a pass over a
    decoded-row-group cache — the "epoch 2+ should be cache-bound"
    contract. Cache-bound means hits dominate (≥80%) AND the pass's
    decode-side time (io+decode+transform) no longer dominates its
    hit-serving time — i.e. the pipeline is reading materialized batches,
    not re-paying the 71% io+decode share. ``stages`` is a
    :func:`pipeline_report`-shaped per-stage dict (baseline-scoped when
    the report was)."""
    total = hits + misses
    if total <= 0:
        return None
    hit_rate = hits / total

    def _sec(stage):
        return stages.get(stage, {}).get('seconds', 0.0)

    decode_side = _sec('io') + _sec('decode') + _sec('transform')
    hit_side = _sec('cache_hit_read')
    if hit_rate >= 0.8 and (decode_side <= hit_side or decode_side < 0.05):
        return 'cache-bound'
    if hit_rate <= 0.2:
        return 'decode-bound'
    return 'mixed'


def decoded_cache_section(registry=None, baseline=None, stages=None):
    """Materialized decoded-row-group cache activity (None when the cache
    never ran), with the :func:`classify_cache_phase` verdict. ``baseline``
    (an earlier ``registry.snapshot()``) scopes the counters to one
    measurement window — pass the snapshot taken between epochs to ask
    "was THIS pass cache-bound?"."""
    from petastorm_tpu.materialized_cache import (
        DECODED_CACHE_BYTES_READ, DECODED_CACHE_BYTES_WRITTEN,
        DECODED_CACHE_COPY_READS, DECODED_CACHE_EVICTIONS,
        DECODED_CACHE_HITS, DECODED_CACHE_MEM_HITS, DECODED_CACHE_MISSES,
        DECODED_CACHE_MMAP_READS, DECODED_CACHE_SIZE_BYTES,
    )
    registry = registry or get_registry()
    base = (baseline or {}).get('counters', {})

    def value(name):
        return registry.counter_value(name) - base.get(name, 0)

    hits = value(DECODED_CACHE_HITS)
    misses = value(DECODED_CACHE_MISSES)
    if not hits and not misses:
        return None
    return {
        'hits': int(hits),
        'misses': int(misses),
        'mem_hits': int(value(DECODED_CACHE_MEM_HITS)),
        'evictions': int(value(DECODED_CACHE_EVICTIONS)),
        'bytes_written': int(value(DECODED_CACHE_BYTES_WRITTEN)),
        'bytes_read': int(value(DECODED_CACHE_BYTES_READ)),
        'mmap_reads': int(value(DECODED_CACHE_MMAP_READS)),
        'copy_reads': int(value(DECODED_CACHE_COPY_READS)),
        # per-process gauges over ONE shared directory: aggregate with
        # max (freshest estimate), never sum — same rule as the raw cache
        'size_bytes': int(max(
            registry.gauges_with_prefix(DECODED_CACHE_SIZE_BYTES).values(),
            default=0)),
        'hit_rate': round(hits / (hits + misses), 4),
        'verdict': classify_cache_phase(stages or {}, hits, misses),
    }


def _service_section(registry):
    """Disaggregated-fleet health, from the gauges/counters the service
    dispatcher mirrors into the registry — present only when a service
    pool ran in this process (a worker ever registered), so local-pool
    reports stay unchanged. Re-ventilation/dedupe make the exactly-once
    machinery's activity visible without reading dispatcher logs."""
    from petastorm_tpu.service.dispatcher import (
        SERVICE_DUPLICATE_DONE, SERVICE_ITEMS_ASSIGNED,
        SERVICE_ITEMS_PENDING, SERVICE_PLACEMENT_HITS,
        SERVICE_PLACEMENT_MISSES, SERVICE_POISONED, SERVICE_PREEMPTIONS,
        SERVICE_RETRIES, SERVICE_REVENTILATED, SERVICE_WORKERS_ALIVE,
        SERVICE_WORKERS_REGISTERED,
    )
    from petastorm_tpu.service.standby import (
        SERVICE_FAILOVERS, SERVICE_REPLICATION_LAG,
    )
    gauges = registry.gauges_with_prefix('petastorm_tpu_service_')
    if not gauges:
        return None
    placement_hits = registry.counter_value(SERVICE_PLACEMENT_HITS)
    placement_misses = registry.counter_value(SERVICE_PLACEMENT_MISSES)
    placed = placement_hits + placement_misses
    return {
        'workers_alive': int(registry.gauge_value(SERVICE_WORKERS_ALIVE)),
        'workers_registered': int(
            registry.gauge_value(SERVICE_WORKERS_REGISTERED)),
        'items_pending': int(registry.gauge_value(SERVICE_ITEMS_PENDING)),
        'items_assigned': int(registry.gauge_value(SERVICE_ITEMS_ASSIGNED)),
        'reventilated': int(registry.counter_value(SERVICE_REVENTILATED)),
        'duplicate_done': int(
            registry.counter_value(SERVICE_DUPLICATE_DONE)),
        'retried': int(registry.counter_value(SERVICE_RETRIES)),
        'poisoned': int(registry.counter_value(SERVICE_POISONED)),
        # high availability + QoS (docs/service.md): how many times THIS
        # process promoted a standby, how stale its mirror is, and what
        # the scheduler did about priorities and warm caches
        'failovers': int(registry.counter_value(SERVICE_FAILOVERS)),
        'replication_lag_s': round(
            registry.gauge_value(SERVICE_REPLICATION_LAG), 3),
        'preemptions': int(registry.counter_value(SERVICE_PREEMPTIONS)),
        'placement_hits': int(placement_hits),
        'placement_misses': int(placement_misses),
        'placement_hit_share': (round(placement_hits / placed, 4)
                                if placed else None),
    }


def _pushdown_section(registry):
    """Selective-read (query-shaped reads) activity: plan-time pruning
    from the consumer-local planner summary, late-materialized rows from
    the fleet-merged worker counters — present only when a predicate
    planner ever ran (or workers late-materialized), so predicate-free
    pipelines keep their report shape unchanged. ``declines`` carries
    the reasons pruning proved nothing (``arbitrary-predicate``,
    ``no-statistics``, ``low-selectivity``) — the "My selective read is
    still full-scan-priced" runbook in docs/troubleshoot.md reads them.
    """
    from petastorm_tpu import pushdown
    summary = pushdown.planner_summary()
    pruned = registry.counter_value(pushdown.ROWGROUPS_PRUNED)
    late = registry.counter_value(pushdown.LATE_MATERIALIZED_ROWS)
    if not summary['planner_runs'] and not pruned and not late:
        return None
    considered = summary['rowgroups_considered']
    return {
        'planner_runs': summary['planner_runs'],
        'rowgroups_considered': considered,
        'rowgroups_pruned': int(pruned),
        'rows_pruned': int(registry.counter_value(pushdown.ROWS_PRUNED)),
        'late_materialized_rows': int(late),
        # share of considered row-groups proven empty, from the LOCAL
        # planner's tallies (the registry counter can include other
        # processes' plans; mixing denominators would lie)
        'prune_share': (round(summary['rowgroups_pruned'] / considered, 4)
                        if considered else None),
        'declines': summary['declines'],
    }


def _readahead_section(registry):
    """Wire-speed I/O plane activity (petastorm_tpu/readahead.py) —
    present only when the plane ever served, missed or degraded (the
    counters are fleet-merged over the pool delta channels), so
    readahead-less pipelines keep their report shape unchanged. Pool
    occupancy/depth come from THIS process's live managers; the "Decode
    is waiting on storage (io-bound)" runbook in docs/troubleshoot.md
    reads the hit share, mean coalesced-read size and degrade reasons."""
    from petastorm_tpu import readahead
    hits = registry.counter_value(readahead.READAHEAD_HITS)
    misses = registry.counter_value(readahead.READAHEAD_MISSES)
    degraded = {}
    for key, value in registry.counters_with_prefix(
            readahead.READAHEAD_DEGRADED).items():
        reason = _label_of(key, 'reason') or 'unknown'
        degraded[reason] = degraded.get(reason, 0) + int(value)
    if not hits and not misses and not degraded:
        return None
    bytes_fetched = registry.counter_value(readahead.READAHEAD_BYTES)
    reads = registry.counter_value(readahead.READAHEAD_COALESCED_READS)
    used, budget = readahead.pool_status()
    return {
        'hits': int(hits),
        'misses': int(misses),
        'hit_share': (round(hits / (hits + misses), 4)
                      if hits or misses else None),
        'bytes_fetched': int(bytes_fetched),
        'coalesced_reads': int(reads),
        'mean_coalesced_bytes': (int(bytes_fetched / reads) if reads
                                 else None),
        'degraded': degraded,
        'depth': readahead.current_depth(),
        'pool_bytes': int(used),
        'pool_budget_bytes': int(budget),
    }


def _peer_cache_section(registry):
    """Fleet-wide decoded-cache tier activity (service/peer_cache.py) —
    present only when a peer fetch ever hit, missed or an evict hint
    shipped (the worker-side counters are fleet-merged over the DONE
    delta channels), so host-local pipelines keep their report shape.
    The "Warm dataset still decode-priced on a fleet" runbook in
    docs/troubleshoot.md reads the hit share and degrade reasons."""
    from petastorm_tpu.service import peer_cache
    hits = registry.counter_value(peer_cache.PEER_CACHE_HITS)
    misses = 0
    degraded = {}
    for key, value in registry.counters_with_prefix(
            peer_cache.PEER_CACHE_MISSES).items():
        reason = _label_of(key, 'reason') or 'unknown'
        degraded[reason] = degraded.get(reason, 0) + int(value)
        misses += int(value)
    hints = registry.counter_value(peer_cache.PEER_CACHE_EVICT_HINTS)
    if not hits and not misses and not hints:
        return None
    return {
        'hits': int(hits),
        'misses': int(misses),
        'hit_share': (round(hits / (hits + misses), 4)
                      if hits or misses else None),
        'bytes_fetched': int(
            registry.counter_value(peer_cache.PEER_CACHE_BYTES)),
        'degraded': degraded,
        'evict_hints': int(hints),
    }


def _write_section(registry):
    """Distributed write plane activity (petastorm_tpu/write/) — present
    only when this process (or its fleet, via the pool delta channels)
    wrote, committed or compacted, so read-only pipelines keep their
    report shape unchanged. The committed generation is a gauge: the
    latest manifest swap this process published."""
    from petastorm_tpu.write import compact, writer
    from petastorm_tpu.write import manifest as write_manifest
    rows = registry.counter_value(writer.WRITE_ROWS)
    commits = registry.counter_value(write_manifest.WRITE_COMMITS)
    compact_runs = registry.counter_value(compact.COMPACT_RUNS)
    if not rows and not commits and not compact_runs:
        return None
    return {
        'rows_written': int(rows),
        'bytes_written': int(registry.counter_value(writer.WRITE_BYTES)),
        'files_written': int(registry.counter_value(writer.WRITE_FILES)),
        'commits': int(commits),
        'generation': int(registry.gauge_value(
            write_manifest.MANIFEST_GENERATION) or 0),
        'compact_runs': int(compact_runs),
        'files_folded': int(registry.counter_value(
            compact.COMPACT_FILES_FOLDED)),
    }


def _sanitizer_section(registry):
    """pipesan runtime-sanitizer findings — present when the sanitizer is
    armed (``PETASTORM_TPU_SANITIZE=1``) or violations were recorded, so
    unarmed reports stay unchanged. ``recent`` carries the last few
    structured violations from the in-process ring (kind, detail, ts);
    the counters aggregate across the pool delta channels like every
    other metric."""
    from petastorm_tpu import sanitizer
    by_kind = {}
    for key, value in registry.counters_with_prefix(
            sanitizer.SANITIZER_VIOLATIONS).items():
        kind = _label_of(key, 'kind') or 'unknown'
        by_kind[kind] = by_kind.get(kind, 0) + int(value)
    total = sum(by_kind.values())
    enabled = sanitizer.sanitize_enabled()
    if not enabled and not total:
        return None
    return {
        'enabled': enabled,
        'violations': total,
        'by_kind': by_kind,
        'views_guarded': int(registry.counter_value(
            sanitizer.SANITIZER_VIEWS_GUARDED)),
        'canary_checks': int(registry.counter_value(
            sanitizer.SANITIZER_CANARY_CHECKS)),
        'recent': sanitizer.violations()[-5:],
    }


def _anomalies_section(registry):
    """Anomaly-detector findings (live observability plane) — present
    when events were ever recorded (counter includes fleet-aggregated
    worker events) or a collector samples in this process, so pipelines
    without the plane armed keep their report shape unchanged. ``recent``
    carries the last few structured events from the in-process ring,
    each naming its troubleshoot.md runbook."""
    from petastorm_tpu.telemetry import timeseries
    by_kind = {}
    for key, value in registry.counters_with_prefix(
            timeseries.ANOMALY_EVENTS).items():
        kind = _label_of(key, 'kind') or 'unknown'
        by_kind[kind] = by_kind.get(kind, 0) + int(value)
    recent = timeseries.recent_anomalies(5)
    if not by_kind and not recent and not timeseries.collector_running():
        return None
    return {
        'total': sum(by_kind.values()),
        'by_kind': by_kind,
        'recent': recent,
    }


def _staging_autotune_section(registry):
    """Staging-autotuner decisions (jax/autotune.py) — present only when
    the control loop ever adjusted something (counter includes
    fleet-aggregated remote decisions), so untouched pipelines keep
    their report shape unchanged. ``recent`` carries this process's
    last few structured decision entries; the counter is the fleet
    total by action. Only consulted when the autotune module is already
    loaded: decisions can only originate in a process running a jax
    loader, so a lean process (service worker, torch consumer) must
    never pay the jax-bridge import for a section that would be None."""
    import sys
    autotune = sys.modules.get('petastorm_tpu.jax.autotune')
    if autotune is None:
        return None
    by_action = {}
    for key, value in registry.counters_with_prefix(
            autotune.AUTOTUNE_DECISIONS).items():
        action = _label_of(key, 'action') or 'unknown'
        by_action[action] = by_action.get(action, 0) + int(value)
    recent = autotune.recent_decisions(10)
    if not by_action and not recent:
        return None
    return {
        'total': sum(by_action.values()),
        'by_action': by_action,
        'recent': recent,
    }


def format_pipeline_report(report):
    """Human-readable rendering of :func:`pipeline_report` (one stage per
    line, canonical pipeline order first, then any extra stages)."""
    lines = ['pipeline stages (share of %s):'
             % ('wall time' if report['wall_time_s'] else 'stage time')]
    ordered = [s for s in report['stage_order'] if s in report['stages']]
    ordered += [s for s in report['stages'] if s not in ordered]
    for stage in ordered:
        info = report['stages'][stage]
        lines.append('  %-10s %8.3fs  %5.1f%%  (%d calls, %.3fs cpu)'
                     % (stage, info['seconds'], 100 * info['share'],
                        info['calls'], info.get('cpu_seconds', 0.0)))
    process_cpu = report.get('process_cpu')
    if process_cpu:
        stage_cpu = sum(i.get('cpu_seconds', 0.0)
                        for i in report['stages'].values())
        spent = sum(process_cpu.values())
        lines.append('  process cpu %s: stages explain %.1f%%'
                     % (', '.join('%s %.3fs' % kv
                                  for kv in sorted(process_cpu.items())),
                        100 * stage_cpu / spent if spent else 0.0))
    if report['wall_time_s']:
        lines.append('  attributed %5.1f%% of %.3fs wall'
                     % (100 * (report['attributed_fraction'] or 0.0),
                        report['wall_time_s']))
    if report.get('h2d_overlap_share') is not None:
        lines.append('  h2d overlap %5.1f%% (share of staging-engine time '
                     'not blocked on an in-flight transfer)'
                     % (100 * report['h2d_overlap_share']))
    stall = report['stall']
    lines.append('stall attribution: %s (producer_wait %.3fs, '
                 'consumer_wait %.3fs over %d window(s))'
                 % (stall['verdict'], stall['producer_wait_s'],
                    stall['consumer_wait_s'], len(stall['windows'])))
    if 'cache' in report:
        c = report['cache']
        lines.append('cache: %d hit / %d miss (%.1f%%), %d eviction(s), '
                     '%d B written, %d B evicted, %d B resident'
                     % (c['hits'], c['misses'], 100 * c['hit_rate'],
                        c['evictions'], c['bytes_written'],
                        c['bytes_evicted'], c['size_bytes']))
    if 'decoded_cache' in report:
        d = report['decoded_cache']
        lines.append('decoded cache: %s — %d hit / %d miss (%.1f%%, %d '
                     'from memory tier), %d mmap / %d copy column read(s), '
                     '%d B written, %d B read, %d eviction(s), %d B '
                     'resident'
                     % (d['verdict'] or 'idle', d['hits'], d['misses'],
                        100 * d['hit_rate'], d['mem_hits'],
                        d['mmap_reads'], d['copy_reads'],
                        d['bytes_written'], d['bytes_read'],
                        d['evictions'], d['size_bytes']))
    if 'service' in report:
        s = report['service']
        lines.append('service fleet: %d alive / %d registered worker(s), '
                     '%d pending / %d assigned item(s), %d re-ventilated, '
                     '%d duplicate completion(s) dropped, %d retried, '
                     '%d poisoned'
                     % (s['workers_alive'], s['workers_registered'],
                        s['items_pending'], s['items_assigned'],
                        s['reventilated'], s['duplicate_done'],
                        s.get('retried', 0), s.get('poisoned', 0)))
        ha_bits = []
        if s.get('failovers'):
            ha_bits.append('%d failover(s), replication lag %.3fs'
                           % (s['failovers'],
                              s.get('replication_lag_s') or 0.0))
        if s.get('preemptions'):
            ha_bits.append('%d preemption(s)' % s['preemptions'])
        if s.get('placement_hit_share') is not None:
            ha_bits.append('placement %d hit / %d miss (%.1f%%)'
                           % (s['placement_hits'], s['placement_misses'],
                              100 * s['placement_hit_share']))
        if ha_bits:
            lines.append('service HA/QoS: %s' % ', '.join(ha_bits))
    if 'pushdown' in report:
        p = report['pushdown']
        share = p['prune_share']
        declines = ', '.join('%s: %d' % (k, v)
                             for k, v in sorted(p['declines'].items()))
        lines.append('pushdown: %d/%d row-group(s) pruned%s (%d rows '
                     'skipped), %d row(s) late-materialized%s'
                     % (p['rowgroups_pruned'], p['rowgroups_considered'],
                        (' = %.1f%%' % (100 * share)
                         if share is not None else ''),
                        p['rows_pruned'], p['late_materialized_rows'],
                        (' — declines: %s' % declines) if declines else ''))
    if 'readahead' in report:
        r = report['readahead']
        reasons = ', '.join('%s: %d' % (k, v)
                            for k, v in sorted(r['degraded'].items()))
        lines.append('readahead: %d hit / %d miss%s, %d B over %d '
                     'coalesced read(s)%s, depth %d, pool %d/%d B%s'
                     % (r['hits'], r['misses'],
                        (' (%.1f%%)' % (100 * r['hit_share'])
                         if r['hit_share'] is not None else ''),
                        r['bytes_fetched'], r['coalesced_reads'],
                        (' (mean %d B)' % r['mean_coalesced_bytes']
                         if r['mean_coalesced_bytes'] is not None else ''),
                        r['depth'], r['pool_bytes'],
                        r['pool_budget_bytes'],
                        (' — degraded: %s' % reasons) if reasons else ''))
    if 'peer_cache' in report:
        p = report['peer_cache']
        reasons = ', '.join('%s: %d' % (k, v)
                            for k, v in sorted(p['degraded'].items()))
        lines.append('peer cache: %d hit / %d miss%s, %d B fetched from '
                     'peers, %d evict hint(s)%s'
                     % (p['hits'], p['misses'],
                        (' (%.1f%%)' % (100 * p['hit_share'])
                         if p['hit_share'] is not None else ''),
                        p['bytes_fetched'], p['evict_hints'],
                        (' — degraded: %s' % reasons) if reasons else ''))
    if 'write' in report:
        w = report['write']
        compact_bit = ''
        if w['compact_runs']:
            compact_bit = (', %d compaction run(s) folding %d file(s)'
                           % (w['compact_runs'], w['files_folded']))
        lines.append('write plane: %d row(s) / %d B in %d part file(s), '
                     '%d commit(s), generation %d%s'
                     % (w['rows_written'], w['bytes_written'],
                        w['files_written'], w['commits'], w['generation'],
                        compact_bit))
    if 'pipesan' in report:
        p = report['pipesan']
        kinds = ', '.join('%s: %d' % (k, v)
                          for k, v in sorted(p['by_kind'].items()))
        lines.append('pipesan: %s — %d violation(s)%s, %d view(s) forced '
                     'read-only, %d canary check(s)'
                     % ('armed' if p['enabled'] else 'off',
                        p['violations'],
                        (' (%s)' % kinds) if kinds else '',
                        p['views_guarded'], p['canary_checks']))
    if 'anomalies' in report:
        a = report['anomalies']
        kinds = ', '.join('%s: %d' % (k, v)
                          for k, v in sorted(a['by_kind'].items()))
        lines.append('anomalies: %d event(s)%s'
                     % (a['total'], (' (%s)' % kinds) if kinds else ''))
        for event in a['recent'][-3:]:
            lines.append('  %s at %.0f — %s'
                         % (event['kind'], event.get('ts') or 0.0,
                            event.get('runbook', '')))
    if 'staging_autotune' in report:
        t = report['staging_autotune']
        actions = ', '.join('%s: %d' % (k, v)
                            for k, v in sorted(t['by_action'].items()))
        lines.append('staging autotune: %d decision(s)%s'
                     % (t['total'], (' (%s)' % actions) if actions else ''))
        for entry in t['recent'][-3:]:
            detail = {k: v for k, v in entry.items()
                      if k not in ('action', 'ts')}
            lines.append('  %s — %s' % (entry['action'], detail))
    if 'critical_path' in report:
        c = report['critical_path']
        lines.append('critical path: bottleneck %s over %.3fs traced '
                     'span (%d item(s), %d stage event(s))'
                     % (c['bottleneck'], c['span_s'], c['items'],
                        c['events']))
        for stage, info in list(c['stages'].items())[:4]:
            lines.append('  %-14s self %8.3fs  overlapped %8.3fs'
                         % (stage, info['self_s'], info['overlap_s']))
        for scenario in c['what_if'][:3]:
            lines.append('  what-if: %s => epoch %+.1f%%'
                         % (scenario['scenario'],
                            scenario['epoch_delta_pct']))
        check = c.get('autotune_crosscheck')
        if check:
            lines.append('  autotuner cross-check: %d agree / %d '
                         'disagree over %d decision(s)'
                         % (check['agree'], check['disagree'],
                            check['decisions']))
    if 'slo' in report:
        for target in report['slo']['targets']:
            lines.append('slo %s %s %g: last %s, burn short %.2fx / '
                         'long %.2fx, budget %.0f%%%s'
                         % (target['target'], target['op'],
                            target['threshold'],
                            ('%.4g' % target['last_value'])
                            if target['last_value'] is not None else '-',
                            target['short_burn'], target['long_burn'],
                            100 * target['budget_remaining'],
                            ' — BREACHING' if target['breaching'] else ''))
    return '\n'.join(lines)
