"""Canonical name sets: the pipeline's naming contracts in ONE place.

Every name contract the concurrency-heavy surface relies on lives here —
imported at runtime by the telemetry subsystem (stage spans, trace events,
the env-knob registry) AND statically by the :mod:`petastorm_tpu.analysis`
checker plus ``tests/test_hygiene.py``. Before this module existed the
same literals were duplicated between ``telemetry/spans.py``,
``telemetry/tracing.py`` and the hygiene test, where they could (and did)
drift silently; now a typo'd stage name, an unregistered knob or an
undocumented metric is a static-analysis finding, not a runtime mystery.

Dependency-free and import-light by design: this module imports nothing,
so the checker can read the contracts without dragging in numpy/pyarrow,
and ``telemetry`` can import it without cycles (nothing here imports
telemetry back).
"""

#: canonical pipeline stages, ventilator → device (docs/telemetry.md):
#: ``ventilate`` hand item to pool · ``io`` parquet row-group read ·
#: ``decode`` codec decode · ``filter`` predicate/row-mask eval ·
#: ``transform`` TransformSpec · ``queue_wait`` consumer blocked pulling ·
#: ``collate`` re-batch/shuffle-buffer/densify · ``h2d_ready`` staging
#: arena blocked until a slot's previous transfer completed ·
#: ``stage_fill`` cast/pad/mask copy into the arena slot ·
#: ``h2d_dispatch`` async transfer dispatch ·
#: ``cache_hit_read`` decoded-row-group cache hit served (mmap + column
#: reconstruct) · ``cache_fill`` decoded batch serialized to Arrow IPC +
#: atomically published into the cache · ``decode_fused`` deferred image
#: cells decoded by the staging arena straight into the destination
#: buffer (slot ring or fresh assembly; petastorm_tpu/fused.py) ·
#: ``rowgroup_prune`` plan-time statistics pruning at Reader
#: construction (footer fetch + prover; petastorm_tpu/pushdown.py) ·
#: ``late_materialize`` survivor-only decode of heavy columns after the
#: predicate mask — the late-materialization specialization of
#: ``decode`` (arrow_worker._load_rowgroup) · ``autotune`` one staging
#: autotuner tick: registry snapshot + rollup window close + policy
#: (petastorm_tpu/jax/autotune.py; the loop's own overhead is on the
#: books) · ``readahead_fetch`` one coalesced prefetch of an upcoming
#: row-group's column-chunk ranges on the readahead plane's fetch
#: threads (petastorm_tpu/readahead.py; wall time overlapped with
#: decode — a high share here with low ``io`` share is the plane
#: working) · ``pack`` token-budget sequence packing: variable-length
#: documents folded into fixed ``(seq_len,)`` rows with loss masks and
#: segment ids (petastorm_tpu/mixture/packing.py) · ``encode``
#: write-path codec encode of row dicts into parquet-storable values
#: (etl/dataset_metadata.DatasetWriter, write/writer.py) ·
#: ``write_flush`` one buffered row-group flushed as an arrow table
#: into a parquet part file (etl/dataset_metadata.DatasetWriter._flush)
#: · ``compact`` one compaction group folded: source part files read at
#: the arrow level, re-chunked to readahead-friendly row-groups and
#: rewritten (write/compact.py) · ``peer_fetch`` one finished decoded
#: entry fetched from a peer worker's serve socket instead of decoded
#: locally: request + streamed Arrow IPC bytes + verify + atomic
#: publish into the local disk tier (service/peer_cache.py; wire-priced
#: where ``decode`` would be decode-priced) · ``loader_next`` the
#: consumer blocked in ``JaxLoader.__next__`` on the staged-batch queue ·
#: ``stage_backpressure`` the staging thread blocked handing a staged
#: batch to a full queue · ``serialize`` a process-pool worker's pickle-5
#: framing and send of one result (its wait for room in the channel is
#: producer wait, not this) · ``deserialize`` the consumer's receive and
#: pickle-5 rebuild of one process-pool message
STAGES = ('ventilate', 'io', 'decode', 'filter', 'transform', 'queue_wait',
          'collate', 'h2d_ready', 'stage_fill', 'h2d_dispatch',
          'cache_hit_read', 'cache_fill', 'decode_fused',
          'rowgroup_prune', 'late_materialize', 'autotune',
          'readahead_fetch', 'pack', 'encode', 'write_flush', 'compact',
          'peer_fetch', 'loader_next', 'stage_backpressure', 'serialize',
          'deserialize')

#: the stages that wait rather than work: their seconds leave out any span
#: nested inside them (telemetry/spans.py), and a device idle gap under one
#: of them is not the loader's work (docs/telemetry.md, timeline)
WAIT_STAGES = frozenset(['queue_wait', 'loader_next', 'stage_backpressure',
                         'h2d_ready'])

#: every trace-event name the package records outside the canonical stage
#: spans (docs/telemetry.md, tracing section)
EVENT_NAMES = frozenset([
    'attempt',          # one worker-side processing of one item (X event)
    'ventilate',        # recorded via the ventilator's stage span
    'dispatch',         # dispatcher assigned the item to a worker (instant)
    'reventilate',      # heartbeat lapse sent the item back to pending
    'retry',            # failed attempt rescheduled with backoff (instant)
    'poisoned',         # retry budget exhausted; item quarantined
    'done',             # the item's single delivered completion
    'duplicate_done',   # a raced second completion, deduped (dropped)
    # standing-service supervision (service/supervisor.py + daemon.py):
    # every scaling/repair action on the fleet is an instant on the
    # 'supervisor'/'daemon' track, so a Perfetto export shows WHY the
    # fleet changed size or membership
    'worker_spawn',     # supervisor started a worker-server process
    'worker_release',   # supervisor drained + released an idle worker
    'breaker_open',     # crash-looping slot tripped its circuit breaker
    'breaker_close',    # a breaker's respawned worker proved stable
    'job_register',     # daemon admitted a client job into the registry
    'job_gone',         # a job left the registry (goodbye or lease GC)
    # high availability + QoS (service/standby.py + dispatcher.py):
    'standby_promote',  # standby detected a primary lapse; promoting
    'endpoint_takeover',  # promoted standby bound the primary's endpoint
    'job_preempt',      # priority preemption cordoned a worker for a job
    # staging autotuner (jax/autotune.py): one instant per knob
    # adjustment on the 'autotuner' track, so a Perfetto export shows
    # WHY throughput changed shape mid-run
    'autotune_decision',
    # streaming mixture engine (mixture/engine.py): one complete event
    # per source-reader batch pull on that source's track, so a traced
    # mixture run shows which source each document lifeline came from
    'mixture_pull',
])

#: every metric series name the package exports — the registry namespace
#: (``petastorm_tpu_*``). Dashboards are built from docs/telemetry.md's
#: metric reference; the hygiene test holds that table and this set equal,
#: and the canonical-name analysis pass holds every
#: ``registry.counter/gauge/histogram`` call in the package to this set.
METRIC_NAMES = frozenset([
    # stage spans (telemetry/spans.py)
    'petastorm_tpu_stage_seconds_total',
    'petastorm_tpu_stage_cpu_seconds_total',
    'petastorm_tpu_stage_calls_total',
    'petastorm_tpu_stage_duration_seconds',
    # process CPU by role (telemetry/registry.py)
    'petastorm_tpu_process_cpu_seconds_total',
    # stall wait clocks (telemetry/__init__.py)
    'petastorm_tpu_stall_producer_wait_seconds_total',
    'petastorm_tpu_stall_consumer_wait_seconds_total',
    # staging arena (jax/staging.py)
    'petastorm_tpu_h2d_bytes_total',
    # staging autotuner (jax/autotune.py)
    'petastorm_tpu_staging_autotune_decisions_total',
    # row-group cache (cache.py)
    'petastorm_tpu_cache_hits_total',
    'petastorm_tpu_cache_misses_total',
    'petastorm_tpu_cache_evictions_total',
    'petastorm_tpu_cache_bytes_written_total',
    'petastorm_tpu_cache_bytes_evicted_total',
    'petastorm_tpu_cache_size_bytes',
    # materialized decoded-row-group cache (materialized_cache.py)
    'petastorm_tpu_decoded_cache_hits_total',
    'petastorm_tpu_decoded_cache_misses_total',
    'petastorm_tpu_decoded_cache_mem_hits_total',
    'petastorm_tpu_decoded_cache_evictions_total',
    'petastorm_tpu_decoded_cache_bytes_written_total',
    'petastorm_tpu_decoded_cache_bytes_read_total',
    'petastorm_tpu_decoded_cache_mmap_reads_total',
    'petastorm_tpu_decoded_cache_copy_reads_total',
    'petastorm_tpu_decoded_cache_size_bytes',
    # disaggregated-service fleet health (service/dispatcher.py)
    'petastorm_tpu_service_reventilated_total',
    'petastorm_tpu_service_duplicate_done_total',
    'petastorm_tpu_service_workers_alive',
    'petastorm_tpu_service_workers_registered',
    'petastorm_tpu_service_items_pending',
    'petastorm_tpu_service_items_assigned',
    # failure-domain hardening (service/dispatcher.py, faults.py,
    # telemetry/__init__.py)
    'petastorm_tpu_service_retries_total',
    'petastorm_tpu_service_items_poisoned_total',
    # standing decode service (service/daemon.py + supervisor.py)
    'petastorm_tpu_service_jobs_active',
    'petastorm_tpu_service_workers_spawned_total',
    'petastorm_tpu_service_workers_released_total',
    'petastorm_tpu_service_breaker_open',
    # highly-available decode service: warm-standby failover, QoS
    # preemption, cache-aware placement (service/standby.py +
    # dispatcher.py)
    'petastorm_tpu_service_failovers_total',
    'petastorm_tpu_service_replication_lag_seconds',
    'petastorm_tpu_service_preemptions_total',
    'petastorm_tpu_service_placement_hits_total',
    'petastorm_tpu_service_placement_misses_total',
    'petastorm_tpu_swallowed_errors_total',
    'petastorm_tpu_faults_injected_total',
    # decoded-cache failure domain (materialized_cache.py)
    'petastorm_tpu_decoded_cache_disk_failures_total',
    'petastorm_tpu_decoded_cache_degraded',
    # pipesan runtime zero-copy sanitizer (sanitizer.py)
    'petastorm_tpu_sanitizer_violations_total',
    'petastorm_tpu_sanitizer_views_guarded_total',
    'petastorm_tpu_sanitizer_canary_checks_total',
    # fused batch-native decode (fused.py / jax/staging.py)
    'petastorm_tpu_fused_decode_rows_total',
    'petastorm_tpu_fused_decode_bytes_total',
    'petastorm_tpu_fused_decode_fallbacks_total',
    # live observability plane (telemetry/timeseries.py + obs_server.py)
    'petastorm_tpu_anomaly_events_total',
    'petastorm_tpu_obs_windows_total',
    'petastorm_tpu_obs_scrapes_total',
    # query-shaped reads: statistics pruning + late materialization
    # (pushdown.py, arrow_worker.py, materialized_cache.py)
    'petastorm_tpu_rowgroups_pruned_total',
    'petastorm_tpu_rows_pruned_total',
    'petastorm_tpu_late_materialized_rows_total',
    'petastorm_tpu_decoded_cache_skipped_total',
    # wire-speed I/O plane: coalesced column-chunk readahead
    # (readahead.py)
    'petastorm_tpu_readahead_hits_total',
    'petastorm_tpu_readahead_misses_total',
    'petastorm_tpu_readahead_bytes_total',
    'petastorm_tpu_readahead_coalesced_reads_total',
    'petastorm_tpu_readahead_degraded_total',
    'petastorm_tpu_readahead_pool_bytes',
    # streaming mixture engine: deterministic mixing + sequence packing
    # (mixture/engine.py, mixture/packing.py)
    'petastorm_tpu_mixture_docs_total',
    'petastorm_tpu_pack_rows_total',
    'petastorm_tpu_pack_tokens_total',
    'petastorm_tpu_pack_padding_tokens_total',
    'petastorm_tpu_pack_split_docs_total',
    # distributed write plane: fleet-ETL writer, compaction, append
    # (write/writer.py, write/compact.py, write/manifest.py)
    'petastorm_tpu_write_rows_total',
    'petastorm_tpu_write_bytes_total',
    'petastorm_tpu_write_files_total',
    'petastorm_tpu_write_commits_total',
    'petastorm_tpu_write_manifest_generation',
    'petastorm_tpu_compact_runs_total',
    'petastorm_tpu_compact_files_folded_total',
    # bounded-staleness append reads (write/append.py): observed lag
    # between the latest committed manifest and the follower's delivery
    'petastorm_tpu_append_staleness_s',
    # fleet-wide decoded-cache tier: peer-served entries
    # (service/peer_cache.py + dispatcher.py). Hits/bytes count
    # successful peer fetches on the FETCHING worker; misses carry the
    # degrade reason (no_holder, peer_miss, timeout, budget, corrupt,
    # injected, send) — every miss decodes locally, never errors;
    # evict_hints counts the dispatcher's advisory global-LRU hints
    'petastorm_tpu_peer_cache_hits_total',
    'petastorm_tpu_peer_cache_misses_total',
    'petastorm_tpu_peer_cache_bytes_total',
    'petastorm_tpu_peer_cache_evict_hints_total',
    # SLO plane (telemetry/slo.py): per-target breach windows + the
    # error budget left in the long burn window (1.0 = untouched)
    'petastorm_tpu_slo_breach_windows_total',
    'petastorm_tpu_slo_budget_remaining',
    # critical-path engine (telemetry/critpath.py): decision-quality
    # cross-check against the staging autotuner (verdict=agree|disagree)
    'petastorm_tpu_critpath_agreement_total',
])

#: prefix of every operator-facing environment knob
KNOB_PREFIX = 'PETASTORM_TPU_'

#: every registered environment knob. A ``PETASTORM_TPU_*`` read anywhere
#: but ``telemetry/knobs.py`` — or of a name missing here, or of a name
#: without a row in docs/env_knobs.md — is an ``env-knob`` analysis
#: finding; :mod:`petastorm_tpu.telemetry.knobs` additionally enforces the
#: set at runtime (reading an unregistered knob raises).
KNOWN_KNOBS = frozenset([
    'PETASTORM_TPU_NATIVE',
    'PETASTORM_TPU_JPEG_FANCY',
    'PETASTORM_TPU_JPEG_DCT',            # parsed by native/jpeg_batch.c
    'PETASTORM_TPU_IMAGE_DECODER_THREADS',
    'PETASTORM_TPU_SERVICE_DISPATCHER',
    'PETASTORM_TPU_SERVICE_WORKERS',
    'PETASTORM_TPU_METRICS',
    'PETASTORM_TPU_METRICS_WINDOW_S',
    'PETASTORM_TPU_TRACE',
    'PETASTORM_TPU_TRACE_SAMPLE',
    'PETASTORM_TPU_TRACE_DUMP',
    'PETASTORM_TPU_TRACE_AUTODUMP_WINDOWS',
    'PETASTORM_TPU_STAGING_SLOTS',
    'PETASTORM_TPU_STAGING_AUTOTUNE',
    'PETASTORM_TPU_STAGING_AUTOTUNE_WINDOW_SEC',
    'PETASTORM_TPU_STAGING_AUTOTUNE_MAX_SLOTS',
    'PETASTORM_TPU_STAGING_AUTOTUNE_MAX_PREFETCH',
    'PETASTORM_TPU_DECODED_CACHE',
    'PETASTORM_TPU_DECODED_CACHE_DIR',
    'PETASTORM_TPU_DECODED_CACHE_MEM_MB',
    'PETASTORM_TPU_DECODED_CACHE_DISK_MB',
    'PETASTORM_TPU_SANITIZE',
    'PETASTORM_TPU_OBS_PORT',
    'PETASTORM_TPU_OBS_HOST',
    'PETASTORM_TPU_OBS_WINDOW_SEC',
    'PETASTORM_TPU_OBS_WINDOWS',
    'PETASTORM_TPU_OBS_COLLAPSE_FRAC',
    'PETASTORM_TPU_OBS_SATURATED_SHARE',
    'PETASTORM_TPU_OBS_FLAP_FLIPS',
    'PETASTORM_TPU_FAULTS',
    'PETASTORM_TPU_SERVICE_MAX_RETRIES',
    'PETASTORM_TPU_SERVICE_RETRY_BACKOFF_S',
    'PETASTORM_TPU_SERVICE_READ_DEADLINE_S',
    'PETASTORM_TPU_SERVICE_DAEMON',
    'PETASTORM_TPU_SERVICE_LEASE_S',
    'PETASTORM_TPU_SERVICE_MAX_JOBS',
    'PETASTORM_TPU_SERVICE_MIN_WORKERS',
    'PETASTORM_TPU_SERVICE_MAX_WORKERS',
    'PETASTORM_TPU_SERVICE_BREAKER_DEATHS',
    'PETASTORM_TPU_SERVICE_BREAKER_WINDOW_S',
    'PETASTORM_TPU_SERVICE_SCALE_WINDOW_S',
    'PETASTORM_TPU_SERVICE_STANDBY_SYNC_S',
    'PETASTORM_TPU_SERVICE_STANDBY_LAPSE_S',
    'PETASTORM_TPU_SERVICE_JOB_WEIGHT',
    'PETASTORM_TPU_SERVICE_JOB_PRIORITY',
    'PETASTORM_TPU_SERVICE_PLACEMENT',
    'PETASTORM_TPU_PUSHDOWN_WORKERS',
    'PETASTORM_TPU_READAHEAD',
    'PETASTORM_TPU_READAHEAD_DEPTH',
    'PETASTORM_TPU_READAHEAD_MAX_DEPTH',
    'PETASTORM_TPU_READAHEAD_THREADS',
    'PETASTORM_TPU_READAHEAD_POOL_MB',
    'PETASTORM_TPU_READAHEAD_GAP_KB',
    'PETASTORM_TPU_READAHEAD_MAX_RANGE_MB',
    'PETASTORM_TPU_MIXTURE_OPEN_BINS',
    'PETASTORM_TPU_MIXTURE_RESEQ_MAX',
    'PETASTORM_TPU_WRITE_ROWGROUP_MB',
    'PETASTORM_TPU_WRITE_WORKERS',
    'PETASTORM_TPU_WRITE_SHARD_ROWS',
    'PETASTORM_TPU_WRITE_SELF_CHECK',
    'PETASTORM_TPU_COMPACT_TARGET_MB',
    'PETASTORM_TPU_COMPACT_MIN_FILES',
    'PETASTORM_TPU_SLO',
    'PETASTORM_TPU_OBS_LOG_DIR',
    'PETASTORM_TPU_OBS_LOG_MB',
    'PETASTORM_TPU_PEER_CACHE',
    'PETASTORM_TPU_PEER_CACHE_HOST',
    'PETASTORM_TPU_PEER_CACHE_BUDGET_MB',
    'PETASTORM_TPU_PEER_CACHE_TIMEOUT_S',
    'PETASTORM_TPU_PEER_CACHE_COLD_S',
])

#: canonical anomaly event kinds the live observability plane's detector
#: (:mod:`petastorm_tpu.telemetry.timeseries`) may emit, mapped to the
#: docs/troubleshoot.md runbook HEADING that explains each one. The value
#: rides on every emitted event as its ``runbook`` field, and
#: ``tests/test_hygiene.py`` holds (a) every ``record_anomaly`` literal in
#: the package to this set, (b) every kind to a row in docs/telemetry.md's
#: anomaly table, and (c) every heading here to a real ``##`` section of
#: docs/troubleshoot.md — an event that names a missing runbook is a
#: hygiene failure, not an operator dead end.
ANOMALY_KINDS = {
    'throughput_collapse': 'Throughput collapsed mid-epoch',
    'stall_flap': 'Stall verdict flaps between producer- and '
                  'consumer-bound',
    'queue_saturated': 'My pipeline is consumer-bound — is it the '
                       'training step or the H2D link?',
    'heartbeat_gap': 'Stale decode workers after a crash',
    'h2d_starvation': 'My pipeline is consumer-bound — is it the '
                      'training step or the H2D link?',
    'row_group_poisoned': 'A row-group was quarantined '
                          '(row_group_poisoned)',
    'cache_degraded': 'The decoded cache degraded to decode-through',
    'worker_flapping': 'A worker slot is crash-looping (worker_flapping)',
    'job_lease_expired': 'A job lease expired and was reclaimed '
                         '(job_lease_expired)',
    'dispatcher_failover': 'The dispatcher failed over to its standby '
                           '(dispatcher_failover)',
    'slo_breach': 'An SLO error budget is burning too fast (slo_breach)',
}

#: every registered fault-injection site (:mod:`petastorm_tpu.faults`),
#: mapped to a one-line description of the seam it sits on. The
#: ``faultpoint`` analysis pass holds every ``fault_hit()`` literal in
#: the package to this set, an armed hit of an unregistered name raises
#: at runtime, and docs/development.md's authoring guide renders this
#: table — a faultpoint can never exist off the books. ``drop`` is only
#: meaningful at the message-send sites; the data-path sites take the
#: error/oserror/delay modes.
FAULTPOINTS = {
    'io.read': 'parquet row-group read (arrow_worker._load_rowgroup), '
               'the pushdown planner\'s footer-statistics fetch '
               '(pushdown.StatsIndex, keys end in #footer — a footer '
               'fault degrades to unpruned reads, never a wrong answer) '
               'and the readahead plane\'s prefetch reads (readahead.py, '
               'keys end in #readahead — a fetch fault degrades to the '
               'worker\'s blocking read, counted in '
               'petastorm_tpu_readahead_degraded_total)',
    'decode.rowgroup': 'whole row-group decode, incl. the native batch '
                       'decoders (arrow_worker._load_rowgroup)',
    'decode.batch': 'one column batch decode (codecs.'
                    'decode_batch_with_nulls; fused + per-cell paths)',
    'cache.read': 'decoded-cache entry open/mmap (materialized_cache)',
    'cache.write': 'decoded-cache entry publish (materialized_cache)',
    'zmq.recv': 'dispatcher inbound message (drop = lose the frame)',
    'zmq.work': 'dispatcher WORK send (drop = assignment lost in '
                'flight; the consumer-read deadline is the backstop)',
    'zmq.done': 'worker DONE/ERROR send (drop = completion lost)',
    'zmq.heartbeat': 'worker heartbeat send (drop = dispatcher sees a '
                     'lapse and re-ventilates)',
    'zmq.stop': 'dispatcher STOP broadcast (drop = dispatcher dies '
                'without goodbye — the restart/reconnect drill)',
    'staging.h2d': 'staging-arena host->device dispatch (jax/staging)',
    'service.spawn': 'supervisor worker-server process spawn '
                     '(service/supervisor.py; error = the spawn fails, '
                     'feeding the crash-loop circuit breaker — the '
                     'breaker drill without burning real processes)',
    'zmq.replicate': 'the standby replication stream, checked at BOTH '
                     'ends (dispatcher SSTATE send, standby receive; '
                     'drop = the snapshot is lost in flight — sustained, '
                     'the standby\'s mirror goes stale and a later '
                     'promotion is COLD: clients re-register from '
                     'scratch, still exactly-once)',
    'service.promote': 'a standby\'s promotion attempt (service/'
                       'standby.py; error = the attempt fails and is '
                       'retried with backoff inside the promote window '
                       '— the failover drill\'s knob for prolonging the '
                       'blackout deterministically)',
    'zmq.peer_serve': 'a peer-cache serve reply (service/peer_cache.py '
                      'server side; drop = the holding worker never '
                      'answers and the fetcher times out into local '
                      'decode — the peer-loss drill without killing a '
                      'process; error = the serve fails mid-read)',
    'zmq.peer_fetch': 'a peer-cache fetch attempt (service/peer_cache.py '
                      'client side, hit before the request is sent; '
                      'error/drop = the fetch fails and the worker '
                      'degrades to local decode, counted in '
                      'petastorm_tpu_peer_cache_misses_total'
                      '{reason=injected} — never a wrong answer)',
    'io.write': 'the distributed write plane\'s publication seams '
                '(write/writer.py, write/compact.py, write/manifest.py):'
                ' part-file data write/close (keys end in #part), the '
                'tmp->final rename that publishes a part file (keys end '
                'in #rename) and the atomic manifest swap that commits '
                'a generation (keys end in #manifest). A fault before '
                'the rename leaves only an invisible .tmp file; a fault '
                'before the manifest swap leaves the previous generation'
                ' committed — either way readers never see a torn '
                'dataset, and a retried shard republishes byte-identical'
                ' output (the crash-safety chaos drill in '
                'tests/test_write.py)',
}

#: the one knob-truthiness rule for "disable"/"enable" env spellings —
#: shared by every PETASTORM_TPU_* switch so spellings cannot drift
DISABLED_VALUES = ('0', 'false', 'off', 'no')
ENABLED_VALUES = ('1', 'true', 'on', 'yes')

# -- pipesan buffer-ownership contracts ---------------------------------------
#
# The zero-copy fast paths hand out BORROWED views: arrays whose memory is
# owned by someone else with a shorter (or recycled) lifetime — ZMQ receive
# buffers, the decoded-cache mmap, staging-arena slots. The
# ``buffer-escape``/``buffer-write`` analysis pass
# (:mod:`petastorm_tpu.analysis.pass_buffers`) taints values born from the
# sources registered here and flags them escaping their owning scope or
# being written through; the runtime sanitizer (``PETASTORM_TPU_SANITIZE=1``,
# :mod:`petastorm_tpu.sanitizer`) guards the same three boundaries
# dynamically. One registry, checked from both sides.

#: terminal call names whose RESULT is a borrowed buffer view.
#: ``frombuffer`` over a *call expression* (``np.frombuffer(bytes(...))``,
#: ``np.frombuffer(x.encode())``) is exempt: the argument is a fresh
#: anonymous temporary whose only reference becomes the array's ``.base``,
#: so the view owns its memory by construction.
BORROW_CALLS = frozenset([
    'frombuffer',           # numpy view over someone else's buffer
    'read_entry',           # decoded-cache columns alias the entry's mmap
    '_binary_cell_views',   # cells alias the arrow column's data buffer
])

#: call names whose result is borrowed only when the given keyword is
#: passed with the given value. ``astype`` over a *call expression*
#: receiver is exempt for the same fresh-temporary reason as frombuffer.
BORROW_CALL_KWARGS = {
    'recv_multipart': ('copy', False),   # frames view ZMQ receive buffers
    'astype': ('copy', False),           # may alias the source array
}

#: dotted expressions denoting borrowed buffer collections — staging-arena
#: slot memory (recycled after the slot's next transfer retires) and a
#: deferred image column's encoded cell views (zero-copy over the arrow
#: data buffer or a received frame, which each view's numpy base keeps
#: alive). Any view over them is borrowed.
BORROW_ATTRS = frozenset([
    'slot.buffers',
    'column.cells',
    # a readahead fetch entry's pooled range buffers (readahead.py):
    # recycled when the entry's reference census drains — views over
    # them are pinned only by a served table's finalizer
    'entry.ranges',
])

#: the ownership-transfer annotation: ``# pipesan: owns`` on (any line of)
#: a flagged statement records that the transfer is intentional and the
#: receiver owns (or knowingly borrows) the memory — always pair it with a
#: justification comment. On a ``return`` it asserts the CALLER owns the
#: result, so taint does NOT propagate — a function whose callers
#: genuinely borrow its result belongs in :data:`BORROW_CALLS` instead.
OWNS_ANNOTATION_RE = r'pipesan:\s*owns'
