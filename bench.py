"""Round benchmark: row, batch, image-pipeline and JAX H2D read rates.

Primary metric replicates the reference's only published absolute number —
the ``petastorm-throughput.py`` hello-world read rate of 709.84 samples/sec
with 3 thread workers (``docs/benchmarks_tutorial.rst:20-21``) — against this
framework's row-at-a-time reader on an equivalent dataset.

``extra`` carries the flagship-path numbers the row metric cannot see
(VERDICT r1 #4): the batched column reader, a jpeg-heavy 224x224x3
imagenet-style pipeline (rows/sec and decoded MB/s, native C decoders on vs
off), and the host→device-staged JAX path (rows/sec into device HBM + H2D
GB/s with uint8-vs-f32 staging accounting).

A like-for-like run of the reference reader on this machine is not possible:
its read stack needs long-removed pyarrow APIs (``pyarrow.filesystem``,
``pyarrow.hdfs``, the legacy ``ParquetDataset`` pieces API) that pyarrow 25
no longer ships, so ``vs_baseline`` compares against its published number.

Wedge-proofing (VERDICT r3 #1 — round 3 lost its whole perf record to an
outer-timeout kill, rc=124):

* The cumulative result JSON is printed (flushed) after EVERY section, so a
  kill at any point still leaves the driver a parseable last line carrying
  every section that finished. The final line is the complete report.
* The TPU is probed ONCE up front in a guarded subprocess. If it finds
  no TPU, every device section records ``<prefix>_error`` at once —
  marked ``tpu_unavailable`` — instead of spawning. A device subprocess
  that finds no TPU fails the same way. No device section ever falls
  back to the CPU: a device key only ever carries a chip's number.
  Device subprocesses share one persistent compile cache
  (``petastorm_tpu.benchmark.device.enable_compile_cache``).
* A global wall-clock budget (``BENCH_BUDGET_SECONDS``, default 1100s —
  chosen to undercut any plausible driver timeout) clamps every section's
  subprocess timeout to the remaining budget and skips sections that no
  longer fit, recording them under ``skipped_sections``.
* ``BENCH_SMOKE=1`` shrinks every dataset/sample count so the whole
  benchmark finishes in well under a minute on CPU — used by
  ``tests/test_bench_wedgeproof.py`` to assert the contract above where
  no TPU exists.

Reference contract matched: one-shot metrics report, the reference's
``benchmark/throughput.py:112`` (single process prints a final report).
"""

import json
import os
import shutil
import subprocess
import sys
import tempfile
import time

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

BASELINE_SAMPLES_PER_SEC = 709.84  # reference: docs/benchmarks_tutorial.rst:20

SMOKE = os.environ.get('BENCH_SMOKE') == '1'

WARMUP_SAMPLES = 50 if SMOKE else 300
MEASURE_SAMPLES = 300 if SMOKE else 3000
HELLO_ROWS = 300 if SMOKE else 1000

IMAGENET_ROWS = 96 if SMOKE else 384
IMAGENET_SHAPE = (224, 224, 3)
# 5 runs for the cheap in-process rates (hello row, imagenet batch):
# single runs on this shared box swing ±10%, and a median-of-5 is
# decisively tighter for a few seconds more wall. The tf.data side pays a
# fresh TF subprocess (import + runtime startup) per run, so it stays at
# median-of-3 — medians of unequal sample counts are still unbiased on
# both sides of the ratio, and the ~2 extra TF startups (up to minutes on
# a loaded box) are exactly the budget the late device sections need.
MEDIAN_RUNS = 1 if SMOKE else 5
TFDATA_RUNS = 1 if SMOKE else 3

C4_DOCS = 256 if SMOKE else 2048

# selective_read section (ISSUE 12): enough row-groups that 1%
# selectivity leaves most of them provably empty, with a decode cost per
# row (96² jpeg) that makes decode-everything-then-filter measurably
# full-scan-priced
SELECTIVE_ROWS = 512 if SMOKE else 4096
SELECTIVE_SHAPE = (128, 128, 3)
SELECTIVE_ROWGROUP_ROWS = 32

# io_overlap section (ISSUE 15): cheap-to-decode rows over many
# row-groups behind an injected-latency filesystem, so the wall is
# storage round trips — exactly what the readahead plane overlaps. The
# delay models a remote/cold object store's per-request latency.
IO_OVERLAP_ROWS = 768 if SMOKE else 4096
IO_OVERLAP_ROWGROUP_ROWS = 32
IO_OVERLAP_READ_DELAY_S = 0.004 if SMOKE else 0.005

# streaming mixture engine (mixture_stream section): two token corpora
# mixed 3:1 and packed to fixed rows; sized so a full pass exercises the
# readahead plane across many row-groups without dominating the budget
MIXTURE_DOCS_A = 384 if SMOKE else 3072
MIXTURE_DOCS_B = 128 if SMOKE else 1024
MIXTURE_SEQ_LEN = 512

# distributed write plane (write_throughput section, ISSUE 18): enough
# rows that encode+flush dominates pool/commit fixed costs, sharded so
# both backends exercise multi-shard dispatch; the compaction probe
# stacks small appended generations so the before/after read contrast
# is file-count-driven, not noise
WRITE_BENCH_ROWS = 4000 if SMOKE else 20000
WRITE_BENCH_SHARD_ROWS = 500 if SMOKE else 2500
WRITE_COMPACT_GENS = 6
WRITE_COMPACT_GEN_ROWS = 250 if SMOKE else 1000

# ONE owner of the staged-batch size shared by the real imagenet H2D
# section and its dummy-source decomposition (the share math divides by
# it — two hardcoded 64s would drift apart silently)
IMAGENET_JAX_BATCH = 64


# The ONE flagship LM shape (~335M params), interpolated into BOTH the
# lm_train and lm_decode subprocess snippets so the decode benchmark can
# never silently measure a different model than the training one.
FLAGSHIP_LM_KW = dict(vocab_size=16384, d_model=1536, n_heads=16,
                      n_layers=10, d_ff=6144)

BUDGET_SECONDS = float(os.environ.get('BENCH_BUDGET_SECONDS',
                                      '240' if SMOKE else '1100'))
_START = time.monotonic()

# The driver records only a 2,000-char stdout TAIL and parses the LAST
# line (rounds 3 and 4 both lost their machine-parsed perf record — r03
# to an outer-timeout kill, r04 because the cumulative line outgrew the
# tail). So every emit prints TWO lines: the full cumulative dict for
# humans, then a compact headline-only line that is always last and
# asserted under _HEADLINE_MAX_CHARS. Ordered by importance: if the line
# ever approaches the cap, the least important tail keys drop first.
# raised 1500 → 1600 for the selective_read headline key, → 1700 for
# the two sharded_staging keys, → 1800 for the two service HA keys,
# → 1900 for the two mixture_stream keys (worst case ~1845), → 1950
# for the write_throughput headline key, → 1980 for the two critpath
# keys, → 2000 for the peer_hit_share key (worst case 1999; +newline
# is exactly the 2,000-char driver tail, so the key list is now FULL —
# the next key must drop or shorten one)
# — the emit loop still drops tail keys at the cap
_HEADLINE_MAX_CHARS = 2000
_HEADLINE_EXTRA_KEYS = (
    'vs_tfdata',
    'hello_world_warm_epoch_rows_per_sec',
    'cache_hit_share',
    # query-shaped reads: effective scan rate at 1% selectivity (the
    # speedups, other selectivities and pruning attribution stay in the
    # full cumulative dict)
    'selective_read_1pct_rows_per_sec',
    # wire-speed I/O plane: cold-read speedup readahead-on vs the
    # blocking oracle under injected storage latency (rates, hit share
    # and coalesced-size attribution stay in the full cumulative dict)
    'io_overlap_speedup',
    # streaming mixture engine (bench mixture_stream section): packed
    # throughput and fill ratio; deviation, hit share and the oracle
    # rate stay in the full cumulative dict
    'mixture_packed_tokens_per_sec',
    'mixture_fill_ratio',
    # distributed write plane (bench write_throughput section): local
    # backend commit-to-commit write rate; MB/s, the fleet backend and
    # the compaction read delta stay in the full cumulative dict
    'write_rows_per_sec',
    # critical-path engine (bench critpath section): the sweep-line
    # analysis' share of a traced epoch (budget <2%) and its best
    # what-if projection; bottleneck and event count stay in the full
    # cumulative dict
    'critpath_overhead_share',
    'critpath_top_whatif',
    # standing-service HA (bench service section): kill-to-first-row
    # blackout through a warm-standby promotion, and the share of
    # bindings that landed on a fingerprint-warm host
    'service_failover_blackout_s',
    'service_placement_hit_share',
    # fleet cache tier (bench peer_cache section): share of warm-epoch
    # row-groups a two-worker fleet served WITHOUT a fresh decode
    # (local disk hit or peer fetch); decode counts and the warm
    # speedup stay in the full cumulative dict
    'peer_hit_share',
    'lm_train_mfu',
    'lm_train_input_bound_util',
    'lm_train_tuned_mfu',
    'lm_decode_decode_tokens_per_sec',
    'lm_decode_gqa_decode_speedup',
    'native_decode_speedup',
    # the granularity ladder's other rungs (fused_decode_batched_* etc.)
    # stay in the full cumulative dict; only the MODE is headline-worthy
    # (it says whether the imagenet numbers rode the fused path at all)
    'fused_decode_mode',
    'imagenet_batch_rows_per_sec',
    'imagenet_jax_rows_per_sec',
    'jax_framework_share',
    'h2d_link_degraded',
    'imagenet_jax_h2d_efficiency',
    'imagenet_jax_h2d_overlap_share',
    # shard-aware staging engine (mesh-wide slot rings + autotuner): the
    # decision log, per-host overlap rows and raw GB/s stay in the full
    # cumulative dict
    'sharded_staging_h2d_efficiency',
    'sharded_staging_gb_per_sec',
    'vit_train_steps_per_sec',
    'vit_train_mfu',
    'lm_train_steps_per_sec',
    'hello_world_rss_mb',
    'hello_world_cpu_percent',
    'probe_platform',
    'skipped_sections',
    'bench_elapsed_sec',
)


def _remaining():
    return BUDGET_SECONDS - (time.monotonic() - _START)


def _clamp_timeout(default):
    """A subprocess timeout that can never outlive the global budget."""
    return max(15, min(default, _remaining() - 10))


def _hello_world_schema():
    import numpy as np
    import pyarrow as pa

    from petastorm_tpu.codecs import (
        CompressedImageCodec, NdarrayCodec, ScalarCodec,
    )
    from petastorm_tpu.unischema import Unischema, UnischemaField

    return Unischema('HelloWorldSchema', [
        UnischemaField('id', np.int32, (), ScalarCodec(pa.int32()), False),
        UnischemaField('array_4d', np.uint8, (128,), NdarrayCodec(), False),
        UnischemaField('image1', np.uint8, (32, 32, 3),
                       CompressedImageCodec('png'), False),
    ])


def _build_hello_world(url):
    import numpy as np

    from petastorm_tpu.etl.dataset_metadata import write_dataset

    rng = np.random.RandomState(42)
    rows = [{
        'id': i,
        'array_4d': rng.randint(0, 255, (128,), dtype=np.uint8),
        'image1': rng.randint(0, 255, (32, 32, 3), dtype=np.uint8),
    } for i in range(HELLO_ROWS)]
    write_dataset(url, _hello_world_schema(), rows,
                  rowgroup_size_rows=100, num_files=4)


def _build_imagenet_like(url):
    """224x224x3 jpeg rows: the BASELINE.json north-star shape."""
    import cv2
    import numpy as np
    import pyarrow as pa

    from petastorm_tpu.codecs import CompressedImageCodec, ScalarCodec
    from petastorm_tpu.etl.dataset_metadata import write_dataset
    from petastorm_tpu.unischema import Unischema, UnischemaField

    schema = Unischema('ImagenetLikeSchema', [
        UnischemaField('noun_id', np.str_, (), ScalarCodec(pa.string()), False),
        UnischemaField('image', np.uint8, IMAGENET_SHAPE,
                       CompressedImageCodec('jpeg', quality=90), False),
    ])
    rng = np.random.RandomState(7)

    def _smooth():
        # low-frequency content so jpeg sizes resemble natural images
        base = (rng.rand(8, 8, 3) * 180).astype(np.uint8)
        return cv2.resize(base, (224, 224),
                          interpolation=cv2.INTER_CUBIC).astype(np.float64)

    smooth = _smooth()
    rows = []
    for i in range(IMAGENET_ROWS):
        noise = rng.rand(*IMAGENET_SHAPE) * 60
        rows.append({'noun_id': 'n%08d' % i,
                     'image': np.clip(smooth + noise, 0, 255).astype(np.uint8)})
        if i % 64 == 63:
            smooth = _smooth()
    write_dataset(url, schema, rows, rowgroup_size_rows=64, num_files=2)


def _build_selective(url):
    """Sorted-id rows with a decode-heavy jpeg column: the query-shaped
    (selective) workload. Sorted ids give tight per-row-group min/max
    statistics, so a range predicate's selectivity maps directly onto
    prunable row-groups — the shape of an eval-slice / per-user read."""
    import cv2
    import numpy as np
    import pyarrow as pa

    from petastorm_tpu.codecs import CompressedImageCodec, ScalarCodec
    from petastorm_tpu.etl.dataset_metadata import write_dataset
    from petastorm_tpu.unischema import Unischema, UnischemaField

    schema = Unischema('SelectiveSchema', [
        UnischemaField('id', np.int32, (), ScalarCodec(pa.int32()), False),
        UnischemaField('image', np.uint8, SELECTIVE_SHAPE,
                       CompressedImageCodec('jpeg', quality=90), False),
    ])
    rng = np.random.RandomState(11)
    base = cv2.resize((rng.rand(8, 8, 3) * 180).astype(np.uint8),
                      SELECTIVE_SHAPE[:2],
                      interpolation=cv2.INTER_CUBIC).astype(np.float64)
    rows = [{'id': i,
             'image': np.clip(base + rng.rand(*SELECTIVE_SHAPE) * 60,
                              0, 255).astype(np.uint8)}
            for i in range(SELECTIVE_ROWS)]
    write_dataset(url, schema, rows,
                  rowgroup_size_rows=SELECTIVE_ROWGROUP_ROWS, num_files=4)


def _build_io_overlap(url):
    """Scalar rows across many row-groups: decode is nearly free, so an
    injected-latency filesystem makes storage round trips the wall —
    the readahead plane's home turf (a jpeg workload would hide the
    contrast behind decode time)."""
    import numpy as np
    import pyarrow as pa

    from petastorm_tpu.codecs import ScalarCodec
    from petastorm_tpu.etl.dataset_metadata import write_dataset
    from petastorm_tpu.unischema import Unischema, UnischemaField

    schema = Unischema('IoOverlapSchema', [
        UnischemaField('id', np.int64, (), ScalarCodec(pa.int64()), False),
        UnischemaField('value', np.float64, (),
                       ScalarCodec(pa.float64()), False),
        UnischemaField('tag', np.str_, (), ScalarCodec(pa.string()),
                       False),
    ])
    rows = [{'id': i, 'value': i * 0.25, 'tag': 'row-%06d' % i}
            for i in range(IO_OVERLAP_ROWS)]
    # TWO files: footer/open costs amortize over many row-groups (as on
    # any real store) while multi-file path handling still exercises
    write_dataset(url, schema, rows,
                  rowgroup_size_rows=IO_OVERLAP_ROWGROUP_ROWS, num_files=2)


def _write_bench_schema():
    """Scalar id + a repeated string payload: enough bytes per row that
    the MB/s number reflects flush/serialization work, while encode
    stays cheap enough that the local-vs-fleet contrast is about
    dispatch, not codec time."""
    import numpy as np
    import pyarrow as pa

    from petastorm_tpu.codecs import ScalarCodec
    from petastorm_tpu.unischema import Unischema, UnischemaField

    return Unischema('WriteBenchSchema', [
        UnischemaField('id', np.int64, (), ScalarCodec(pa.int64()), False),
        UnischemaField('payload', np.str_, (), ScalarCodec(pa.string()),
                       False),
    ])


def _write_bench_fs(url):
    from petastorm_tpu.fs import get_filesystem_and_path_or_paths
    return get_filesystem_and_path_or_paths(url)


def _build_mixture_source(url, num_docs, seed):
    """Plain-parquet token corpus (list<int64> ``tokens``) across many
    row-groups — the mixture engine's input shape."""
    import numpy as np
    import pyarrow as pa
    import pyarrow.parquet as pq

    path = url[len('file://'):]
    os.makedirs(path, exist_ok=True)
    rng = np.random.RandomState(seed)
    per_file = (num_docs + 1) // 2
    doc_id = 0
    for file_idx in range(2):
        n = min(per_file, num_docs - doc_id)
        tokens = [rng.randint(2, 1000, size=int(rng.randint(20, 400)))
                  .tolist() for _ in range(n)]
        table = pa.table({'doc_id': np.arange(doc_id, doc_id + n),
                          'tokens': tokens})
        pq.write_table(table, os.path.join(path,
                                           'part-%d.parquet' % file_idx),
                       row_group_size=64)
        doc_id += n


class _SlowFile:
    """One fixed round-trip of latency per read request — the
    per-request cost shape of remote/cold object storage."""

    def __init__(self, wrapped, delay_s):
        self._f = wrapped
        self._delay = delay_s

    def read(self, *args):
        time.sleep(self._delay)
        return self._f.read(*args)

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self._f.close()
        return False

    def __getattr__(self, name):
        return getattr(self._f, name)


class _SlowFS:
    """fsspec-delegating wrapper whose opened files pay the read
    latency; everything else (protocol checks, listings, footers at
    construction) passes straight through."""

    def __init__(self, fs, delay_s):
        self._fs = fs
        self._delay = delay_s

    def open(self, *args, **kwargs):
        return _SlowFile(self._fs.open(*args, **kwargs), self._delay)

    def __getattr__(self, name):
        return getattr(self._fs, name)


def _measure_rows(url):
    from petastorm_tpu.reader import make_reader
    # workers_count=None auto-sizes to the host (the reference's published
    # number used 3 workers on its own box; ours adapts the same way)
    with make_reader(url, reader_pool_type='thread',
                     num_epochs=None, shuffle_row_groups=True) as reader:
        for _ in range(WARMUP_SAMPLES):
            next(reader)
        start = time.monotonic()
        for _ in range(MEASURE_SAMPLES):
            next(reader)
        return MEASURE_SAMPLES / (time.monotonic() - start)


def _build_c4_like(url):
    from examples.lm.pretrain_example import generate_c4_like
    generate_c4_like(url, num_docs=C4_DOCS)


def _measure_lm_tokens(url, seq_len=128, warmup_rows=64, measure_rows=2048):
    """BASELINE config 5: variable-length token docs packed to fixed
    ``seq_len`` rows on the decode workers — packed tokens/sec."""
    from examples.lm.pretrain_example import packing_transform

    if SMOKE:
        warmup_rows, measure_rows = 16, 128
    rate, _ = _measure_batch(url, warmup_rows, measure_rows,
                             transform_spec=packing_transform(seq_len))
    return rate * seq_len


def _measure_batch(url, warmup_rows, measure_rows, bytes_per_row=0,
                   transform_spec=None):
    """Batched column reader: rows/sec (and decoded MB/s when sized)."""
    from petastorm_tpu.reader import make_batch_reader
    with make_batch_reader(url, reader_pool_type='thread',
                           num_epochs=None, shuffle_row_groups=True,
                           transform_spec=transform_spec) as reader:
        seen = 0
        while seen < warmup_rows:
            batch = next(reader)
            seen += len(next(iter(batch._asdict().values())))
        seen = 0
        start = time.monotonic()
        while seen < measure_rows:
            batch = next(reader)
            seen += len(next(iter(batch._asdict().values())))
        elapsed = time.monotonic() - start
    rate = seen / elapsed
    return rate, rate * bytes_per_row / 2 ** 20


_TFDATA_SNIPPET = r'''
import json, os, sys, time
os.environ.setdefault('TF_CPP_MIN_LOG_LEVEL', '3')
import numpy as np
import tensorflow as tf
tfrecord_path, warmup, measure = sys.argv[1], int(sys.argv[2]), int(sys.argv[3])

def parse(example):
    feat = tf.io.parse_single_example(example, {
        'image': tf.io.FixedLenFeature([], tf.string),
        'noun_id': tf.io.FixedLenFeature([], tf.string),
    })
    return tf.io.decode_jpeg(feat['image'], channels=3)

dataset = (tf.data.TFRecordDataset(tfrecord_path)
           .repeat()
           .map(parse, num_parallel_calls=tf.data.AUTOTUNE)
           .batch(64)
           .prefetch(tf.data.AUTOTUNE))
it = iter(dataset)
seen = 0
while seen < warmup:
    seen += int(next(it).shape[0])
seen = 0
start = time.monotonic()
while seen < measure:
    seen += int(next(it).shape[0])
elapsed = time.monotonic() - start
print(json.dumps({"rows_per_sec": seen / elapsed}))
'''


def _run_subprocess(argv, timeout):
    """Run a helper subprocess → ``(completed_process, None)`` on success or
    ``(None, error_string)``; the benchmark never dies on helper failures.
    On timeout the partial stdout (if any) rides along in the error tuple
    as ``(stdout_str, 'timeout')`` so measurement snippets that print
    intermediate result lines don't lose them to the kill."""
    try:
        out = subprocess.run(argv, capture_output=True, timeout=timeout,
                             text=True)
    except subprocess.TimeoutExpired as e:
        partial = e.stdout
        if isinstance(partial, bytes):
            partial = partial.decode('utf-8', 'replace')
        return partial, 'timeout'
    if out.returncode != 0:
        return None, (out.stderr or 'failed').strip()[-300:]
    return out, None


def _parse_last_json_line(text):
    try:
        return json.loads(text.strip().splitlines()[-1])
    except (ValueError, IndexError, AttributeError):
        return None


def _run_json_subprocess(argv, timeout):
    """Run a measurement subprocess; parse its last stdout line as JSON.

    Same last-line contract the driver applies to bench.py itself — and
    the same salvage rule: a snippet killed by the timeout still yields
    whatever cumulative result line it had already printed (marked
    ``partial_after_timeout`` so the artifact says what happened)."""
    out, error = _run_subprocess(argv, timeout)
    if error is not None:
        salvaged = _parse_last_json_line(out) if isinstance(out, str) else None
        if salvaged is not None:
            salvaged['partial_after_timeout'] = True
            return salvaged
        return {'error': error}
    result = _parse_last_json_line(out.stdout)
    return result if result is not None else {'error': 'unparseable output'}


_PROBE_SNIPPET = r'''
import json
import jax
d = jax.devices()[0]
print(json.dumps({"platform": d.platform, "device_kind": d.device_kind}))
'''


def _probe_tpu(extra, timeout=75):
    """One upfront device probe in a guarded subprocess (VERDICT r3 #1b).

    Backend init can hang; probing once bounds that cost to ``timeout``
    seconds for the WHOLE benchmark. When the probe finds no TPU (an
    error, or another default platform) the run is marked
    ``tpu_unavailable`` and every later device section records an error
    without spawning — no device section ever measures the CPU.
    """
    result = _run_json_subprocess(
        [sys.executable, '-c', _PROBE_SNIPPET], _clamp_timeout(timeout))
    if 'error' in result:
        extra['tpu_unavailable'] = result['error']
        return
    extra['probe_platform'] = result['platform']
    extra['probe_device_kind'] = result['device_kind']
    if result['platform'] != 'tpu':
        extra['tpu_unavailable'] = 'default backend is %s' % (
            result['platform'],)


def _build_tfrecord(url, timeout=240):
    """Re-encode the parquet dataset's jpeg cells into a TFRecord file.
    Returns the path, or an error string."""
    code = r'''
import glob, sys
import pyarrow.parquet as pq
import tensorflow as tf
out, pattern = sys.argv[1], sys.argv[2]
with tf.io.TFRecordWriter(out) as writer:
    for path in sorted(glob.glob(pattern)):
        table = pq.read_table(path, columns=['noun_id', 'image'])
        for nid, img in zip(table.column('noun_id').to_pylist(),
                            table.column('image').to_pylist()):
            ex = tf.train.Example(features=tf.train.Features(feature={
                'noun_id': tf.train.Feature(
                    bytes_list=tf.train.BytesList(value=[nid.encode()])),
                'image': tf.train.Feature(
                    bytes_list=tf.train.BytesList(value=[bytes(img)])),
            }))
            writer.write(ex.SerializeToString())
'''
    root = url[len('file://'):]
    tfrecord_path = root + '.tfrecord'
    _, error = _run_subprocess(
        [sys.executable, '-c', code, tfrecord_path, root + '/*.parquet'],
        _clamp_timeout(timeout))
    if error is not None:
        return None, 'tfrecord build: %s' % error
    return tfrecord_path, None


def _measure_tfdata(tfrecord_path, warmup, measure, timeout=240):
    """BASELINE.json north star: the same jpeg bytes through a
    tf.data+TFRecord input pipeline, for a like-for-like rows/sec ratio.
    Runs in a subprocess so TF's runtime never pollutes this process."""
    return _run_json_subprocess(
        [sys.executable, '-c', _TFDATA_SNIPPET, tfrecord_path,
         str(warmup), str(measure)], _clamp_timeout(timeout))


_JAX_SNIPPET = r'''
import json, os, sys, time
sys.path.insert(0, %(repo)r)
import jax
from petastorm_tpu.benchmark.device import enable_compile_cache, require_tpu
enable_compile_cache(%(repo)r)
require_tpu()  # no TPU: this section fails, never measures the CPU
# arm the live observability plane (ephemeral loopback port): the
# section's result embeds the final live /report snapshot — rollup
# windows + any anomaly events — so BENCH_r0x rounds are self-describing
# about HOW the measured rate was produced, not just its value
os.environ.setdefault('PETASTORM_TPU_OBS_PORT', '0')
os.environ.setdefault('PETASTORM_TPU_OBS_WINDOW_SEC', '0.5')
from petastorm_tpu.jax import make_jax_loader
url, batch_size, warmup, measure, fields = %(url)r, %(batch)d, %(warmup)d, %(measure)d, %(fields)r
with make_jax_loader(url, batch_size=batch_size, fields=fields,
                     num_epochs=None,
                     shuffle_row_groups=True) as loader:
    import jax.numpy as jnp
    it = iter(loader)
    seen = 0
    fence = jnp.zeros((), jnp.float32)
    while seen < warmup:
        b = next(it); seen += batch_size
        for arr in b.values():
            # warm the fence ops' compiles outside the measured window
            fence = fence + jnp.sum(arr[..., :1].astype(jnp.float32))
    float(fence)
    # Steady-state gate: the fence read above fully drained the transfer
    # pipeline, so the next batch pays the un-overlapped refill (and any
    # dispatch-path compile) alone — a first-batch outlier that belongs to
    # warmup, not to the steady-state rate the h2d_* metrics claim.
    # Consume ONE batch outside the timed window to exclude it.
    for arr in next(it).values():
        arr.block_until_ready()
    from petastorm_tpu.telemetry import pipeline_report, get_registry
    stage_baseline = get_registry().snapshot()
    seen = 0
    nbytes = 0
    fence = jnp.zeros((), jnp.float32)
    start = time.monotonic()
    while seen < measure:
        b = next(it)
        for arr in b.values():
            arr.block_until_ready()
            nbytes += arr.nbytes
            # device-side touch of every staged array: the final host READ
            # of `fence` (below) transitively requires every transfer to
            # have really completed, even if an experimental runtime's
            # ready-signal fires early
            fence = fence + jnp.sum(arr[..., :1].astype(jnp.float32))
        seen += batch_size
    float(fence)
    elapsed = time.monotonic() - start
    # fill/transfer overlap achieved over the measured window only (the
    # registry baseline scopes it); None when telemetry is off or the
    # staging arena is disabled
    overlap_share = pipeline_report(
        baseline=stage_baseline).get('h2d_overlap_share')
    # where image decode ran for this pass (fused-into-slot / -slab /
    # batched) — makes BENCH_r0x rounds attributable when the fusion
    # silently falls back (docs/troubleshoot.md)
    fused_mode = loader.diagnostics.get('fused_decode_mode')
    # final LIVE /report snapshot through the real HTTP endpoint (the
    # same bytes an operator's curl would get), trimmed to the
    # attribution keys: rollup headline, stall verdict, anomaly events.
    # Optional: a scrape failure must never cost the measured rate.
    live_report = None
    try:
        import urllib.request
        from petastorm_tpu.telemetry import obs_server
        obs_port = obs_server.server_port()
        if obs_port:
            live = json.loads(urllib.request.urlopen(
                'http://127.0.0.1:%%d/report' %% obs_port,
                timeout=10).read())
            live_report = {
                'stall_verdict': (live.get('stall') or {}).get('verdict'),
                'rollup': (live.get('rollup') or {}).get('headline'),
                'anomalies': (live.get('anomalies') or {}).get('by_kind'),
                'anomaly_recent': [
                    {'kind': e.get('kind'), 'detail': e.get('detail')}
                    for e in (live.get('anomalies') or {})
                    .get('recent', [])],
            }
    except Exception as e:
        live_report = {'error': repr(e)[:200]}

# Raw H2D calibration: device_put the SAME host batch shapes in a tight
# loop — the link's achievable bandwidth with zero pipeline around it.
# h2d_efficiency = loader H2D / raw H2D attributes the host-vs-jax gap:
# ~1.0 means the staging layer saturates the link (the gap IS the
# link); <1.0 means staging overhead steals bandwidth.
import numpy as np
hosts = [{k: np.array(v) for k, v in b.items()} for _ in range(2)]
batch_bytes = sum(a.nbytes for a in hosts[0].values())


def raw_h2d_mb(batches, reps_budget_bytes=3e8):
    """Tight device_put loop over alternating host batches → MB/s."""
    nbytes = sum(a.nbytes for a in batches[0].values())
    reps = max(4, min(64, int(reps_budget_bytes / max(1, nbytes))))
    # warm lazy init AND the fence ops' compiles outside the timed window
    for arr in jax.device_put(batches[0]).values():
        np.asarray(arr.ravel()[:1])
    start = time.monotonic()
    put = None
    for i in range(reps):
        put = jax.device_put(batches[i %% 2])  # alternate: defeat caching
        for arr in put.values():
            arr.block_until_ready()
    # final-rep D2H value reads: transfers execute in dispatch order on the
    # device, so forcing the LAST rep's arrays to concrete host values
    # bounds the whole sequence even if intermediate ready-signals fired
    # early (a per-rep device-op fence would dominate the measurement with
    # dispatch overhead on fast links)
    for arr in put.values():
        np.asarray(arr.ravel()[:1])  # device-side slice: 1-element D2H only
    return reps * nbytes / (time.monotonic() - start) / 2 ** 20


raw_mb = raw_h2d_mb(hosts)
loader_mb = nbytes / elapsed / 2 ** 20
result = {"rows_per_sec": seen / elapsed,
          "h2d_mb_per_sec": loader_mb,
          "h2d_gb_per_sec": loader_mb / 1024,
          "raw_h2d_mb_per_sec": raw_mb,
          "raw_h2d_gb_per_sec": raw_mb / 1024,
          "staged_bytes_per_batch": batch_bytes,
          "staged_dtypes": sorted({str(a.dtype) for a in hosts[0].values()}),
          "h2d_efficiency": loader_mb / raw_mb}
if overlap_share is not None:
    result["h2d_overlap_share"] = overlap_share
if fused_mode is not None:
    result["fused_decode_mode"] = fused_mode
if live_report is not None:
    result["live_report"] = live_report

# Bytes accounting for the uint8-staging design (VERDICT r3 #3): image
# pipelines stage uint8 over the link and cast/normalize ON DEVICE
# (ops/normalize.py), quartering link bytes vs staging f32. Measure the
# same pixels staged as f32 for the like-for-like rate, and report the
# f32-EQUIVALENT delivery rate of the uint8 path (pixels that arrive per
# second, scaled to f32 width) after demonstrating the on-device cast.
# Guarded: this block is OPTIONAL extra accounting, and a late failure
# (link wedge, device OOM) must not discard the core numbers already
# measured above — same never-lose-measured-data contract as the
# per-section emission in bench.py itself.
if all(a.dtype == np.uint8 for a in hosts[0].values()):
    try:
        f32_hosts = [{k: v.astype(np.float32) for k, v in h.items()}
                     for h in hosts]
        result["raw_h2d_f32_gb_per_sec"] = raw_h2d_mb(f32_hosts) / 1024
        # prove the on-device cast path runs (bf16 normalize of the
        # staged uint8 batch) — the f32-equivalent claim is only honest
        # if it does
        staged = jax.device_put(hosts[0])
        arr = next(iter(staged.values()))
        jnp.mean((arr.astype(jnp.bfloat16) - 127.5)
                 / 58.0).block_until_ready()
        result["f32_equiv_delivery_gb_per_sec"] = 4.0 * raw_mb / 1024
    except Exception as e:
        result["f32_accounting_error"] = repr(e)[:200]
print(json.dumps(result))
'''


def _measure_jax(url, batch_size, warmup, measure, fields, timeout=150):
    """JAX H2D staging in a guarded subprocess (default device = real chip
    under the driver). Returns dict or an {"error": ...} marker."""
    code = _JAX_SNIPPET % {
        'repo': os.path.dirname(os.path.abspath(__file__)), 'url': url,
        'batch': batch_size, 'warmup': warmup, 'measure': measure,
        'fields': fields}
    return _run_json_subprocess([sys.executable, '-c', code],
                                _clamp_timeout(timeout))


_JAX_DUMMY_SNIPPET = r'''
import json, os, sys, time
sys.path.insert(0, %(repo)r)
import numpy as np
import jax
from petastorm_tpu.benchmark.device import enable_compile_cache, require_tpu
enable_compile_cache(%(repo)r)
require_tpu()  # no TPU: this section fails, never measures the CPU
import jax.numpy as jnp
from petastorm_tpu.benchmark.dummy_reader import DummyBatchReader
from petastorm_tpu.jax import make_jax_loader

batch, warmup, measure, shape = %(batch)d, %(warmup)d, %(measure)d, %(shape)r


def factory(url, **kw):
    # zero I/O, zero decode: pre-generated in-RAM batches of the SAME
    # decoded shape the real pipeline stages
    return DummyBatchReader(fields={'image': (tuple(shape), np.uint8)},
                            batch_size=batch, num_batches=None)


with make_jax_loader('dummy://calibration', batch_size=batch,
                     num_epochs=None, reader_factory=factory) as loader:
    it = iter(loader)
    fence = jnp.zeros((), jnp.float32)
    seen = 0
    while seen < warmup:
        b = next(it); seen += batch
        for arr in b.values():
            fence = fence + jnp.sum(arr[..., :1].astype(jnp.float32))
    float(fence)
    seen = 0
    fence = jnp.zeros((), jnp.float32)
    start = time.monotonic()
    while seen < measure:
        b = next(it)
        for arr in b.values():
            arr.block_until_ready()
            fence = fence + jnp.sum(arr[..., :1].astype(jnp.float32))
        seen += batch
    float(fence)
    elapsed = time.monotonic() - start
print(json.dumps({"rows_per_sec": seen / elapsed}))
'''


_SHARDED_STAGING_SNIPPET = r'''
import json, os, sys, time
sys.path.insert(0, %(repo)r)
# fast autotune windows so the decision log can show work inside a short
# bench section (actions stay bounded by the MAX knobs as always)
os.environ.setdefault('PETASTORM_TPU_STAGING_AUTOTUNE_WINDOW_SEC', '0.25')
import numpy as np
import jax
from petastorm_tpu.benchmark.device import enable_compile_cache, require_tpu
enable_compile_cache(%(repo)r)
require_tpu()  # no TPU: this section fails, never measures the CPU
import jax.numpy as jnp
from petastorm_tpu.benchmark.dummy_reader import DummyBatchReader
from petastorm_tpu.jax import autotune
from petastorm_tpu.jax.loader import make_jax_loader
from petastorm_tpu.parallel.mesh import DATA_AXIS, make_mesh
from petastorm_tpu.parallel.sharding import local_shard_plan
from petastorm_tpu.telemetry import get_registry, pipeline_report

batch, warmup, measure, shape = %(batch)d, %(warmup)d, %(measure)d, %(shape)r
devices = jax.devices()
mesh = make_mesh(data=len(devices))


def factory(url, **kw):
    # zero I/O, zero decode: the sharded staging + H2D cost in isolation
    # (the real-pipeline rates are the imagenet_jax section's job)
    return DummyBatchReader(fields={'image': (tuple(shape), np.uint8)},
                            batch_size=batch, num_batches=None)


with make_jax_loader('dummy://sharded', batch_size=batch, num_epochs=None,
                     mesh=mesh, data_axes=(DATA_AXIS,),
                     reader_factory=factory) as loader:
    it = iter(loader)
    fence = jnp.zeros((), jnp.float32)
    seen = 0
    while seen < warmup:
        b = next(it); seen += batch
        for arr in b.values():
            fence = fence + jnp.sum(arr[..., :1].astype(jnp.float32))
    float(fence)
    # steady-state gate (same contract as the jax section): one batch
    # outside the timed window absorbs the un-overlapped refill
    for arr in next(it).values():
        arr.block_until_ready()
    stage_baseline = get_registry().snapshot()
    seen = 0
    nbytes = 0
    fence = jnp.zeros((), jnp.float32)
    start = time.monotonic()
    while seen < measure:
        b = next(it)
        for arr in b.values():
            arr.block_until_ready()
            # shard-slice accounting: the bytes THIS host put on the wire
            nbytes += arr.nbytes // jax.process_count()
            fence = fence + jnp.sum(arr[..., :1].astype(jnp.float32))
        seen += batch
    float(fence)
    elapsed = time.monotonic() - start
    overlap = pipeline_report(
        baseline=stage_baseline).get('h2d_overlap_share')
    diag = loader.diagnostics

# Raw sharded-put calibration: the same per-device row plan the loader
# dispatches with, in a tight loop with zero pipeline around it — the
# mesh link's achievable wire speed. h2d_efficiency = loader / raw.
sharding = loader.sharding
plan = local_shard_plan(sharding, batch)
rng = np.random.RandomState(3)
hosts = [{'image': rng.randint(0, 255, (batch,) + tuple(shape),
                               dtype=np.uint8)} for _ in range(2)]


def put_planned(tree):
    slices, devs = [], []
    for arr in tree.values():
        for dev, lo, hi in plan:
            slices.append(arr[lo:hi])
            devs.append(dev)
    return jax.device_put(slices, devs)


raw_mb = None
if plan is not None:
    nb = sum(a.nbytes for a in hosts[0].values())
    reps = max(4, min(64, int(3e8 / max(1, nb))))
    for arr in put_planned(hosts[0]):
        arr.block_until_ready()
    t0 = time.monotonic()
    out = None
    for i in range(reps):
        out = put_planned(hosts[i %% 2])
        for arr in out:
            arr.block_until_ready()
    for arr in out:
        np.asarray(arr.ravel()[:1])
    raw_mb = reps * nb / (time.monotonic() - t0) / 2 ** 20

loader_mb = nbytes / elapsed / 2 ** 20
result = {
    'devices': len(devices),
    'rows_per_sec': seen / elapsed,
    'gb_per_sec': loader_mb / 1024,
    'slot_depth': diag.get('staging_slot_depth'),
    'prefetch_depth': diag.get('staging_prefetch'),
    'autotune_decisions': autotune.decision_counts(),
    'autotune_recent': autotune.recent_decisions(5),
}
if raw_mb:
    result['raw_gb_per_sec'] = raw_mb / 1024
    result['h2d_efficiency'] = loader_mb / raw_mb
if overlap is not None:
    result['overlap_share'] = overlap
    # per-host rows of the mesh-wide overlap picture (one process here;
    # a pod job reports one row per host through its own endpoint)
    result['per_host_overlap_share'] = {str(jax.process_index()): overlap}
print(json.dumps(result))
'''


def _measure_sharded_staging(batch_size, warmup, measure, shape,
                             timeout=150):
    """Shard-aware staging engine on a data mesh over every visible
    TPU device: aggregate GB/s into NamedSharding batches, per-host
    overlap share, staged-vs-raw h2d efficiency, and the autotuner's
    decision log."""
    code = _SHARDED_STAGING_SNIPPET % {
        'repo': os.path.dirname(os.path.abspath(__file__)),
        'batch': batch_size, 'warmup': warmup, 'measure': measure,
        'shape': tuple(shape)}
    return _run_json_subprocess([sys.executable, '-c', code],
                                _clamp_timeout(timeout))


def _measure_jax_dummy(batch_size, warmup, measure, shape, timeout=120):
    """The SAME make_jax_loader consumer over a DummyBatchReader source
    (zero I/O, zero decode): the framework-staging + H2D cost in
    isolation, so the real imagenet_jax sec/row decomposes — the
    reference's dummy-reader method (``benchmark/throughput.py:112-149``
    via ``benchmark/dummy_reader.py``)."""
    code = _JAX_DUMMY_SNIPPET % {
        'repo': os.path.dirname(os.path.abspath(__file__)),
        'batch': batch_size, 'warmup': warmup, 'measure': measure,
        'shape': tuple(shape)}
    return _run_json_subprocess([sys.executable, '-c', code],
                                _clamp_timeout(timeout))


_VIT_TRAIN_SNIPPET = r'''
import json, os, sys, time
sys.path.insert(0, %(repo)r)
import numpy as np
import jax
from petastorm_tpu.benchmark.device import enable_compile_cache, require_tpu
enable_compile_cache(%(repo)r)
require_tpu()  # no TPU: this section fails, never measures the CPU
import jax.numpy as jnp
import optax
from petastorm_tpu.models.vit import (
    ViTConfig, init_vit_params, vit_train_step,
)

# Image-family silicon throughput (VERDICT r4 #7): ViT-Base dims on a
# 32x32 patch grid — image 384 / patch 12 gives S=1024 patches, a
# multiple of the fused kernel's 128 block, so attention rides the
# bidirectional flash path (models/vit.py).
cfg_kw = dict(image_size=384, patch_size=12, n_classes=1000,
              d_model=768, n_heads=12, n_layers=12, d_ff=3072)
batch, warmup, measure = 16, 2, 12

attn_impl = 'dense'
config = ViTConfig(**cfg_kw)
rng = np.random.RandomState(0)
# two synthetic in-HBM batches, alternated: this is the COMPUTE-side
# number (the ingest side of the image family is the imagenet_jax
# section); two buffers defeat any single-buffer caching
images = [jnp.asarray(rng.rand(batch, config.image_size, config.image_size,
                               3).astype(np.float32)) for _ in range(2)]
labels = [jnp.asarray(rng.randint(0, cfg_kw['n_classes'], (batch,),
                                  np.int32)) for _ in range(2)]
optimizer = optax.adamw(1e-3)


def build(cfg):
    p = init_vit_params(jax.random.PRNGKey(0), cfg)
    return p, optimizer.init(p), vit_train_step(cfg, optimizer)


from petastorm_tpu.ops.flash_attention import kernel_supported
use_flash = kernel_supported(config.n_patches)  # honest label: 'flash'
try:                                            # means the kernel RAN
    if not use_flash:
        raise RuntimeError('n_patches=%%d below the kernel block'
                           %% config.n_patches)
    flash_cfg = ViTConfig(attn_impl='flash', **cfg_kw)
    params, opt_state, step = build(flash_cfg)
    p2, o2, l2 = step(params, opt_state, images[0], labels[0])
    float(l2)
    config, attn_impl = flash_cfg, 'flash'
    params, opt_state = p2, o2
except Exception as e:
    print('vit flash unavailable, dense fallback: %%r' %% (e,),
          file=sys.stderr)
    params, opt_state, step = build(config)
    params, opt_state, _ = step(params, opt_state, images[0], labels[0])
for i in range(max(0, warmup - 1)):
    params, opt_state, loss = step(params, opt_state, images[i %% 2],
                                   labels[i %% 2])
float(jnp.sum(jax.tree_util.tree_leaves(params)[0]
              .astype(jnp.float32)))  # D2H fence before the timed window
start = time.monotonic()
for i in range(measure):
    params, opt_state, loss = step(params, opt_state, images[i %% 2],
                                   labels[i %% 2])
final_loss = float(loss)  # D2H value fence bounds every prior step
elapsed = time.monotonic() - start

# Analytic matmul FLOPs per step (fwd 2 FLOP/MAC, bwd 2x fwd): patch
# embed + per-layer qkv/proj/ffn + attention scores + head.
c = config
S = c.n_patches
n_matmul = (c.patch_dim * c.d_model
            + c.n_layers * (4 * c.d_model ** 2
                            + 2 * c.d_model * c.d_ff))
flops_per_step = (6 * n_matmul * batch * S
                  + 12 * c.n_layers * batch * S ** 2 * c.d_model
                  + 6 * batch * c.d_model * c.n_classes)
from petastorm_tpu.benchmark.device import tpu_peak
peak = tpu_peak(jax.devices()[0].device_kind)  # unknown kind: KeyError
result = {
    "steps_per_sec": measure / elapsed,
    "images_per_sec": measure * batch / elapsed,
    "final_loss": final_loss,
    "attn_impl": attn_impl,
    "n_patches": S,
    "model_params_m": round((n_matmul + S * c.d_model
                             + c.d_model * c.n_classes) / 1e6, 1),
    "device_kind": jax.devices()[0].device_kind,
}
result["mfu"] = flops_per_step * measure / elapsed / peak
print(json.dumps(result))
'''


def _measure_vit_train(timeout=300):
    """ViT train throughput on the default device: the image family's
    compute-side silicon number (steps/s, images/s, MFU)."""
    code = _VIT_TRAIN_SNIPPET % {
        'repo': os.path.dirname(os.path.abspath(__file__))}
    return _run_json_subprocess([sys.executable, '-c', code],
                                _clamp_timeout(timeout))


_LM_TRAIN_SNIPPET = r'''
import json, os, sys, time
sys.path.insert(0, %(repo)r)
import numpy as np
import jax
from petastorm_tpu.benchmark.device import enable_compile_cache, require_tpu
enable_compile_cache(%(repo)r)
require_tpu()  # no TPU: this section fails, never measures the CPU
import optax
from petastorm_tpu.jax import make_jax_loader
from petastorm_tpu.models.transformer import (
    TransformerConfig, init_transformer_params, transformer_train_step,
)
from examples.lm.pretrain_example import packing_transform

url, batch, seq_len, warmup, measure = (
    %(url)r, %(batch)d, %(seq)d, %(warmup)d, %(measure)d)
warmup = max(1, warmup)  # the impl-selection step below consumes one batch
# Realistically-sized decoder (~335M params, 308M in matmul weights):
# large enough that the per-step matmuls tile the MXU and MFU is
# meaningful (BASELINE.json metric; a toy model would measure dispatch
# latency, not feeding capacity). The d_model=1536/10-layer shape was
# picked by measurement on the v5e-16GB: with the donated train state it
# reaches ~0.435 MFU, vs 0.406 for 1536/8 and 0.29 for the original
# 1024/12 config (wider matmuls tile the MXU better at the same FLOP
# budget); deeper/wider or batch>8 exhausts HBM with adamw state.
# loss_chunk: the (B, S, V) logits at this vocab are ~0.5 GB f32;
# chunked CE keeps peak loss memory at one 256-position chunk.
# 10 layers fit (vs 8 undonated) because the step donates the train
# state — measured MFU 0.435 at this shape vs 0.406 for L8.
model_kw = dict(max_seq_len=seq_len, loss_chunk=256, **%(flagship)r)
# tuned-variant knobs (VERDICT r4 #3): model DIMENSIONS stay the
# flagship's for cross-round comparability; overrides may only add
# execution levers (remat, loss_chunk) — batch rides the %%(batch)d param
model_kw.update(%(overrides)r)
config = TransformerConfig(**model_kw)
params = init_transformer_params(jax.random.PRNGKey(0), config)
optimizer = optax.adamw(1e-3)
opt_state = optimizer.init(params)
# donate=True: the train state updates in place (the whole measured loop
# is state = step(state, ...)), freeing a params+opt_state copy of HBM
step = transformer_train_step(config, optimizer, donate=True)

# Analytic matmul FLOPs per optimizer step (fwd 2 FLOP/MAC, bwd 2x fwd):
# parameter matmuls 6*N_matmul*tokens + attention scores 12*L*B*S^2*d.
# The train step next-token-shifts to S-1 positions.
c = config
s_eff = seq_len - 1
# qkv projection width varies with GQA; FFN matrix count with swiglu
qkv_params = c.d_model * (c.n_heads + 2 * c.kv_heads) * (c.d_model
                                                         // c.n_heads)
ffn_mats = 3 if c.ffn == 'swiglu' else 2
n_matmul = (c.n_layers * (qkv_params + c.d_model ** 2
                          + ffn_mats * c.d_model * c.d_ff)
            + c.d_model * c.vocab_size)
flops_per_step = (6 * n_matmul * batch * s_eff
                  + 12 * c.n_layers * batch * s_eff ** 2 * c.d_model)

# bf16 peak of the chip actually running the step (MFU denominator);
# a device_kind missing from the table is an error, not a dropped MFU
from petastorm_tpu.benchmark.device import tpu_peak
peak = tpu_peak(jax.devices()[0].device_kind)


def measured_matmul_tflops(n=4096, reps_lo=64, reps_hi=320):
    """Achievable bf16 matmul rate on THIS device, measured: a chained
    (sequentially dependent) square-matmul loop under one jit, fenced by
    a device-to-host value read. Cross-checks the book peak: if the
    device_kind's table entry disagrees wildly with what the silicon
    actually does, MFU numbers against the book value are meaningless.

    TWO rep counts, rate from the delta: one timed call carries a fixed
    dispatch + D2H-fence cost, and (t_hi - t_lo) cancels
    exactly that constant. The per-rep rescale keeps values finite and
    fuses into the matmul epilogue (unlike a tanh, which would add a
    separate HBM-bound elementwise pass to every rep)."""
    import jax.numpy as jnp

    def chain(a, reps):
        scale = jnp.bfloat16(4.0 / n)  # keeps x bounded: |row sum| ~ n/4
        def body(x, _):
            return (x @ a) * scale, None
        x, _ = jax.lax.scan(body, a, None, length=reps)
        return x

    a = jnp.asarray(np.random.RandomState(0).rand(n, n) * 0.5,
                    jnp.bfloat16)
    run = jax.jit(chain, static_argnums=1)

    def timed(reps):
        float(run(a, reps)[0, 0])  # compile + warm
        start = time.monotonic()
        float(run(a, reps)[0, 0])  # D2H fence
        return time.monotonic() - start

    t_lo, t_hi = timed(reps_lo), timed(reps_hi)
    if t_hi <= t_lo:
        # timing noise swallowed the compute delta: report a calibration
        # failure, never a clamped garbage rate posing as a measurement
        raise RuntimeError('non-positive timing delta (t_lo=%%.4fs, '
                           't_hi=%%.4fs): timing too noisy to calibrate'
                           %% (t_lo, t_hi))
    return 2.0 * n ** 3 * (reps_hi - reps_lo) / (t_hi - t_lo) / 1e12

attn_impl = 'dense'
with make_jax_loader(url, batch_size=batch, num_epochs=None,
                     transform_spec=packing_transform(seq_len),
                     shuffle_row_groups=True) as loader:
    it = loader.iter_steps(warmup + measure)
    staged = []
    first = next(it)['tokens']
    staged.append(first)
    from petastorm_tpu.ops.flash_attention import kernel_supported
    if kernel_supported(seq_len):
        # try the fused Pallas flash-attention step first (no HBM score
        # tensor -> higher MFU); an unsupported kernel on this chip just
        # falls back to the dense step. kernel_supported is the wrapper
        # module's own gate, so 'flash' in the output always means the
        # fused kernel actually ran. The steps DONATE the train state, so
        # the except path re-inits rather than reusing possibly-donated
        # buffers (compile failures leave them intact, but a runtime
        # failure after dispatch would not — re-init is deterministic and
        # cheap next to the step compile itself).
        try:
            flash_cfg = TransformerConfig(attn_impl='flash', **model_kw)
            flash_step = transformer_train_step(flash_cfg, optimizer,
                                                donate=True)
            p2, o2, l2 = flash_step(params, opt_state, first)
            float(l2)
            config, step, attn_impl = flash_cfg, flash_step, 'flash'
            params, opt_state, loss = p2, o2, l2
        except Exception as e:
            print('flash attention unavailable, dense fallback: %%r' %% (e,),
                  file=sys.stderr)
            params = init_transformer_params(jax.random.PRNGKey(0), config)
            opt_state = optimizer.init(params)
            params, opt_state, loss = step(params, opt_state, first)
    else:
        params, opt_state, loss = step(params, opt_state, first)
    for _ in range(warmup - 1):
        tokens = next(it)['tokens']
        if len(staged) < 4:
            staged.append(tokens)
        params, opt_state, loss = step(params, opt_state, tokens)
    # Timing fence: a DEVICE-TO-HOST VALUE READ, not block_until_ready.
    # The value of step N's loss transitively requires every prior step's
    # compute, and a concrete host float cannot be delivered early by an
    # experimental runtime the way a too-eager ready-signal can.
    float(loss)
    start = time.monotonic()
    for _ in range(measure):
        params, opt_state, loss = step(params, opt_state, next(it)['tokens'])
    # the reported loss is the LOADER-FED run's final loss; the synthetic
    # re-feed below keeps training and must not redefine it
    final_loss = float(loss)
    loader_elapsed = time.monotonic() - start

# Same step count fed from batches ALREADY in HBM: the loader-free step
# time. input_bound_util = loader-fed / in-HBM step time; <=1.05 means the
# input pipeline steals <=5%% of the step (BASELINE.json "input-bound
# step util"). Needs warmup>0 (staged batches are captured there).
synthetic_elapsed = None
if staged:
    start = time.monotonic()
    for i in range(measure):
        params, opt_state, loss = step(params, opt_state,
                                       staged[i %% len(staged)])
    float(loss)  # same D2H fence as the loader-fed loop
    synthetic_elapsed = time.monotonic() - start

result = {
    "steps_per_sec": measure / loader_elapsed,
    "train_tokens_per_sec": measure * batch * seq_len / loader_elapsed,
    "final_loss": final_loss,
    "model_params_m": round((n_matmul + c.vocab_size * c.d_model
                             + c.max_seq_len * c.d_model) / 1e6, 1),
    "device_kind": jax.devices()[0].device_kind,
    "attn_impl": attn_impl,
}
if synthetic_elapsed is not None:
    result["input_bound_util"] = loader_elapsed / synthetic_elapsed
result["mfu"] = flops_per_step * measure / loader_elapsed / peak
if synthetic_elapsed is not None:
    result["synthetic_mfu"] = (flops_per_step * measure
                               / synthetic_elapsed / peak)
# self-validate the MFU denominator against the silicon
try:
    measured = measured_matmul_tflops()
    result["measured_matmul_tflops"] = measured
    result["measured_vs_book_peak"] = measured * 1e12 / peak
except Exception as e:
    print('matmul calibration failed: %%r' %% (e,), file=sys.stderr)
print(json.dumps(result))
'''


_MFU_BREAKDOWN_SNIPPET = r'''
import json, os, sys, time
sys.path.insert(0, %(repo)r)
import numpy as np
import jax
from petastorm_tpu.benchmark.device import enable_compile_cache, require_tpu
enable_compile_cache(%(repo)r)
require_tpu()  # no TPU: this section fails, never measures the CPU
import jax.numpy as jnp

from petastorm_tpu.models.transformer import (
    _chunked_next_token_nll, _rmsnorm,
)
from petastorm_tpu.ops.flash_attention import flash_causal_attention

# Where the non-MXU 50+%% of the flagship step goes (VERDICT r4 #3):
# the flash-attention fwd+VJP, the rmsnorms, and the chunked
# loss+lm_head, each timed AT THE FLAGSHIP SHAPE. One timed call
# carries a fixed dispatch + D2H fence cost, so each part runs as ONE
# chained scan of R sequentially-dependent reps and the separately-
# measured dispatch constant is subtracted:
# per_rep = (t_chain - t_dispatch) / R. One scan length per part keeps
# the compile count at 3. The parent
# combines the part times with lm_train's step time and matmul
# calibration into shares.
kw = dict(max_seq_len=%(seq)d, **%(flagship)r)
B, S = %(batch)d, kw['max_seq_len']
d, H, L = kw['d_model'], kw['n_heads'], kw['n_layers']
V, dff = kw['vocab_size'], kw['d_ff']
Dh = d // H
rng = np.random.RandomState(1)

# the fixed cost of one fenced call: jit dispatch + D2H
_tiny = jax.jit(lambda x: x + 1.0)
float(_tiny(jnp.zeros((), jnp.float32)))  # compile
_samples = []
for _ in range(5):
    _t0 = time.monotonic()
    float(_tiny(jnp.zeros((), jnp.float32)))
    _samples.append(time.monotonic() - _t0)
_samples.sort()
t_dispatch = _samples[len(_samples) // 2]


def chain_time(vg_fn, x0, reps, *rest):
    """Per-rep seconds of vg_fn from one chained scan: x feeds back
    through its own gradient so reps cannot overlap; one D2H read
    fences the chain; the dispatch constant is subtracted."""
    @jax.jit
    def chain(x):
        def body(xc, _):
            val, gx = vg_fn(xc, *rest)
            return xc + jnp.bfloat16(1e-6) * gx, val
        _, vals = jax.lax.scan(body, x, None, length=reps)
        return vals[-1]

    float(chain(x0))  # compile + warm
    times = []
    for _ in range(2):
        start = time.monotonic()
        float(chain(x0))
        times.append(time.monotonic() - start)
    t = min(times)
    if t <= t_dispatch:
        raise RuntimeError('chain faster than the dispatch constant')
    return (t - t_dispatch) / reps


# CUMULATIVE emission per part (the parent parses the last stdout line
# and salvages it on a timeout kill — same contract as bench.py itself):
# a slow compile on a later part can never cost the parts already
# measured. Ordered by value: attention first.
result = {"dispatch_ms": t_dispatch * 1e3}

q0 = jnp.asarray(rng.randn(B, S, H, Dh) * 0.1, jnp.bfloat16)
kk = jnp.asarray(rng.randn(B, S, H, Dh) * 0.1, jnp.bfloat16)
vv = jnp.asarray(rng.randn(B, S, H, Dh) * 0.1, jnp.bfloat16)
attn_vg = jax.value_and_grad(
    lambda q, k, v: flash_causal_attention(q, k, v)
    .astype(jnp.float32).sum())
result["attn_total_ms"] = chain_time(
    lambda q: attn_vg(q, kk, vv), q0, 8) * L * 1e3
print(json.dumps(result), flush=True)

lm_head = jnp.asarray(rng.randn(d, V) * 0.02, jnp.bfloat16)
targets = jnp.asarray(rng.randint(0, V, (B, S - 1), np.int32))
mask = jnp.ones((B, S - 1), jnp.float32)
xs0 = jnp.asarray(rng.randn(B, S - 1, d) * 0.1, jnp.bfloat16)


def _nll(xc, w):
    nll, cnt = _chunked_next_token_nll(xc, w, targets, mask, 256,
                                       jnp.bfloat16)
    return nll / cnt


_nll_vg = jax.value_and_grad(_nll, argnums=(0, 1))


def _nll_part(xc):
    # consume BOTH grads: the real step also computes d(lm_head) — a
    # full (d, V) matmul, ~1/3 of the head's backward FLOPs — and a
    # grad-wrt-x-only timer would drop it. The 1e-30-scaled full
    # reduction of gw forces its computation without changing the value
    # (a 0.0 scale would invite multiply-by-zero folding).
    val, (gx, gw) = _nll_vg(xc, lm_head)
    return (val + jnp.float32(1e-30)
            * jnp.sum(gw).astype(jnp.float32), gx)


result["loss_head_ms"] = chain_time(_nll_part, xs0, 8) * 1e3
print(json.dumps(result), flush=True)

x0 = jnp.asarray(rng.randn(B, S, d) * 0.1, jnp.bfloat16)
gain = jnp.ones((d,), jnp.float32)
norm_vg = jax.value_and_grad(
    lambda x, g: _rmsnorm(x, g).astype(jnp.float32).sum())
result["norm_total_ms"] = chain_time(
    lambda x: norm_vg(x, gain), x0, 64) * (2 * L + 1) * 1e3
print(json.dumps(result), flush=True)
'''


# the breakdown MUST time the same (batch, seq) lm_train measures — its
# shares divide part-times by lm_train's step time (SMOKE shrinks both)
BREAKDOWN_BATCH, BREAKDOWN_SEQ = (2, 64) if SMOKE else (8, 1024)


def compute_staging_shares(real_rows_s, dummy_rows_s, raw_h2d_mb_s,
                           bytes_per_batch, batch_size):
    """Three-way split of the real pipeline's sec/row (VERDICT r4 #4):

    * ``jax_h2d_share`` — the link cost of the staged bytes, capped at
      the dummy path's whole time: on a degraded link the loader's
      overlapped H2D beats the raw tight loop (sec_dummy < sec_h2d), and
      attributing MORE than the dummy time to the link would make the
      three shares sum past 1;
    * ``jax_framework_share`` — what the dummy-fed loader adds on top of
      the (capped) link term: staging machinery; 0 in the overlap regime;
    * ``jax_io_decode_share`` — the remainder: parquet I/O + decode.

    The three shares partition sec/row(real) by construction (sum 1,
    up to rounding). Returns None unless all inputs are positive.
    """
    if not (real_rows_s and dummy_rows_s and raw_h2d_mb_s
            and bytes_per_batch):
        return None
    sec_real = 1.0 / real_rows_s
    sec_dummy = min(1.0 / dummy_rows_s, sec_real)
    sec_h2d = min((bytes_per_batch / batch_size)
                  / (raw_h2d_mb_s * 2 ** 20), sec_dummy)
    return {
        'jax_h2d_share': round(sec_h2d / sec_real, 4),
        'jax_framework_share': round(
            (sec_dummy - sec_h2d) / sec_real, 4),
        'jax_io_decode_share': round(
            (sec_real - sec_dummy) / sec_real, 4),
    }


def compute_mfu_breakdown(steps_per_sec, input_bound_util, tflops,
                          part_ms, flagship=None, batch=None, seq=None):
    """Combine measured part-times (ms) with the step rate into shares
    of the COMPUTE step (VERDICT r4 #3). ``tflops`` (lm_train's matmul
    calibration) adds the ideal-time term for the parameter matmuls
    outside the measured parts; ``input_wait_of_step`` reports the
    loader's share of the WALL step when input_bound_util > 1. Returns
    None without a step rate or any measured part."""
    measured = {key: v for key, v in part_ms.items() if v is not None}
    if not steps_per_sec or not measured:
        return None
    if tflops:
        k = flagship or FLAGSHIP_LM_KW
        d = k['d_model']
        b = batch or BREAKDOWN_BATCH
        s_eff = (seq or BREAKDOWN_SEQ) - 1
        no_head = k['n_layers'] * (3 * d * d + d * d
                                   + 2 * d * k['d_ff'])
        measured['param_matmul_ideal'] = (
            6 * no_head * b * s_eff / (tflops * 1e12) * 1e3)
    step_ms = 1000.0 / steps_per_sec
    util = input_bound_util
    compute_ms = step_ms / util if util and util > 1 else step_ms
    shares = {key: round(v / compute_ms, 4) for key, v in measured.items()}
    if len(measured) == 4:  # all parts present: close the sum
        shares['other'] = round(max(0.0, 1.0 - sum(shares.values())), 4)
    if util and util > 1:
        shares['input_wait_of_step'] = round(1.0 - 1.0 / util, 4)
    return shares


def _measure_mfu_breakdown(timeout=480):
    """Part-times of the flagship step's big consumers, for the
    ``lm_train_mfu_breakdown`` shares computed in ``sec_mfu_breakdown``."""
    code = _MFU_BREAKDOWN_SNIPPET % {
        'repo': os.path.dirname(os.path.abspath(__file__)),
        'flagship': FLAGSHIP_LM_KW, 'batch': BREAKDOWN_BATCH,
        'seq': BREAKDOWN_SEQ}
    return _run_json_subprocess([sys.executable, '-c', code],
                                _clamp_timeout(timeout))


_LM_DECODE_SNIPPET = r'''
import json, os, sys, time
sys.path.insert(0, %(repo)r)
import numpy as np
import jax
from petastorm_tpu.benchmark.device import enable_compile_cache, require_tpu
enable_compile_cache(%(repo)r)
require_tpu()  # no TPU: this section fails, never measures the CPU
import jax.numpy as jnp
from petastorm_tpu.models.transformer import (
    TransformerConfig, init_transformer_params,
)
from petastorm_tpu.models.generate import greedy_generate

# inference throughput on the SAME model family as lm_train: KV-cache
# greedy decode, one jitted prefill+scan; tokens/sec = new tokens over
# wall time after a D2H value fence
kw = dict(max_seq_len=1024, **%(flagship)r)  # = the lm_train shape
batch, prompt_len, n_lo, n_hi = 8, 128, 64, 256
config = TransformerConfig(**kw)
params = init_transformer_params(jax.random.PRNGKey(0), config)
prompt = jnp.asarray(np.random.RandomState(0).randint(
    0, kw['vocab_size'], (batch, prompt_len), np.int32))

# two decode lengths, rate from the delta: one call's time includes the
# prefill + dispatch/compile-cache costs, and (t_hi - t_lo) cancels them
# so the metric is the PURE per-token decode rate. Median of 3 per
# length: single runs on this box swing about ten percent (same policy
# as the imagenet/tfdata metrics).
import statistics


def make_runs(cfg):
    return {n: jax.jit(lambda p, t, n=n, c=cfg: greedy_generate(p, t, c, n))
            for n in (n_lo, n_hi)}


def timed(run_map, run_params, n):
    """Median-of-3 wall time of one decode length (compile outside)."""
    int(run_map[n](run_params, prompt)[0, -1])  # compile + warm
    samples = []
    for _ in range(3):
        start = time.monotonic()
        int(run_map[n](run_params, prompt)[0, -1])  # D2H fence
        samples.append(time.monotonic() - start)
    return statistics.median(samples)


def delta_rate(run_map, run_params):
    """Tokens/sec from the two-length delta, or None on inverted timing."""
    t_lo, t_hi = (timed(run_map, run_params, n) for n in (n_lo, n_hi))
    if t_hi <= t_lo:
        return None
    return batch * (n_hi - n_lo) / (t_hi - t_lo)

rate = delta_rate(make_runs(config), params)
if rate is None:
    print(json.dumps({"error": "non-positive decode timing delta"}))
    sys.exit(0)
result = {
    "decode_tokens_per_sec": rate,
    "per_stream_tokens_per_sec": rate / batch,
    "batch": batch, "new_tokens": n_hi,
    "device_kind": jax.devices()[0].device_kind,
}
# the base metric is now SAFE: the parent parses the LAST stdout line
# and salvages it even on a timeout kill, so the GQA phase below (two
# more flagship compiles) can never cost the numbers already measured
print(json.dumps(result), flush=True)

# GQA comparison: the SAME shape with grouped K/V heads — at this
# batch x context the KV cache's HBM reads rival the weights', so the
# group factor is a real decode lever and the artifact should show its
# measured worth, not a claim.
try:
    group = 4
    gqa_cfg = TransformerConfig(n_kv_heads=config.n_heads // group, **kw)
    gqa_params = init_transformer_params(jax.random.PRNGKey(0), gqa_cfg)
    gqa_rate = delta_rate(make_runs(gqa_cfg), gqa_params)
    if gqa_rate is None:
        result["gqa_error"] = "non-positive timing delta"
    else:
        result["gqa_decode_tokens_per_sec"] = gqa_rate
        result["gqa_kv_group"] = group
        result["gqa_decode_speedup"] = gqa_rate / rate
except Exception as e:
    result["gqa_error"] = repr(e)[:200]
print(json.dumps(result))
'''


def _measure_lm_decode(timeout=600):
    """KV-cache inference throughput on the flagship model family."""
    code = _LM_DECODE_SNIPPET % {
        'repo': os.path.dirname(os.path.abspath(__file__)),
        'flagship': FLAGSHIP_LM_KW}
    return _run_json_subprocess([sys.executable, '-c', code],
                                _clamp_timeout(timeout))


_PP_BF16_SNIPPET = r'''
import json, os, sys
sys.path.insert(0, %(repo)r)
import jax
from petastorm_tpu.benchmark.device import enable_compile_cache, require_tpu
enable_compile_cache(%(repo)r)
require_tpu()  # no TPU: this section fails, never measures the CPU
import numpy as np
import optax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from petastorm_tpu.models.transformer import (
    TransformerConfig, init_pipelined_transformer_params,
    pipelined_transformer_train_step,
)

# The production dtype (bf16) for the pipelined (shard_map+scan+ppermute)
# step is exactly what XLA:CPU cannot compile (docs/troubleshoot.md), so
# the virtual-mesh dryrun pins f32 — this smoke validates bf16 pipelining
# on REAL hardware: jit-compile + one optimizer step on a 1-stage 'pipe'
# mesh on the chip (the single-device schedule runs the identical
# scan/ppermute lowering with a trivial permutation).
mesh = Mesh(np.array(jax.devices()[:1]), ('pipe',))
config = TransformerConfig(vocab_size=64, d_model=32, n_heads=2, n_layers=2,
                           d_ff=64, max_seq_len=16)  # dtype default: bf16
with mesh:
    params = init_pipelined_transformer_params(jax.random.PRNGKey(0),
                                               config, mesh)
    optimizer = optax.adamw(1e-3)
    step = pipelined_transformer_train_step(config, optimizer, mesh,
                                            n_microbatches=2)
    tokens = jax.device_put(
        np.random.RandomState(0).randint(0, 64, (4, 16), np.int32),
        NamedSharding(mesh, P(None, None)))
    _, _, loss = step(params, optimizer.init(params), tokens)
    loss = float(loss)
assert np.isfinite(loss), loss
print(json.dumps({"loss": loss,
                  "device_kind": jax.devices()[0].device_kind}))
'''


def _measure_pp_bf16(timeout=300):
    """VERDICT r2 #7: the bf16 pipelined train step has never executed
    anywhere (XLA:CPU crashes on it; the dryrun pins f32). Compile + step
    it on the real chip."""
    code = _PP_BF16_SNIPPET % {
        'repo': os.path.dirname(os.path.abspath(__file__))}
    return _run_json_subprocess([sys.executable, '-c', code],
                                _clamp_timeout(timeout))


def _measure_lm_train(url, batch=8, seq_len=1024, warmup=4, measure=16,
                      timeout=900, overrides=None):
    """END-TO-END training throughput on a realistically-sized (~335M
    param) transformer: Parquet docs → packed batches → device staging →
    real optimizer steps on the default device (the TPU chip under the
    driver). Reports MFU and input-bound step utilization — the
    BASELINE.json metric — alongside raw throughput.

    ``overrides`` (the ``lm_train_tuned`` section): execution-lever
    config fields merged over the flagship shape — remat/loss_chunk
    only, never dimensions, so MFU stays cross-round comparable."""
    if SMOKE:
        batch, seq_len, warmup, measure = 2, 64, 1, 2
    code = _LM_TRAIN_SNIPPET % {
        'repo': os.path.dirname(os.path.abspath(__file__)), 'url': url,
        'batch': batch, 'seq': seq_len, 'warmup': warmup,
        'measure': measure, 'flagship': FLAGSHIP_LM_KW,
        'overrides': dict(overrides or {})}
    return _run_json_subprocess([sys.executable, '-c', code],
                                _clamp_timeout(timeout))


def main():
    import numpy as np

    tmp = tempfile.mkdtemp(prefix='petastorm_tpu_bench_')
    hello_url = 'file://' + tmp + '/hello_world'
    imagenet_url = 'file://' + tmp + '/imagenet_like'
    c4_url = 'file://' + tmp + '/c4_like'
    selective_url = 'file://' + tmp + '/selective'
    io_overlap_url = 'file://' + tmp + '/io_overlap'
    mix_a_url = 'file://' + tmp + '/mixture_web'
    mix_b_url = 'file://' + tmp + '/mixture_code'
    write_bench_dir = tmp + '/write_plane'
    extra = {}
    state = {
        'metric': 'hello_world_read_rate',
        'value': 0.0,
        'unit': 'samples/sec',
        'vs_baseline': 0.0,
        'extra': extra,
    }

    def emit():
        """Cumulative result after every section: a kill at ANY point
        leaves the driver's last-line parse with everything finished so
        far (VERDICT r3 #1a). Small single-line writes + flush keep the
        line intact under an outer SIGKILL.

        Two lines per emit (VERDICT r4 #1): the full cumulative dict,
        then a compact headline line that stays the LAST stdout line and
        always fits the driver's 2,000-char tail — r04's record was lost
        because the single cumulative line outgrew that tail."""
        print(json.dumps(state), flush=True)
        head_extra = {k: extra[k] for k in _HEADLINE_EXTRA_KEYS
                      if k in extra}
        head = {'metric': state['metric'], 'value': state['value'],
                'unit': state['unit'], 'vs_baseline': state['vs_baseline'],
                'headline': True, 'extra': head_extra}
        line = json.dumps(head)
        while len(line) >= _HEADLINE_MAX_CHARS and head_extra:
            head_extra.popitem()  # insertion-ordered: least important last
            line = json.dumps(head)
        assert len(line) < _HEADLINE_MAX_CHARS, len(line)
        print(line, flush=True)

    def section(name, min_seconds, fn):
        """Deadline-gated, exception-isolated benchmark section."""
        if _remaining() < min_seconds:
            extra.setdefault('skipped_sections', []).append(name)
        else:
            try:
                fn()
            except Exception as e:  # noqa: BLE001 - a section must never
                extra[name + '_error'] = repr(e)[:300]  # kill the report
        emit()

    def jax_metrics(prefix, *args, fn=_measure_jax):
        if 'tpu_unavailable' in extra:
            # the probe found no TPU: record the error, never a CPU number
            # under a device key
            result = {'error': 'no TPU: %s' % (extra['tpu_unavailable'],)}
        else:
            result = fn(*args)
        for k, v in result.items():
            if isinstance(v, float):
                # keep 4 significant digits: rates are O(10^3)+ but
                # steps/sec and losses are O(1) and would be erased by
                # fixed 1-decimal rounding
                v = float('%.4g' % v)
            extra['%s_%s' % (prefix, k)] = v

    img_state = {}

    def sec_hello_row():
        import statistics
        _build_hello_world(hello_url)
        # the PRIMARY metric: median of MEDIAN_RUNS like the other headline
        # rates (a single draw on the shared box risks recording a stall)
        rate = statistics.median(
            _measure_rows(hello_url) for _ in range(MEDIAN_RUNS))
        state['value'] = round(rate, 2)
        state['vs_baseline'] = round(rate / BASELINE_SAMPLES_PER_SEC, 3)
        # The reference's tool reports rate + RSS + CPU% together (its
        # published hello-world row carries 217 MB / 136%). Reuse this
        # repo's throughput tool with its fresh-process mode — measuring
        # RSS on THIS long-lived driver process would report harness +
        # dataset-build memory, not the reader's footprint (the same
        # reason the reference re-spawns, throughput.py:144-149). One
        # owner of the metric definition; optional: a failure never
        # touches the primary rate above.
        try:
            from petastorm_tpu.benchmark.throughput import reader_throughput
            r = reader_throughput(hello_url, warmup_cycles=WARMUP_SAMPLES,
                                  measure_cycles=MEASURE_SAMPLES,
                                  spawn_new_process=True)
            extra['hello_world_rss_mb'] = round(r.memory_rss_mb, 1)
            extra['hello_world_cpu_percent'] = round(r.cpu_percent, 1)
        except Exception as e:  # noqa: BLE001 - accounting is optional
            extra['hello_world_rss_error'] = repr(e)[:200]

    def sec_hello_batch():
        warm, meas = (100, 600) if SMOKE else (1000, 8000)
        batch_rate, _ = _measure_batch(hello_url, warm, meas)
        extra['hello_world_batch_rows_per_sec'] = round(batch_rate, 1)

    def sec_decoded_cache():
        # Decode-once serve-many (the 71% io+decode share in BENCH_r05):
        # epoch 1 fills the materialized decoded-row-group cache (Arrow
        # IPC, cache_type='decoded'), epoch 2 must serve from it — the
        # warm/cold ratio and hit share are the record. Full sweeps, no
        # warmup: a warmup pass would pre-fill the cache and erase the
        # cold number.
        from petastorm_tpu.reader import make_batch_reader
        from petastorm_tpu.telemetry import get_registry, pipeline_report
        cache_dir = os.path.join(tmp, 'decoded_cache')

        def one_pass(epochs):
            # rate over the iteration only (construction is identical on
            # both sides and would otherwise drown the 1k-row sweep)
            with make_batch_reader(hello_url, reader_pool_type='thread',
                                   num_epochs=epochs,
                                   shuffle_row_groups=False,
                                   cache_type='decoded',
                                   cache_location=cache_dir,
                                   cache_size_limit=2 * 10 ** 9) as reader:
                seen = 0
                start = time.monotonic()
                for batch in reader:
                    seen += len(batch.id)
                return seen / (time.monotonic() - start)

        # the cold pass is exactly ONE epoch: its epoch 2 would already
        # be warm; the warm pass sweeps more to amortize scheduling noise
        cold_rate = one_pass(1)
        mid = get_registry().snapshot()
        warm_rate = one_pass(1 if SMOKE else 3)
        report = pipeline_report(baseline=mid)
        extra['hello_world_cold_epoch_rows_per_sec'] = round(cold_rate, 1)
        extra['hello_world_warm_epoch_rows_per_sec'] = round(warm_rate, 1)
        extra['decoded_cache_warm_speedup'] = round(warm_rate / cold_rate, 3)
        cache = report.get('decoded_cache') or {}
        if cache:
            extra['cache_hit_share'] = cache['hit_rate']
            extra['decoded_cache_warm_verdict'] = cache['verdict']

    def sec_selective_read():
        """Query-shaped reads (ISSUE 12): a range predicate at ~1%/10%/50%
        selectivity over the sorted-id jpeg dataset, through the
        pruned + late-materialized read. The rate is the EFFECTIVE scan
        rate — dataset rows / epoch wall — because a selective read's
        value is how fast it disposes of the rows it does NOT want;
        rowgroups pruned is recorded so the rate is attributable to
        pruning, not caching."""
        from petastorm_tpu import pushdown
        from petastorm_tpu.filters import FiltersPredicate
        from petastorm_tpu.reader import make_batch_reader
        from petastorm_tpu.telemetry import get_registry

        _build_selective(selective_url)

        def one_epoch(cutoff):
            start = time.monotonic()
            with make_batch_reader(
                    selective_url, reader_pool_type='thread',
                    shuffle_row_groups=False,
                    predicate=FiltersPredicate(
                        [('id', '<', cutoff)])) as reader:
                delivered = sum(len(b.id) for b in reader)
            return time.monotonic() - start, delivered

        registry = get_registry()
        pruned_before = registry.counter_value(pushdown.ROWGROUPS_PRUNED)
        late_before = registry.counter_value(pushdown.LATE_MATERIALIZED_ROWS)
        for label, fraction in (('1pct', 0.01), ('10pct', 0.10),
                                ('50pct', 0.50)):
            cutoff = max(1, int(SELECTIVE_ROWS * fraction))
            # warm the page cache + footer memo so the timed epoch reads
            # the steady-state path, not first-touch I/O
            one_epoch(cutoff)
            seconds, delivered = one_epoch(cutoff)
            assert delivered == cutoff, (delivered, cutoff)
            extra['selective_read_%s_rows_per_sec' % label] = round(
                SELECTIVE_ROWS / seconds, 1)
        extra['selective_read_rowgroups_pruned'] = int(
            registry.counter_value(pushdown.ROWGROUPS_PRUNED)
            - pruned_before)
        # delta over this section only (warm-ups included), like the
        # pruned count — the absolute counter would absorb any earlier
        # predicate reader in the process
        extra['selective_read_late_materialized_rows'] = int(
            registry.counter_value(pushdown.LATE_MATERIALIZED_ROWS)
            - late_before)

    def sec_io_overlap():
        """Wire-speed I/O plane (ISSUE 15): cold-read rows/s with
        coalesced readahead vs the PETASTORM_TPU_READAHEAD=0 blocking
        oracle, both behind the same injected-latency filesystem
        (_SlowFS: every read request pays one fixed round trip — the
        remote/cold-storage shape the plane exists to hide). The oracle
        serializes per-column-chunk requests inside each worker; the
        plane fetches coalesced ranges depth-ahead on its own threads,
        so storage latency overlaps decode. Parity is asserted, not
        assumed: both runs must deliver the identical row multiset."""
        import fsspec

        from petastorm_tpu import readahead
        from petastorm_tpu.reader import make_batch_reader
        from petastorm_tpu import telemetry
        from petastorm_tpu.telemetry import get_registry

        _build_io_overlap(io_overlap_url)
        base_fs, _ = fsspec.core.url_to_fs(io_overlap_url)

        section_env = {'PETASTORM_TPU_READAHEAD_THREADS': '4',
                       'PETASTORM_TPU_READAHEAD_DEPTH': '8'}

        def one_epoch(oracle):
            env = dict(section_env)
            # BOTH sides pin the knob: an ambient =0 in the operator's
            # environment must not silently measure oracle-vs-oracle and
            # record a phantom ~1.0 "speedup"
            env['PETASTORM_TPU_READAHEAD'] = '0' if oracle else '1'
            saved = {k: os.environ.get(k) for k in env}
            os.environ.update(env)
            telemetry.refresh()
            try:
                fs = _SlowFS(base_fs, IO_OVERLAP_READ_DELAY_S)
                with make_batch_reader(io_overlap_url,
                                       reader_pool_type='thread',
                                       workers_count=2,
                                       shuffle_row_groups=False,
                                       filesystem=fs) as reader:
                    # rate over the DATA plane only: construction
                    # (row-group enumeration footers) is identical on
                    # both sides and would only compress the contrast
                    start = time.monotonic()
                    ids = sorted(int(i) for b in reader for i in b.id)
                    return time.monotonic() - start, ids
            finally:
                for k, v in saved.items():
                    if v is None:
                        os.environ.pop(k, None)
                    else:
                        os.environ[k] = v
                telemetry.refresh()

        registry = get_registry()
        before = {name: registry.counter_value(name) for name in
                  (readahead.READAHEAD_HITS, readahead.READAHEAD_MISSES,
                   readahead.READAHEAD_BYTES,
                   readahead.READAHEAD_COALESCED_READS)}
        ahead_s, ahead_ids = one_epoch(oracle=False)
        delta = {name: registry.counter_value(name) - before[name]
                 for name in before}
        oracle_s, oracle_ids = one_epoch(oracle=True)
        assert ahead_ids == oracle_ids, 'io_overlap parity broke'
        extra['io_overlap_parity'] = True
        extra['io_overlap_readahead_rows_per_sec'] = round(
            IO_OVERLAP_ROWS / ahead_s, 1)
        extra['io_overlap_oracle_rows_per_sec'] = round(
            IO_OVERLAP_ROWS / oracle_s, 1)
        extra['io_overlap_speedup'] = round(oracle_s / ahead_s, 3)
        served = (delta[readahead.READAHEAD_HITS]
                  + delta[readahead.READAHEAD_MISSES])
        assert served > 0, 'io_overlap: readahead plane never engaged'
        extra['io_overlap_hit_share'] = round(
            delta[readahead.READAHEAD_HITS] / served, 4)
        reads = delta[readahead.READAHEAD_COALESCED_READS]
        extra['io_overlap_mean_coalesced_kb'] = round(
            delta[readahead.READAHEAD_BYTES] / reads / 1024, 2) if reads \
            else 0.0

    def sec_mixture_stream():
        """Streaming mixture engine (ISSUE 17): two token corpora mixed
        3:1 by the arithmetic interleave and packed to fixed
        MIXTURE_SEQ_LEN rows — packed-token throughput, fill ratio, the
        interleave's realized-ratio deviation against an RNG-draw
        baseline, and the readahead hit share on the mixture path, with
        the PETASTORM_TPU_READAHEAD=0 pass as the exact-parity oracle
        (identical packed rows, bit for bit)."""
        from petastorm_tpu import readahead, telemetry
        from petastorm_tpu.mixture import (InterleaveSchedule,
                                           MixtureSource, MixtureSpec,
                                           MixtureStream,
                                           realized_deviation)
        from petastorm_tpu.telemetry import get_registry

        _build_mixture_source(mix_a_url, MIXTURE_DOCS_A, seed=1)
        _build_mixture_source(mix_b_url, MIXTURE_DOCS_B, seed=2)
        weights = [3, 1]

        def spec():
            return MixtureSpec(
                [MixtureSource('web', weights[0], url=mix_a_url),
                 MixtureSource('code', weights[1], url=mix_b_url)],
                seed=0, seq_len=MIXTURE_SEQ_LEN)

        def one_pass(oracle):
            env = {'PETASTORM_TPU_READAHEAD': '0' if oracle else '1',
                   'PETASTORM_TPU_READAHEAD_DEPTH': '8',
                   'PETASTORM_TPU_READAHEAD_THREADS': '4'}
            saved = {k: os.environ.get(k) for k in env}
            os.environ.update(env)
            telemetry.refresh()
            try:
                stream = MixtureStream(spec(), reader_pool_type='thread',
                                       workers_count=2)
                try:
                    start = time.monotonic()
                    rows = list(stream)
                    elapsed = time.monotonic() - start
                    return elapsed, rows, stream.pack_stats
                finally:
                    stream.stop()
                    stream.join()
            finally:
                for k, v in saved.items():
                    if v is None:
                        os.environ.pop(k, None)
                    else:
                        os.environ[k] = v
                telemetry.refresh()

        registry = get_registry()
        before = {name: registry.counter_value(name) for name in
                  (readahead.READAHEAD_HITS, readahead.READAHEAD_MISSES)}
        ahead_s, ahead_rows, stats = one_pass(oracle=False)
        delta = {name: registry.counter_value(name) - before[name]
                 for name in before}
        oracle_s, oracle_rows, _ = one_pass(oracle=True)
        assert len(ahead_rows) == len(oracle_rows) and all(
            np.array_equal(a[k], b[k])
            for a, b in zip(ahead_rows, oracle_rows)
            for k in ('tokens', 'loss_mask', 'segment_ids')), \
            'mixture_stream readahead parity broke'
        extra['mixture_parity'] = True
        extra['mixture_packed_tokens_per_sec'] = round(
            stats['tokens'] / ahead_s, 1)
        extra['mixture_oracle_packed_tokens_per_sec'] = round(
            stats['tokens'] / oracle_s, 1)
        extra['mixture_fill_ratio'] = round(stats['fill_ratio'], 4)
        extra['mixture_rows'] = stats['rows']
        extra['mixture_split_doc_share'] = round(
            stats['split_docs'] / max(1, stats['docs']), 4)
        served = (delta[readahead.READAHEAD_HITS]
                  + delta[readahead.READAHEAD_MISSES])
        if served:
            extra['mixture_readahead_hit_share'] = round(
                delta[readahead.READAHEAD_HITS] / served, 4)
        # interleave-vs-RNG divergence: worst realized-ratio deviation
        # over 2k positions — the arithmetic schedule holds a hard O(1)
        # bound where RNG draws wander O(sqrt(n))
        k = 2000
        order = InterleaveSchedule.order(weights, seed=0, start=0, k=k)
        extra['mixture_interleave_deviation'] = round(
            realized_deviation(order, weights), 3)
        rng = np.random.RandomState(0)
        share = weights[0] / float(sum(weights))
        rng_order = [0 if draw < share else 1
                     for draw in rng.random_sample(k)]
        extra['mixture_rng_deviation'] = round(
            realized_deviation(rng_order, weights), 3)

    def sec_write_throughput():
        """Distributed write plane (ISSUE 18): commit-to-commit rows/s
        and MB/s for the local (pool=None, shards run inline) and fleet
        (ServicePool subprocess workers) backends over the same row
        stream — backend byte-parity asserted via the committed
        manifests, which carry no timestamps. Then the compaction
        story: small appended generations read before and after
        compact_dataset folds them, for the read-speed delta the
        re-shard service exists to buy."""
        from petastorm_tpu.reader import make_batch_reader
        from petastorm_tpu.service.service_pool import ServicePool
        from petastorm_tpu.write import compact_dataset
        from petastorm_tpu.write import manifest as wmanifest
        from petastorm_tpu.write import write_dataset_distributed

        schema = _write_bench_schema()
        rows = [{'id': i, 'payload': 'payload-%06d|' % i * 8}
                for i in range(WRITE_BENCH_ROWS)]

        def one_write(name, pool):
            url = 'file://' + write_bench_dir + '/' + name
            start = time.monotonic()
            writer = write_dataset_distributed(
                url, schema, rows, sort_by='id',
                shard_rows=WRITE_BENCH_SHARD_ROWS, pool=pool)
            elapsed = time.monotonic() - start
            nbytes = sum(e['bytes'] for e in writer.manifest['files'])
            return elapsed, nbytes, writer

        local_s, local_bytes, w_local = one_write('local', None)
        fleet_s, fleet_bytes, w_fleet = one_write(
            'fleet', ServicePool(spawn_local_workers=4,
                                 heartbeat_interval_s=0.2,
                                 liveness_timeout_s=2.0,
                                 connect_timeout_s=60,
                                 no_workers_timeout_s=30))
        assert wmanifest.dumps(w_local.manifest) == \
            wmanifest.dumps(w_fleet.manifest), 'write backend parity broke'
        extra['write_parity'] = True
        extra['write_rows_per_sec'] = round(WRITE_BENCH_ROWS / local_s, 1)
        extra['write_mb_per_sec'] = round(
            local_bytes / local_s / (1024 * 1024), 2)
        extra['write_fleet_rows_per_sec'] = round(
            WRITE_BENCH_ROWS / fleet_s, 1)
        extra['write_fleet_mb_per_sec'] = round(
            fleet_bytes / fleet_s / (1024 * 1024), 2)
        check = w_local.last_self_check
        if check:
            extra['write_selfcheck_prune_share'] = round(
                check['predicted_prune_share'], 4)
            extra['write_selfcheck_fits_window_share'] = round(
                check['coalesce']['fits_window_share'], 4)

        # compaction before/after: many small generations vs the fold
        compact_url = 'file://' + write_bench_dir + '/compact'
        for gen in range(WRITE_COMPACT_GENS):
            write_dataset_distributed(
                compact_url, schema,
                [{'id': i, 'payload': 'payload-%06d|' % i * 8}
                 for i in range(gen * WRITE_COMPACT_GEN_ROWS,
                                (gen + 1) * WRITE_COMPACT_GEN_ROWS)],
                sort_by='id', shard_rows=WRITE_COMPACT_GEN_ROWS // 4,
                append=(gen > 0))

        def read_s():
            best = None
            for _ in range(3):
                start = time.monotonic()
                with make_batch_reader(compact_url,
                                       shuffle_row_groups=False) as r:
                    total = sum(len(b.id) for b in r)
                elapsed = time.monotonic() - start
                assert total == WRITE_COMPACT_GENS * WRITE_COMPACT_GEN_ROWS
                best = elapsed if best is None else min(best, elapsed)
            return best

        before_files = len(wmanifest.load(
            *_write_bench_fs(compact_url))['files'])
        before_s = read_s()
        compacted = compact_dataset(compact_url, minimum=2)
        assert compacted is not None, 'write bench compaction planned nothing'
        after_s = read_s()
        extra['write_compact_files_before'] = before_files
        extra['write_compact_files_after'] = len(compacted['files'])
        extra['write_compact_read_speedup'] = round(before_s / after_s, 3)

    def sec_critpath():
        """Critical-path engine (ISSUE 19): a fully-traced hello-world
        read, then the sweep-line analysis over its flight recorder —
        the analysis' wall-clock share of the traced read (the <2%
        overhead budget the perf-marked test also gates), the
        critical-path bottleneck stage and the top what-if projection."""
        from petastorm_tpu import telemetry
        from petastorm_tpu.reader import make_reader
        from petastorm_tpu.telemetry import critpath, recorder

        if not os.path.isdir(tmp + '/hello_world'):
            _build_hello_world(hello_url)
        env = {'PETASTORM_TPU_TRACE': '1',
               'PETASTORM_TPU_TRACE_SAMPLE': '1'}
        saved = {k: os.environ.get(k) for k in env}
        os.environ.update(env)
        telemetry.refresh()
        try:
            start = time.monotonic()
            with make_reader(hello_url, reader_pool_type='thread',
                             workers_count=2, num_epochs=1,
                             shuffle_row_groups=False) as reader:
                rows = sum(1 for _ in reader)
            traced_s = time.monotonic() - start
            assert rows == HELLO_ROWS, rows
            start = time.monotonic()
            report = critpath.analyze()
            analyze_s = time.monotonic() - start
        finally:
            for k, v in saved.items():
                if v is None:
                    os.environ.pop(k, None)
                else:
                    os.environ[k] = v
            telemetry.refresh()
            recorder.reset_recorder()
        assert report is not None, 'traced read recorded no stage events'
        extra['critpath_overhead_share'] = round(analyze_s / traced_s, 4)
        extra['critpath_bottleneck'] = report['bottleneck']
        extra['critpath_events'] = report['events']
        if report['what_if']:
            top = report['what_if'][0]
            extra['critpath_top_whatif'] = '%s => %+.1f%%' % (
                top['scenario'], top['epoch_delta_pct'])

    def sec_service():
        # Standing-service HA record (docs/service.md, "High
        # availability"): SIGKILL a subprocess primary mid-job with a
        # warm in-process standby attached and measure the delivery
        # blackout — kill to first post-promotion row at the client.
        # Then a second job with the identical decode fingerprint binds
        # against the promoted daemon's warm fleet for the
        # placement-hit share.
        import signal as _signal

        from petastorm_tpu.service.daemon import DaemonClientPool
        from petastorm_tpu.service.protocol import free_tcp_port
        from petastorm_tpu.service.standby import StandbyDaemon
        from petastorm_tpu.workers.worker_base import WorkerBase

        class _Echo(WorkerBase):  # shipped to the workers via dill
            def process(self, value):
                self.publish_func(value)

        repo = os.path.dirname(os.path.abspath(__file__))
        env = dict(os.environ, PYTHONPATH=repo, JAX_PLATFORMS='cpu')
        endpoint = 'tcp://127.0.0.1:%d' % free_tcp_port()
        procs = [subprocess.Popen(
            [sys.executable, '-m', 'petastorm_tpu.service',
             '--endpoint', endpoint, '--no-supervisor',
             '--heartbeat-interval', '0.2'], env=env)]
        procs += [subprocess.Popen(
            [sys.executable, '-m', 'petastorm_tpu.service.worker_server',
             '--endpoint', endpoint, '--heartbeat-interval', '0.2',
             '--ack-timeout', '2', '--parent-pid', str(os.getpid())],
            env=env) for _ in range(2)]
        standby = None
        pools = []
        try:
            pool = DaemonClientPool(endpoint, name='bench-ha',
                                    heartbeat_interval_s=0.2,
                                    ack_timeout_s=1.5,
                                    connect_timeout_s=60)
            pools.append(pool)
            pool.start(_Echo, worker_args={'placement_group': 'bench-ha'})
            standby = StandbyDaemon(endpoint, sync_interval_s=0.2,
                                    lapse_s=1.0, supervise=False,
                                    heartbeat_interval_s=0.2)
            standby.start()
            n = 50 if SMOKE else 200
            for i in range(n):
                pool.ventilate(i)
            got = [pool.get_results(timeout=60) for _ in range(n // 4)]
            t_kill = time.monotonic()
            os.kill(procs[0].pid, _signal.SIGKILL)
            procs[0].wait()
            # blackout at the SERVICE plane: kill → the first row
            # delivered through the promoted incarnation (client
            # re-registration and re-submission included) — the local
            # results buffer can't fake this number
            standby.wait_promoted(60)
            deadline = time.monotonic() + 60
            while time.monotonic() < deadline:
                promoted = standby.daemon
                if promoted is not None and promoted.dispatcher.health()[
                        'items_completed'] > 0:
                    break
                time.sleep(0.02)
            extra['service_failover_blackout_s'] = round(
                time.monotonic() - t_kill, 2)
            while len(got) < n:
                got.append(pool.get_results(timeout=60))
            extra['service_failover_exact'] = sorted(got) == list(range(n))
            pools.remove(pool)
            pool.stop()
            pool.join()
            second = DaemonClientPool(endpoint, name='bench-warm',
                                      heartbeat_interval_s=0.2,
                                      ack_timeout_s=1.5,
                                      connect_timeout_s=60)
            pools.append(second)
            second.start(_Echo,
                         worker_args={'placement_group': 'bench-ha'})
            for i in range(50):
                second.ventilate(i)
            for _ in range(50):
                second.get_results(timeout=60)
            health = standby.health()
            placed = (health.get('placement_hits', 0)
                      + health.get('placement_misses', 0))
            if placed:
                extra['service_placement_hit_share'] = round(
                    health['placement_hits'] / placed, 3)
        finally:
            for p in pools:
                p.stop()
                p.join()
            if standby is not None:
                standby.stop()
            for p in procs:
                if p.poll() is None:
                    p.terminate()
            for p in procs:
                try:
                    p.wait(timeout=10)
                except subprocess.TimeoutExpired:
                    p.kill()
                    p.wait()

    def sec_peer_cache():
        # Fleet cache tier record (docs/service.md, "Fleet cache
        # tier"): two worker servers with DISJOINT host-local cache
        # directories over one hot dataset. Epoch 1 decodes each
        # row-group exactly once fleet-wide (cold fill with an injected
        # decode cost). Epoch 2 is a fresh job over the same keys: the
        # decoding host serves its own items from its disk tier and the
        # OTHER host's items arrive by peer fetch — so the headline is
        # the share of warm-epoch items served without a fresh decode.
        from petastorm_tpu.materialized_cache import (
            MaterializedRowGroupCache,
        )
        from petastorm_tpu.service.daemon import DaemonClientPool
        from petastorm_tpu.service.protocol import free_tcp_port
        from petastorm_tpu.workers.worker_base import WorkerBase

        class _FleetDecode(WorkerBase):  # shipped to the workers via dill
            def process(self, item):
                import time as _time

                import numpy as _np

                from petastorm_tpu.arrow_worker import ColumnBatch
                decoded = []

                def fill():
                    decoded.append(True)
                    _time.sleep(self.args['decode_s'])
                    cols = {'v': _np.full(256, item, dtype=_np.int64)}
                    return ColumnBatch(cols, 256)

                batch = self.args['cache'].get(('bench-peer', item), fill)
                self.publish_func((item, bool(decoded),
                                   int(batch.columns['v'][0])))

        repo = os.path.dirname(os.path.abspath(__file__))
        env = dict(os.environ, PYTHONPATH=repo, JAX_PLATFORMS='cpu')
        endpoint = 'tcp://127.0.0.1:%d' % free_tcp_port()
        procs = [subprocess.Popen(
            [sys.executable, '-m', 'petastorm_tpu.service',
             '--endpoint', endpoint, '--no-supervisor',
             '--heartbeat-interval', '0.2'], env=env)]
        procs += [subprocess.Popen(
            [sys.executable, '-m', 'petastorm_tpu.service.worker_server',
             '--endpoint', endpoint, '--heartbeat-interval', '0.2',
             '--ack-timeout', '2', '--parent-pid', str(os.getpid()),
             '--cache-dir', os.path.join(tmp, 'peer_host%d' % i)],
            env=env) for i in range(2)]
        cache = MaterializedRowGroupCache(
            os.path.join(tmp, 'peer_seed'), disk_limit_bytes=1 << 30,
            mem_limit_bytes=0)
        n = 24 if SMOKE else 48
        pools = []

        def one_epoch(name):
            pool = DaemonClientPool(endpoint, name=name,
                                    heartbeat_interval_s=0.2,
                                    ack_timeout_s=2,
                                    connect_timeout_s=60)
            pools.append(pool)
            pool.start(_FleetDecode,
                       worker_args={'cache': cache, 'decode_s': 0.02,
                                    'placement_group': 'bench-peer'})
            start = time.monotonic()
            for i in range(n):
                pool.ventilate(i)
            rows = [pool.get_results(timeout=60) for _ in range(n)]
            elapsed = time.monotonic() - start
            pools.remove(pool)
            pool.stop()
            pool.join()
            return rows, elapsed

        try:
            cold_rows, cold_s = one_epoch('bench-peer-cold')
            warm_rows, warm_s = one_epoch('bench-peer-warm')
            decodes = sum(1 for _, was_decoded, _v in warm_rows
                          if was_decoded)
            extra['peer_hit_share'] = round((n - decodes) / n, 3)
            extra['peer_cache_warm_decodes'] = decodes
            extra['peer_cache_cold_epoch_s'] = round(cold_s, 2)
            extra['peer_cache_warm_epoch_s'] = round(warm_s, 2)
            if warm_s > 0:
                extra['peer_cache_warm_speedup'] = round(cold_s / warm_s, 2)
            extra['peer_cache_exact'] = (
                sorted(v for _i, _d, v in warm_rows)
                == sorted(v for _i, _d, v in cold_rows)
                == list(range(n)))
        finally:
            for p in pools:
                p.stop()
                p.join()
            for p in procs:
                if p.poll() is None:
                    p.terminate()
            for p in procs:
                try:
                    p.wait(timeout=10)
                except subprocess.TimeoutExpired:
                    p.kill()
                    p.wait()

    def sec_lm_tokens():
        _build_c4_like(c4_url)
        extra['lm_packed_tokens_per_sec'] = round(_measure_lm_tokens(c4_url),
                                                  1)

    def sec_imagenet():
        _build_imagenet_like(imagenet_url)
        img_bytes = int(np.prod(IMAGENET_SHAPE))
        # median of MEDIAN_RUNS: the shared box is noisy (single runs
        # swing +-10%) and this is the north-star rate
        img_runs = sorted(
            (_measure_batch(imagenet_url, IMAGENET_ROWS // 2,
                            IMAGENET_ROWS * 4, bytes_per_row=img_bytes)
             for _ in range(MEDIAN_RUNS)), key=lambda pair: pair[0])
        img_rate, img_mb = img_runs[MEDIAN_RUNS // 2]
        img_state['rate'] = img_rate
        extra['imagenet_batch_rows_per_sec'] = round(img_rate, 1)
        extra['imagenet_decoded_mb_per_sec'] = round(img_mb, 1)

    def sec_imagenet_python_decode():
        """Native C decoders OFF (pure-Python/cv2 fallback): the native
        layer's measured win on the same bytes (VERDICT r3 #8). The
        toggle is live per-call, so an in-process re-run measures the
        fallback path; the default (native on when built) is what the
        main imagenet section measured. Only a real comparison is
        reported: if the main run itself used the fallback (no built
        jpeg extension, or an ambient kill-switch) a 'speedup' would be
        ~1.0 noise posing as the native layer's win."""
        from petastorm_tpu.native import get_jpeg_module, native_disabled
        if native_disabled():
            extra['native_decode'] = 'disabled-by-env'
            return
        if get_jpeg_module() is None:
            extra['native_decode'] = 'unavailable'
            return
        img_bytes = int(np.prod(IMAGENET_SHAPE))
        saved = os.environ.get('PETASTORM_TPU_NATIVE')
        os.environ['PETASTORM_TPU_NATIVE'] = '0'
        try:
            py_rate, py_mb = _measure_batch(
                imagenet_url, IMAGENET_ROWS // 2, IMAGENET_ROWS * 4,
                bytes_per_row=img_bytes)
        finally:
            if saved is None:
                del os.environ['PETASTORM_TPU_NATIVE']
            else:
                os.environ['PETASTORM_TPU_NATIVE'] = saved
        extra['imagenet_python_decode_rows_per_sec'] = round(py_rate, 1)
        extra['imagenet_python_decode_mb_per_sec'] = round(py_mb, 1)
        if img_state.get('rate'):
            extra['native_decode_speedup'] = round(
                img_state['rate'] / py_rate, 3)

    def sec_fused_decode():
        """ISSUE 9's decode-granularity ladder at the 224² north-star
        shape, on the SAME jpeg bytes the imagenet sections read: python
        per-cell (cv2, the oracle), native per-cell (one C call per
        image — the old dispatch granularity whose win capped at ~1.15×),
        and native batched (ONE C call for the whole column, cells fanned
        across the internal pthread pool). The fused-into-slot rung is
        loader-level and lives in the imagenet_jax section — its
        ``fused_decode_mode`` key says whether fusion engaged there."""
        import glob
        import statistics

        import pyarrow.parquet as pq

        from petastorm_tpu.codecs import (
            CompressedImageCodec, image_decoder_threads,
        )
        from petastorm_tpu.native import get_jpeg_module, native_disabled
        from petastorm_tpu.unischema import UnischemaField

        root = imagenet_url[len('file://'):]
        cells = []
        for path in sorted(glob.glob(root + '/*.parquet')):
            cells.extend(
                bytes(c) for c in pq.read_table(path, columns=['image'])
                .column('image').to_pylist())
        cells = cells[:64 if SMOKE else 256]
        n = len(cells)
        codec = CompressedImageCodec('jpeg', quality=90)
        field = UnischemaField('image', np.uint8, IMAGENET_SHAPE, codec,
                               False)
        out = np.empty((n,) + IMAGENET_SHAPE, np.uint8)

        def rate(fn, reps=1 if SMOKE else 3):
            fn()  # warm (page-in, mode calibration, pool spin-up)
            samples = []
            for _ in range(reps):
                start = time.monotonic()
                fn()
                samples.append(time.monotonic() - start)
            return n / statistics.median(samples)

        py_rate = rate(lambda: [codec.decode(field, c) for c in cells])
        extra['fused_decode_per_image_rows_per_sec'] = round(py_rate, 1)
        if native_disabled() or get_jpeg_module() is None:
            extra['fused_decode_native'] = 'unavailable'
            return
        decode_fn = get_jpeg_module().decode_jpeg_batch
        # BOTH native rungs decode with the SAME chroma-upsampling mode —
        # the one the codec's calibration picked (what decode_batch below
        # uses) — so the batched-vs-per-cell ratio measures the batching
        # win alone, never a mode delta
        from petastorm_tpu.codecs import _jpeg_upsampling_mode
        mode = _jpeg_upsampling_mode(decode_fn, cells, IMAGENET_SHAPE)
        extra['fused_decode_jpeg_mode'] = mode

        def native_per_cell():
            for i in range(n):
                decode_fn(cells[i:i + 1], out[i:i + 1], mode, 1)

        cell_rate = rate(native_per_cell)
        batched_rate = rate(
            lambda: codec.decode_batch(field, cells, out=out))
        extra['fused_decode_native_per_cell_rows_per_sec'] = \
            round(cell_rate, 1)
        extra['fused_decode_batched_rows_per_sec'] = round(batched_rate, 1)
        extra['fused_decode_batched_vs_per_cell'] = \
            round(batched_rate / cell_rate, 3)
        extra['fused_decode_native_threads'] = image_decoder_threads()

    def sec_tfdata():
        # North star (BASELINE.json): ratio vs a tf.data+TFRecord pipeline
        # decoding the SAME jpeg bytes on the same machine. Target >= 0.9.
        tfrecord_path, build_error = _build_tfrecord(imagenet_url)
        if build_error:
            extra['tfdata_imagenet_error'] = build_error
            return
        runs = [_measure_tfdata(tfrecord_path, IMAGENET_ROWS // 2,
                                IMAGENET_ROWS * 4)
                for _ in range(TFDATA_RUNS)]
        os.unlink(tfrecord_path)
        ok_rates = sorted(r['rows_per_sec'] for r in runs
                          if 'rows_per_sec' in r)
        if ok_rates:
            import statistics
            tf_rate = statistics.median(ok_rates)
            extra['tfdata_imagenet_rows_per_sec'] = round(tf_rate, 1)
            if img_state.get('rate'):
                extra['vs_tfdata'] = round(img_state['rate'] / tf_rate, 3)
        else:
            extra['tfdata_imagenet_error'] = runs[-1].get('error', 'unknown')

    def sec_jax_hello():
        warm, meas = (128, 1024) if SMOKE else (1024, 8192)
        jax_metrics('hello_world_jax', hello_url, 256, warm, meas,
                    ['^id$', '^array_4d$', '^image1$'])

    def sec_jax_imagenet():
        jax_metrics('imagenet_jax', imagenet_url, IMAGENET_JAX_BATCH,
                    IMAGENET_ROWS // 2, IMAGENET_ROWS * 3, ['^image$'])
        # headline-named copy: the BENCH_r0x record must say whether the
        # imagenet H2D number rode the fused decode path or a fallback
        if 'imagenet_jax_fused_decode_mode' in extra:
            extra['fused_decode_mode'] = \
                extra['imagenet_jax_fused_decode_mode']
        # Attribution marker: when even a RAW device_put tight loop cannot
        # reach 1 GB/s, the H2D ceiling is the link, not the staging
        # layer — h2d_efficiency (loader/raw) close to or above 1.0 in the
        # same run confirms staging adds nothing on top.
        raw_gb = extra.get('imagenet_jax_raw_h2d_gb_per_sec')
        if raw_gb is not None and raw_gb < 1.0:
            extra['h2d_link_degraded'] = True

    def sec_jax_dummy():
        # VERDICT r4 #4: the same loader consumer over a DummyBatchReader
        # source (zero I/O, zero decode) decomposes the imagenet_jax
        # sec/row into framework-staging vs I/O+decode vs H2D-link. The
        # raw-H2D calibration from sec_jax_imagenet provides the
        # link-only term; shares are clamped at 0 (on a degraded link
        # the loader overlaps H2D better than the raw loop, so the
        # staging term can measure negative — meaning it adds nothing).
        warm, meas = (128, 512) if SMOKE else (IMAGENET_ROWS // 2,
                                               IMAGENET_ROWS * 3)
        jax_metrics('imagenet_jax_dummy', IMAGENET_JAX_BATCH, warm, meas,
                    IMAGENET_SHAPE, fn=_measure_jax_dummy)
        shares = compute_staging_shares(
            extra.get('imagenet_jax_rows_per_sec'),
            extra.get('imagenet_jax_dummy_rows_per_sec'),
            extra.get('imagenet_jax_raw_h2d_mb_per_sec'),
            extra.get('imagenet_jax_staged_bytes_per_batch'),
            IMAGENET_JAX_BATCH)
        if shares:
            extra.update(shares)

    def sec_sharded_staging():
        # Shard-aware staging engine (ISSUE 14): global jax.Array batches
        # onto a data mesh over every visible device — aggregate GB/s,
        # per-host overlap share, staged-vs-raw h2d efficiency (chasing
        # the r05 0.035 on the staged path; the dummy source isolates
        # the staging layer from decode), and the autotuner's decision
        # log, so rounds are attributable when a knob moved mid-run.
        warm, meas = (128, 512) if SMOKE else (256, 3072)
        jax_metrics('sharded_staging', IMAGENET_JAX_BATCH, warm, meas,
                    IMAGENET_SHAPE, fn=_measure_sharded_staging)

    def sec_vit_train():
        # image-family silicon throughput (VERDICT r4 #7): ViT-Base-dims
        # train steps from in-HBM batches — steps/s, images/s, MFU
        jax_metrics('vit_train', fn=_measure_vit_train)

    def sec_lm_train():
        # end-to-end TRAINING throughput on the default device: Parquet →
        # packed batches → H2D → real transformer optimizer steps. Runs
        # immediately after the probe, so the chip's health is at most
        # one section old when the most valuable capture starts.
        jax_metrics('lm_train', c4_url, fn=_measure_lm_train)

    def sec_mfu_breakdown():
        # VERDICT r4 #3: where the non-MXU half of the flagship step
        # goes. Part-times from the subprocess + lm_train's own step
        # time and matmul calibration combine into shares of the COMPUTE
        # step (input wait reported separately from input_bound_util).
        jax_metrics('mfu_parts', fn=_measure_mfu_breakdown)
        shares = compute_mfu_breakdown(
            extra.get('lm_train_steps_per_sec'),
            extra.get('lm_train_input_bound_util'),
            extra.get('lm_train_measured_matmul_tflops'),
            {'attn_measured': extra.get('mfu_parts_attn_total_ms'),
             'norms_measured': extra.get('mfu_parts_norm_total_ms'),
             'loss_head_measured': extra.get('mfu_parts_loss_head_ms')})
        if shares:
            extra['lm_train_mfu_breakdown'] = shares

    def sec_lm_train_tuned():
        # VERDICT r4 #3: a separately-keyed tuned variant — flagship
        # DIMENSIONS untouched (cross-round MFU comparability lives in
        # lm_train); only execution levers move here. remat=True frees
        # the activation HBM that capped the flagship at batch 8, and the
        # larger per-core batch amortizes the non-MXU per-step work the
        # breakdown section quantifies.
        # bounded timeout: a pathological compile here must not starve
        # the sections that follow (lm_train's own 900s is for the single
        # most valuable capture; this is the experiment, not the record).
        # Ladder: if batch 16 under remat doesn't fit/compile on this
        # chip, batch 12 still tests the amortization hypothesis — an
        # error should cost one rung, not the whole experiment.
        def tuned(url):
            result = {'error': 'no tuned rung ran'}
            for b in (16, 12):
                result = _measure_lm_train(url, batch=b,
                                           overrides=dict(remat=True),
                                           timeout=420)
                if 'error' not in result:
                    result['batch'] = b
                    return result
                # setdefault: the CPU-fallback re-invocation must not
                # overwrite the TPU rung's diagnostic (OOM vs wedge)
                extra.setdefault('lm_train_tuned_b%d_error' % b,
                                 result['error'][:200])
                if 'timeout' in result['error']:
                    # a timed-out rung means a dead/wedged link, not a
                    # too-big batch — a second rung would re-burn 420s
                    # against it and starve the sections that follow
                    break
            return result

        jax_metrics('lm_train_tuned', c4_url, fn=tuned)

    def sec_lm_decode():
        # inference: KV-cache greedy decode rate on the same model family
        jax_metrics('lm_decode', fn=_measure_lm_decode)

    def sec_pp_bf16():
        # bf16 pipelined train step smoke on the real chip
        jax_metrics('pp_bf16', fn=_measure_pp_bf16)

    try:
        # Cumulative emission means finished sections are never lost, so
        # the order IS the value ranking under budget pressure: the cheap
        # host sections that secure the primary metric (and build the
        # datasets later sections read), then the probe, then lm_train
        # FIRST among the expensive sections — the MFU / input-bound-util
        # capture is the single most valuable device number (VERDICT r3
        # #2) and must not queue behind tf.data subprocess startups that
        # can eat minutes each on a loaded box. tfdata (the north-star
        # ratio) follows, then the H2D story, decode, pp smoke.
        section('hello_row', 10, sec_hello_row)
        section('hello_batch', 5, sec_hello_batch)
        section('decoded_cache', 10, sec_decoded_cache)
        section('selective_read', 15, sec_selective_read)
        section('io_overlap', 10, sec_io_overlap)
        section('mixture_stream', 15, sec_mixture_stream)
        section('write_throughput', 15, sec_write_throughput)
        section('critpath', 10, sec_critpath)
        section('service', 20, sec_service)
        section('peer_cache', 15, sec_peer_cache)
        section('lm_tokens', 10, sec_lm_tokens)
        section('imagenet', 20, sec_imagenet)
        section('probe', 20, lambda: _probe_tpu(extra))
        section('lm_train', 60, sec_lm_train)
        section('tfdata', 30, sec_tfdata)
        section('imagenet_python_decode', 10, sec_imagenet_python_decode)
        section('fused_decode', 15, sec_fused_decode)
        section('jax_imagenet', 30, sec_jax_imagenet)
        # proven captures (decode/GQA) run before the round-5 sections
        # (vit/tuned/breakdown) — a new section's worst-case compile must
        # never squeeze a number the ledger already tracks
        section('jax_dummy', 20, sec_jax_dummy)
        section('sharded_staging', 25, sec_sharded_staging)
        section('lm_decode', 45, sec_lm_decode)
        section('vit_train', 45, sec_vit_train)
        section('lm_train_tuned', 60, sec_lm_train_tuned)
        section('mfu_breakdown', 60, sec_mfu_breakdown)
        section('jax_hello', 30, sec_jax_hello)
        section('pp_bf16', 30, sec_pp_bf16)
        extra['bench_elapsed_sec'] = round(time.monotonic() - _START, 1)
        emit()
    finally:
        shutil.rmtree(tmp, ignore_errors=True)


if __name__ == '__main__':
    main()
