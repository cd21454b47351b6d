"""Field codecs: (de)serialize rich field values into Parquet-storable cells.

From-scratch re-design of ``petastorm/codecs.py`` with the same on-disk byte
formats (so datasets written by the reference and by this framework interop):

* :class:`CompressedImageCodec` — png/jpeg bytes as produced by OpenCV
  (``codecs.py:58-130``), RGB channel order at the API boundary.
* :class:`NdarrayCodec` — the ``np.save`` .npy byte stream (``codecs.py:133-171``).
* :class:`CompressedNdarrayCodec` — ``np.savez_compressed`` bytes (``codecs.py:174-212``).
* :class:`ScalarCodec` — plain typed parquet cells (``codecs.py:215-271``).

Differences from the reference, deliberately:

* Codecs declare an **arrow** storage type (:meth:`arrow_type`); Spark types
  are derived from arrow only when pyspark is installed.
* Every codec also implements :meth:`decode_batch`, a vectorized batch decode
  used by the TPU host pipeline (the reference decodes strictly row-by-row via
  ``utils.decode_row``). This is the seam where native/Pallas batched decoders
  plug in.
* Codecs are JSON-describable (``codec_to_json``/``codec_from_json``) for the
  versioned footer format, instead of being pickled with the schema.
"""

import logging
import os
import threading
from abc import ABCMeta, abstractmethod
from decimal import Decimal
from io import BytesIO

import numpy as np
import pyarrow as pa

from petastorm_tpu import faults
from petastorm_tpu.telemetry import knobs
from petastorm_tpu.telemetry.spans import add_cpu
from petastorm_tpu.unischema import numpy_to_arrow_type

logger = logging.getLogger(__name__)

_IMAGE_POOL = None
_IMAGE_POOL_DISABLED = object()
_IMAGE_POOL_LOCK = threading.Lock()

# decode_fn -> bool: does this native build accept the trailing `threads`
# argument? Probed once per function with a zero-length call (only a stale
# .so predating the argument can raise TypeError there).
_NATIVE_THREADS_SUPPORT = {}

# In-process override of the PETASTORM_TPU_IMAGE_DECODER_THREADS parse
# (None = the knob rules). The staging autotuner's adjustment seam
# (jax/autotune.py): it must retune THIS process without mutating
# os.environ — child processes inherit the environment, and a mid-run
# mutation would silently retarget every later reader in this process
# and poison A/B comparisons against the knob's documented value.
_DECODER_THREADS_OVERRIDE = None


def set_image_decoder_threads_override(value):
    """Set (int) or clear (None) the in-process decoder-thread override
    consumed by :func:`image_decoder_threads`. Owned by the staging
    autotuner; the loader clears it at stop so a tuned-down width never
    outlives the loader that learned it."""
    global _DECODER_THREADS_OVERRIDE
    _DECODER_THREADS_OVERRIDE = (None if value is None
                                 else max(0, int(value)))

# Calibrated jpeg chroma-upsampling mode (1 fancy / 0 merged), or None until
# the first sizeable batch decides it; see _jpeg_upsampling_mode.
_JPEG_FANCY_MODE = None
_JPEG_FANCY_LOCK = threading.Lock()
_JPEG_FANCY_ATTEMPTS = 0
_JPEG_FANCY_MAX_ATTEMPTS = 5


def _jpeg_mode_cache_path(decode_fn):
    """Per-host cache file for the calibrated mode, keyed by the native
    jpeg module build (path+size+mtime): the winner depends only on the
    libjpeg build linked into that .so, so caching it makes the pick
    stable run-to-run on a host instead of re-flipping on machine noise
    (advisor r4). Returns None when the build can't be identified."""
    import hashlib
    import sys
    import tempfile
    module = sys.modules.get(getattr(decode_fn, '__module__', None))
    so_path = getattr(module, '__file__', None)
    if not so_path:
        return None
    try:
        st = os.stat(so_path)
    except OSError:
        return None
    key = hashlib.md5(('%s:%d:%d' % (so_path, st.st_size, st.st_mtime_ns))
                      .encode('utf-8')).hexdigest()[:12]
    return os.path.join(tempfile.gettempdir(),
                        'petastorm_tpu_jpeg_fancy_%d_%s'
                        % (os.getuid(), key))


def _jpeg_upsampling_mode(decode_fn, cells, image_shape):
    """Pick the faster libjpeg chroma-upsampling mode for THIS host.

    Which of libjpeg's two 4:2:0 paths wins depends on the host's libjpeg
    build (turbo SIMD-vectorizes the fancy upsampler; its merged RGB path
    is scalar on some configurations — see ``native/jpeg_batch.c``), so
    instead of hardcoding a loser, time both modes once per process on the
    first real batch and cache the winner. Shared boxes drift by 2x over
    seconds, so the timing is INTERLEAVED (mode order alternating within
    each round, median per mode) — back-to-back per-mode loops would just
    measure which mode ran during the quiet period. A set (non-empty)
    ``PETASTORM_TPU_JPEG_FANCY`` disables calibration and defers to the C
    module's env parse (returns -1), preserving the bit-exactness escape
    hatch (=1 is bit-identical to cv2).

    Cost: ~8 x min(n, 8) single-image decodes, once per process. A wrong
    pick on pathological timing costs only decode rate, never correctness
    — both modes are faithful decodes of the same bytes.
    """
    global _JPEG_FANCY_MODE, _JPEG_FANCY_ATTEMPTS
    if knobs.raw('PETASTORM_TPU_JPEG_FANCY'):
        return -1
    if _JPEG_FANCY_MODE is not None:
        return _JPEG_FANCY_MODE
    if len(cells) < 4:
        return -1  # too small to time; env default, keep calibration open
    with _JPEG_FANCY_LOCK:
        if _JPEG_FANCY_MODE is not None:
            return _JPEG_FANCY_MODE
        cache_path = _jpeg_mode_cache_path(decode_fn)
        if cache_path is not None:
            try:
                with open(cache_path) as f:
                    cached = f.read().strip()
                if cached in ('0', '1'):
                    _JPEG_FANCY_MODE = int(cached)
                    logger.info(
                        'jpeg upsampling mode: %s (host cache %s)',
                        'fancy' if _JPEG_FANCY_MODE else 'merged',
                        cache_path)
                    return _JPEG_FANCY_MODE
            except OSError:
                pass
        import statistics
        import time
        sample = cells[:8]
        scratch = np.empty((len(sample),) + tuple(image_shape), np.uint8)
        try:
            # zero-length signature probe: ONLY a stale .so predating the
            # mode argument can raise TypeError here (oddball cells are
            # prefix-skipped by the C loop, never raised)
            decode_fn([], scratch[:0], 0)
        except TypeError:
            _JPEG_FANCY_MODE = -1  # env default forever
            return -1
        timings = {0: [], 1: []}
        for mode in (0, 1):
            decode_fn(sample, scratch, mode)  # warm (page-in, caches)
        for round_idx in range(3):
            order = (0, 1) if round_idx % 2 == 0 else (1, 0)
            for mode in order:
                start = time.perf_counter()
                done = decode_fn(sample, scratch, mode)
                timings[mode].append(time.perf_counter() - start)
                if done != len(sample):
                    # non-jpeg/oddball cells: timing would compare
                    # different work; env default for now, and retry on a
                    # later batch — but only a bounded number of times
                    # (a dataset whose every batch leads with an oddball
                    # must not pay a calibration attempt per batch)
                    _JPEG_FANCY_ATTEMPTS += 1
                    if _JPEG_FANCY_ATTEMPTS >= _JPEG_FANCY_MAX_ATTEMPTS:
                        _JPEG_FANCY_MODE = -1
                    return -1
        medians = {m: statistics.median(t) for m, t in timings.items()}
        _JPEG_FANCY_MODE = min(medians, key=medians.get)
        # INFO so run artifacts record which mode produced the pixels
        # (the two modes are both faithful decodes but not bit-identical)
        logger.info(
            'jpeg upsampling calibrated: %s (merged %.1f img/s, fancy '
            '%.1f img/s)', 'fancy' if _JPEG_FANCY_MODE else 'merged',
            len(sample) / medians[0], len(sample) / medians[1])
        if cache_path is not None:
            try:
                tmp_path = cache_path + '.%d' % os.getpid()
                with open(tmp_path, 'w') as f:
                    f.write(str(_JPEG_FANCY_MODE))
                os.replace(tmp_path, cache_path)
            except OSError:
                pass  # stability cache only; calibration already decided
        return _JPEG_FANCY_MODE


def _pool_cpu_out():
    """The one-int64 buffer a native batch decoder adds its pool threads'
    CPU nanoseconds to (its ``cpu_out`` argument)."""
    return np.zeros(1, np.int64)


def _native_supports_threads(decode_fn, out, prefix_args):
    """True when this native build's batch decoder accepts the trailing
    ``threads`` and ``cpu_out`` arguments (probed once per function with a
    zero-length call; a stale ``.so`` predating them raises TypeError and
    is routed to the Python-side chunking fallback)."""
    ok = _NATIVE_THREADS_SUPPORT.get(decode_fn)
    if ok is None:
        try:
            decode_fn([], out[:0],
                      *(tuple(prefix_args) + (1, _pool_cpu_out())))
            ok = True
        except TypeError:
            ok = False
        _NATIVE_THREADS_SUPPORT[decode_fn] = ok
    return ok


def image_decoder_threads():
    """Decode-parallelism width from ``PETASTORM_TPU_IMAGE_DECODER_THREADS``
    (0/1 = serial; default min(4, cpu_count)) — the ONE owner of the
    parse. The SAME number sizes whichever pool actually runs a given
    batch: the native batch decoders' internal C-level pthread pool (one
    native call per row-group column, GIL released) when the C extensions
    are live, or the Python-side cv2 executor
    (:func:`_image_decode_pool`) on the fallback path. The two pools
    never stack on ONE batch (no threads × threads within a decode);
    concurrent reader workers each get their own width, so process-wide
    decode threads scale as workers × knob — sizing guidance in
    docs/env_knobs.md. The staging autotuner may override the parsed
    value in-process (:func:`set_image_decoder_threads_override`)."""
    if _DECODER_THREADS_OVERRIDE is not None:
        return _DECODER_THREADS_OVERRIDE
    return image_decoder_threads_from_knob()


def image_decoder_threads_from_knob():
    """The knob's own parsed value, ignoring any in-process override —
    the autotuner's restore ceiling (a tuner constructed while another
    loader's override is live must not mistake the tuned-down width for
    the configured baseline)."""
    raw = knobs.raw('PETASTORM_TPU_IMAGE_DECODER_THREADS')
    if raw is None:
        return min(4, os.cpu_count() or 1)
    try:
        return max(0, int(raw))
    except ValueError:
        logger.warning(
            'PETASTORM_TPU_IMAGE_DECODER_THREADS=%r is not an '
            'integer; threaded image decode disabled', raw)
        return 0


def _image_decode_pool():
    """Shared small thread pool for batched cv2 image decode, or None.

    cv2 releases the GIL, so a few threads give real parallelism on top of
    the reader's own worker parallelism without oversubscribing. Sized by
    :func:`image_decoder_threads`; only the cv2 fallback path uses it —
    the native decoders parallelize inside the C call instead.
    """
    global _IMAGE_POOL
    if _IMAGE_POOL is _IMAGE_POOL_DISABLED:
        return None
    if _IMAGE_POOL is None:
        with _IMAGE_POOL_LOCK:
            if _IMAGE_POOL is _IMAGE_POOL_DISABLED:
                return None
            if _IMAGE_POOL is None:
                workers = image_decoder_threads()
                if workers <= 1:
                    _IMAGE_POOL = _IMAGE_POOL_DISABLED
                    return None
                from concurrent.futures import ThreadPoolExecutor
                _IMAGE_POOL = ThreadPoolExecutor(
                    max_workers=workers, thread_name_prefix='img-decode')
    return _IMAGE_POOL


class DataframeColumnCodec(metaclass=ABCMeta):
    """Abstract codec contract (reference: ``petastorm/codecs.py:36-55``)."""

    @abstractmethod
    def encode(self, unischema_field, value):
        """Encode a single value into its parquet-storable form."""

    @abstractmethod
    def decode(self, unischema_field, encoded):
        """Decode a single stored cell back into its numpy form."""

    def decode_batch(self, unischema_field, encoded_iterable, out=None):
        """Decode many cells; default is a python loop, codecs may vectorize.

        ``out=`` (the fused-decode destination API, docs/telemetry.md):
        a preallocated ``(n,) + shape`` array the decoded rows land in —
        page-aligned column slabs from the row-group worker, or staging-
        arena slot views from the JAX loader's fused fill. When given it
        is filled IN PLACE and returned; a cell whose decoded shape/dtype
        cannot land in its row raises instead of silently degrading to a
        list (callers gate ``out=`` on fixed-shape fields).
        """
        values = [self.decode(unischema_field, v) for v in encoded_iterable]
        if out is None:
            return values
        for i, value in enumerate(values):
            _assign_row(out, i, value, unischema_field)
        return out

    @abstractmethod
    def arrow_type(self, unischema_field):
        """The arrow DataType of the stored column."""

    def spark_dtype(self, unischema_field):
        """Spark type of the stored column (requires pyspark)."""
        return arrow_to_spark_type(self.arrow_type(unischema_field))

    # JSON description for the versioned footer
    def to_json_dict(self):
        return {'type': type(self).__name__}


def _check_out_destination(unischema_field, out, n):
    """The ONE validation of a ``decode_batch(out=)`` destination: the
    field must be fixed-shape and ``out`` must be exactly ``(n,) + shape``
    in the field's dtype (both vectorizing codecs share this — the fused
    contract must not fork between them)."""
    shape = unischema_field.shape
    if not shape or any(d is None for d in shape):
        raise ValueError(
            'decode_batch(out=) requires a fixed-shape field; %r '
            'has shape %r' % (unischema_field.name, shape))
    expected = (n,) + tuple(shape)
    dtype = np.dtype(unischema_field.numpy_dtype)
    if out.shape != expected or out.dtype != dtype:
        raise ValueError(
            'decode_batch(out=): destination %s %s does not match '
            'the declared %s %s' % (out.shape, out.dtype, expected, dtype))


def _assign_row(out, i, value, unischema_field):
    """One decoded cell into row ``i`` of a caller-owned destination.
    The shape must match EXACTLY — plain ``out[i] = value`` would happily
    numpy-BROADCAST a smaller cell across the row (silent replicated
    data, where the no-``out`` path preserved the true shape and
    surfaced the raggedness downstream); dtype follows numpy assignment
    casting, same as the codecs' own ``astype`` to the declared dtype."""
    value = np.asarray(value)
    if value.shape != out.shape[1:]:
        raise ValueError(
            'decode_batch(out=): field %r cell decoded to shape %s, not '
            'the declared %s' % (unischema_field.name, value.shape,
                                 out.shape[1:]))
    out[i] = value


def decode_batch_with_nulls(unischema_field, values, out=None):
    """Batch-decode a column whose cells may be None (nullable fields): null
    cells bypass the codec and stay None, non-null cells go through the
    codec's vectorized ``decode_batch``. Positions are preserved.

    Returns either a list (one entry per cell, None preserved) or — on the
    all-non-null fast path — whatever the codec's ``decode_batch`` returned,
    which may be a contiguous ``(n,)+shape`` ndarray.

    ``out=`` (fused-decode destination): rows decode straight into the
    caller's preallocated ``(n,) + shape`` slab — contiguous runs of
    non-null cells each go through ONE vectorized ``decode_batch(out=)``
    call on the matching slab slice, and null positions are explicitly
    ZERO-FILLED (never left as uninitialized or previous-slot bytes —
    the slab may be a recycled staging-arena slot whose stale pixels
    would otherwise leak into "null" rows). Returns ``out``.
    """
    if faults.ARMED:
        # the one decode seam every path shares — native batch, cv2
        # fallback, fused-into-slot — so an injected "poisoned cell"
        # exercises whichever decoder actually runs
        faults.fault_hit('decode.batch',
                         key=getattr(unischema_field, 'name', None))
    if out is not None:
        codec = unischema_field.codec
        n = len(values)
        i = 0
        while i < n:
            if values[i] is None:
                j = i
                while j < n and values[j] is None:
                    j += 1
                out[i:j] = 0
                i = j
            else:
                j = i
                while j < n and values[j] is not None:
                    j += 1
                codec.decode_batch(unischema_field, values[i:j],
                                   out=out[i:j])
                i = j
        return out
    non_null_idx = [i for i, v in enumerate(values) if v is not None]
    if len(non_null_idx) == len(values):
        return unischema_field.codec.decode_batch(unischema_field, values)
    decoded = unischema_field.codec.decode_batch(
        unischema_field, [values[i] for i in non_null_idx])
    result = [None] * len(values)
    for slot, i in enumerate(non_null_idx):
        result[i] = decoded[slot]
    return result


class CompressedImageCodec(DataframeColumnCodec):
    """Store uint8/uint16 images as png or jpeg bytes.

    Byte-compatible with the reference codec (``petastorm/codecs.py:58-130``):
    images are RGB at the API boundary and channel-swapped to OpenCV's BGR for
    encode/decode of 3-channel images.

    .. note:: **jpeg decode determinism.** ``decode_batch`` prefers the
       first-party native decoder, whose DEFAULT chroma-upsampling mode
       (merged vs fancy) is auto-calibrated once per process to whichever
       this host decodes faster (see ``_jpeg_upsampling_mode``); per-cell
       ``decode`` and any fallback rows go through cv2, which always uses
       fancy upsampling. The two modes differ by small
       chroma-interpolation deltas (quality vs source within 0.2 dB
       PSNR), so in the default mode decoded pixels can vary with the
       path taken — across hosts (native build present or not, and which
       mode calibration picked) and across rows of one batch
       (oddball-cell fallback). Pipelines that need bit-identical decode
       everywhere should set env ``PETASTORM_TPU_JPEG_FANCY=1``, which
       forces fancy upsampling and makes the native path bit-identical to
       cv2 (provided the DCT method stays at its ``islow`` default —
       ``PETASTORM_TPU_JPEG_DCT=ifast`` trades that bit-identity away).
       png decode is lossless and path-independent either way.
    """

    def __init__(self, image_codec='png', quality=80):
        if image_codec not in ('png', 'jpeg', 'jpg'):
            raise ValueError('Unsupported image codec: %r' % image_codec)
        self._image_codec = '.' + image_codec
        self._quality = quality

    @property
    def image_codec(self):
        return self._image_codec[1:]

    def encode(self, unischema_field, value):
        import cv2
        if unischema_field.numpy_dtype != value.dtype:
            raise ValueError('Field %r dtype %s != value dtype %s'
                             % (unischema_field.name, unischema_field.numpy_dtype, value.dtype))
        if not unischema_field.is_shape_compliant(value.shape):
            raise ValueError('Field %r: image shape %s does not match %s'
                             % (unischema_field.name, value.shape, unischema_field.shape))
        if value.ndim == 3 and value.shape[2] not in (3, 4):
            raise ValueError('Field %r: images must be 2-d, HxWx3 or HxWx4; got shape %s'
                             % (unischema_field.name, value.shape))
        if value.ndim == 3:
            # cv2.cvtColor is SIMD-vectorized; numpy fancy-index channel
            # swaps cost ~25% of total decode throughput (measured).
            code = (cv2.COLOR_RGB2BGR if value.shape[2] == 3
                    else cv2.COLOR_RGBA2BGRA)
            bgr = cv2.cvtColor(np.ascontiguousarray(value), code)
        else:
            bgr = value
        params = ([int(cv2.IMWRITE_JPEG_QUALITY), self._quality]
                  if self._image_codec in ('.jpeg', '.jpg') else [])
        ok, encoded = cv2.imencode(self._image_codec, bgr, params)
        if not ok:
            raise RuntimeError('cv2.imencode failed for field %r' % unischema_field.name)
        return bytearray(encoded)

    @staticmethod
    def _as_uint8(encoded):
        """Cell bytes as a uint8 array, zero-copy for buffer views."""
        if isinstance(encoded, np.ndarray) and encoded.dtype == np.uint8:
            return encoded
        return np.frombuffer(bytes(encoded), dtype=np.uint8)

    @staticmethod
    def _is_3_channel(raw):
        """Header sniff: True only when the stored image provably has 3
        color components. Guards the direct-RGB decode fast path — forcing
        RGB onto a grayscale cell would silently colorize it instead of
        surfacing the shape mismatch."""
        if len(raw) < 26:
            return False
        head = raw[:4].tobytes()
        if head.startswith(b'\x89PNG'):
            # IHDR color type 2 = RGB triple; bit depth must be 8 — 16-bit
            # PNGs downscale by >>8 under forced-RGB decode but cast mod-256
            # under decode(), a silent value divergence
            return raw[25] == 2 and raw[24] == 8
        if head.startswith(b'\xff\xd8'):  # JPEG: scan for an SOF marker
            i = 2
            n = len(raw)
            while i + 9 < n:
                if raw[i] != 0xFF:
                    return False
                marker = int(raw[i + 1])
                if marker in (0xD8, 0x01) or 0xD0 <= marker <= 0xD7:
                    i += 2
                    continue
                if 0xC0 <= marker <= 0xCF and marker not in (0xC4, 0xC8, 0xCC):
                    # precision must be 8 bits; component count 3
                    return int(raw[i + 4]) == 8 and int(raw[i + 9]) == 3
                seg_len = (int(raw[i + 2]) << 8) | int(raw[i + 3])
                if seg_len < 2:
                    return False
                i += 2 + seg_len
        return False

    def decode(self, unischema_field, encoded):
        import cv2
        image = cv2.imdecode(self._as_uint8(encoded), cv2.IMREAD_UNCHANGED)
        if image is None:
            raise ValueError('cv2.imdecode failed for field %r' % unischema_field.name)
        if image.ndim == 3 and image.shape[2] in (3, 4):
            code = (cv2.COLOR_BGR2RGB if image.shape[2] == 3
                    else cv2.COLOR_BGRA2RGBA)
            image = cv2.cvtColor(image, code)
        # Every branch above leaves `image` a buffer cv2 freshly
        # allocated for THIS call (imdecode or cvtColor); when no cast is
        # needed astype(copy=False) returns that same owned array, so
        # ownership transfers cleanly to the caller.  # pipesan: owns
        return image.astype(unischema_field.numpy_dtype, copy=False)

    def _decode_into(self, unischema_field, encoded, dst):
        """Decode one cell directly into a row of a preallocated batch:
        cvtColor writes into ``dst`` (no intermediate copy). Raises on any
        shape/decode surprise so the caller can fall back."""
        import cv2
        raw = self._as_uint8(encoded)
        if (dst.ndim == 3 and dst.shape[2] == 3 and dst.dtype == np.uint8
                and hasattr(cv2, 'IMREAD_COLOR_RGB')
                and self._is_3_channel(raw)):
            # decode straight to RGB: saves the whole-image BGR→RGB pass
            # (bit-identical; the flag exists since OpenCV 4.10). EXIF
            # orientation must be ignored — decode()'s IMREAD_UNCHANGED
            # never applies it, and a silently rotated batch would diverge.
            image = cv2.imdecode(
                raw, cv2.IMREAD_COLOR_RGB | cv2.IMREAD_IGNORE_ORIENTATION)
            if image is None:
                raise ValueError('cv2.imdecode failed for field %r'
                                 % unischema_field.name)
            if image.shape != dst.shape:
                raise ValueError('decoded shape %s != declared %s'
                                 % (image.shape, dst.shape))
            dst[...] = image
            return
        image = cv2.imdecode(raw, cv2.IMREAD_UNCHANGED)
        if image is None:
            raise ValueError('cv2.imdecode failed for field %r' % unischema_field.name)
        if image.shape != dst.shape:
            raise ValueError('decoded shape %s != declared %s'
                             % (image.shape, dst.shape))
        if image.ndim == 3 and image.shape[2] in (3, 4):
            code = (cv2.COLOR_BGR2RGB if image.shape[2] == 3
                    else cv2.COLOR_BGRA2RGBA)
            if dst.dtype == image.dtype:
                cv2.cvtColor(image, code, dst=dst)
            else:
                dst[...] = cv2.cvtColor(image, code)
        else:
            dst[...] = image

    def decode_batch(self, unischema_field, encoded_iterable, out=None):
        """Batched decode with a threaded cv2 fan-out for fixed-shape fields.

        cv2.imdecode releases the GIL, so decoding cells on a small shared
        thread pool runs truly in parallel; results land directly in one
        preallocated contiguous ``(n,)+shape`` array (no per-cell ndarray
        retained + no later np.stack copy — downstream collation passes the
        dense batch through). Wildcard-shaped fields and any decode surprise
        (bad bytes, shape mismatch) fall back to the sequential per-cell
        path, which preserves reference semantics exactly.

        ``out=`` selects the fused-decode destination contract: the rows
        decode straight into the caller's buffer (a page-aligned column
        slab or a staging-arena slot view), it must be ``(n,) + shape`` in
        the field's dtype, and decode surprises RAISE instead of falling
        back to a list — the caller owns the buffer's lifecycle and a
        silent shape change would corrupt it.

        SURVEY §7.3 calls jpeg/png decode throughput the place the
        north-star input rate is won or lost; this is the corresponding
        hot-loop (reference equivalent: ``petastorm/codecs.py:102-130``,
        one cv2 call per row with no batch seam at all).
        """
        cells = encoded_iterable if isinstance(encoded_iterable, list) \
            else list(encoded_iterable)
        shape = unischema_field.shape
        n = len(cells)
        if out is not None:
            _check_out_destination(unischema_field, out, n)
            self._decode_dense(unischema_field, cells, out)
            return out
        if n >= 4 and shape and not any(d is None for d in shape):
            try:
                dense = np.empty((n,) + tuple(shape),
                                 dtype=unischema_field.numpy_dtype)
                self._decode_dense(unischema_field, cells, dense)
                return dense
            except Exception:  # noqa: BLE001 - dense path is an accelerator
                logger.debug('Dense batched image decode failed; falling back '
                             'to the per-cell path', exc_info=True)
        return [self.decode(unischema_field, v) for v in cells]

    def _decode_dense(self, unischema_field, cells, out):
        """Decode every cell into its row of ``out``; raises on any decode
        surprise (the no-``out`` caller catches and falls back). The
        Python-side cv2 executor is consulted only AFTER the native path
        declined — on the native one-call path it is never even created
        (the one-pool contract of docs/env_knobs.md)."""
        if self._native_image_batch(unischema_field, cells, out):
            return
        pool = _image_decode_pool()
        if pool is None:
            for i in range(len(cells)):
                self._decode_into(unischema_field, cells[i], out[i])
        else:
            list(pool.map(
                lambda i: self._decode_into(unischema_field,
                                            cells[i], out[i]),
                range(len(cells))))

    def _native_image_batch(self, unischema_field, cells, out):
        """Decode an image batch with the first-party native loops
        (``native/jpeg_batch.c`` / ``native/png_batch.c``); True when
        ``out`` is fully populated.

        One C call decodes the whole batch RGB-direct into ``out`` with the
        GIL released, without per-cell Python dispatch or Mat allocation.
        png is bit-identical to the cv2 path (PNG stores RGB natively).
        jpeg chroma upsampling is auto-calibrated per process — merged vs
        fancy, whichever THIS host decodes faster (the winner is
        host-dependent; see ``_jpeg_upsampling_mode``); the two differ
        only in chroma interpolation (within 0.2 dB PSNR vs the source).
        Set env ``PETASTORM_TPU_JPEG_FANCY=1`` to force fancy, which is
        bit-identical-to-cv2 output (both ride libjpeg-turbo; see
        ``native/jpeg_batch.c``; requires the default ``islow`` DCT — not
        ``PETASTORM_TPU_JPEG_DCT=ifast``), or ``=0`` to force merged.

        Parallelism is ONE pool, never two (docs/env_knobs.md): with
        ``PETASTORM_TPU_IMAGE_DECODER_THREADS`` > 1 and a current native
        build, the whole column goes down in a SINGLE native call whose
        internal C-level pthread pool fans the cells out (no Python task
        churn, no GIL round trips between chunks); only a stale ``.so``
        predating the ``threads`` argument falls back to chunking the
        batch across the shared Python executor. Cells the native loop
        rejects (not a 3-component 8-bit image of the declared shape)
        finish through ``_decode_into``, whose failures propagate to the
        caller's sequential fallback.
        """
        if out.dtype != np.uint8 or out.ndim != 4 or out.shape[3] != 3:
            return False
        if self._image_codec in ('.jpeg', '.jpg'):
            from petastorm_tpu.native import get_jpeg_module
            native_mod = get_jpeg_module()
            decode_fn = getattr(native_mod, 'decode_jpeg_batch', None)
            if decode_fn is None:
                return False
            mode = _jpeg_upsampling_mode(decode_fn, cells, out.shape[1:])
            # the jpeg threads argument is positional AFTER the mode, so
            # the threaded call always names the mode explicitly (-1 =
            # the C env-default contract); the chunked fallback keeps the
            # historical arity for stale builds
            threaded_prefix = (mode,)
            decode_args = (mode,) if mode >= 0 else ()
        elif self._image_codec == '.png':
            from petastorm_tpu.native import get_png_module
            native_mod = get_png_module()
            decode_fn = getattr(native_mod, 'decode_png_batch', None)
            if decode_fn is None:
                return False
            threaded_prefix = ()
            decode_args = ()
        else:
            return False

        def run(lo, hi, call_args):
            # prefix-count contract: decode natively, route ONLY the
            # rejected cell through the generic path, then re-enter the
            # native loop on the tail (one oddball must not demote the
            # whole remaining chunk to per-cell decode)
            while lo < hi:
                done = decode_fn(cells[lo:hi], out[lo:hi], *call_args)
                lo += done
                if lo < hi:
                    self._decode_into(unischema_field, cells[lo], out[lo])
                    lo += 1

        n = len(cells)
        threads = image_decoder_threads()
        if threads > 1 and _native_supports_threads(decode_fn, out,
                                                    threaded_prefix):
            # ONE native call: the C pool fans the whole row-group column
            # out with the GIL released. The Python executor is NOT also
            # engaged (nor created) — the knob sizes exactly one pool per
            # batch. The pool's CPU goes to the enclosing stage span.
            pool_cpu = _pool_cpu_out()
            run(0, n, threaded_prefix + (threads, pool_cpu))
            add_cpu(int(pool_cpu[0]) / 1e9)
            return True
        # only the chunked fallback (stale .so / serial knob) consults
        # the Python-side executor into existence
        pool = _image_decode_pool()
        workers = getattr(pool, '_max_workers', 0) if pool is not None else 0
        if workers > 1 and n >= 2 * workers:
            chunk = -(-n // workers)
            bounds = [(lo, min(lo + chunk, n))
                      for lo in range(0, n, chunk)]
            list(pool.map(lambda b: run(b[0], b[1], decode_args), bounds))
        else:
            run(0, n, decode_args)
        return True

    def arrow_type(self, unischema_field):
        return pa.binary()

    def to_json_dict(self):
        return {'type': 'CompressedImageCodec',
                'image_codec': self.image_codec, 'quality': self._quality}


class NdarrayCodec(DataframeColumnCodec):
    """Store any numpy ndarray as .npy bytes (``petastorm/codecs.py:133-171``)."""

    def encode(self, unischema_field, value):
        _check_ndarray(unischema_field, value)
        buf = BytesIO()
        np.save(buf, value, allow_pickle=False)
        return bytearray(buf.getvalue())

    def decode(self, unischema_field, encoded):
        arr = np.load(BytesIO(bytes(encoded)), allow_pickle=False)
        return arr

    def decode_batch(self, unischema_field, encoded_iterable, out=None):
        """Fixed-shape numeric fields take the native batched decoder (one C
        call parsing all headers then memcpy-ing every payload with the GIL
        released — fanned across the internal pthread pool when
        ``PETASTORM_TPU_IMAGE_DECODER_THREADS`` > 1); anything else —
        wildcard dims, strings, or cells the native parser rejects — flows
        through the per-cell Python path. ``out=`` decodes into the
        caller's preallocated slab (fused-decode destination contract:
        fixed-shape fields only; surprises raise)."""
        cells = list(encoded_iterable)
        shape = unischema_field.shape
        if out is not None and not cells:
            return out
        fixed = bool(cells) and bool(shape) \
            and not any(d is None for d in shape)
        try:
            dtype = np.dtype(unischema_field.numpy_dtype)
        except TypeError:
            dtype = None
        if not fixed or dtype is None or dtype.kind not in 'iufb':
            if out is not None:
                raise ValueError(
                    'decode_batch(out=) requires a fixed-shape numeric '
                    'field; %r has shape %r' % (unischema_field.name, shape))
            return super().decode_batch(unischema_field, cells)
        dense = out
        if dense is not None:
            _check_out_destination(unischema_field, dense, len(cells))
        from petastorm_tpu.native import get_native_module
        native = get_native_module()
        if native is None:
            if dense is not None:
                return super().decode_batch(unischema_field, cells,
                                            out=dense)
            return super().decode_batch(unischema_field, cells)
        if dense is None:
            dense = np.empty((len(cells),) + shape, dtype=dtype)
        # numpy's header writer emits the shape tuple with canonical repr
        # spacing ("'shape': (2, 3)"), so an exact substring match rejects
        # any cell whose true shape differs from the declared one even when
        # the byte counts coincide (e.g. (3,2) vs (2,3)); rejected cells
        # fall back to the Python path, which preserves the true shape.
        shape_str = "'shape': %r" % (tuple(int(d) for d in shape),)
        threads = image_decoder_threads()
        pool_cpu = _pool_cpu_out()
        try:
            done = native.decode_npy_batch(cells, dense, dtype.str,
                                           shape_str, threads, pool_cpu)
        except TypeError:  # stale .so predating the threads argument
            done = native.decode_npy_batch(cells, dense, dtype.str,
                                           shape_str)
        add_cpu(int(pool_cpu[0]) / 1e9)
        if done == len(cells):
            # Return the contiguous batch itself: downstream collation
            # (arrow_worker._stack) passes it through, avoiding a second
            # full-batch copy via np.stack.
            return dense
        if out is not None:
            # fused destination: the rejected tail decodes per-cell into
            # its rows; a true-shape mismatch raises (the caller owns the
            # buffer and a silent broadcast would corrupt it)
            for i in range(done, len(cells)):
                _assign_row(out, i, self.decode(unischema_field, cells[i]),
                            unischema_field)
            return out
        rows = list(dense[:done])
        rows.extend(self.decode(unischema_field, c) for c in cells[done:])
        return rows

    def arrow_type(self, unischema_field):
        return pa.binary()


class CompressedNdarrayCodec(DataframeColumnCodec):
    """Store a numpy ndarray zlib-compressed (``petastorm/codecs.py:174-212``)."""

    def encode(self, unischema_field, value):
        _check_ndarray(unischema_field, value)
        buf = BytesIO()
        np.savez_compressed(buf, arr=value)
        return bytearray(buf.getvalue())

    def decode(self, unischema_field, encoded):
        with np.load(BytesIO(bytes(encoded)), allow_pickle=False) as npz:
            return npz['arr']

    def arrow_type(self, unischema_field):
        return pa.binary()


class ScalarCodec(DataframeColumnCodec):
    """Store a scalar as a typed parquet cell (``petastorm/codecs.py:215-271``).

    The reference parameterizes this codec with a Spark type; here it is
    parameterized with an **arrow** type (a numpy dtype or a Spark type are
    also accepted and converted), keeping Spark optional.
    """

    def __init__(self, storage_type):
        self._arrow_type = _as_arrow_type(storage_type)

    def encode(self, unischema_field, value):
        at = self._arrow_type
        if pa.types.is_integer(at):
            return int(value)
        if pa.types.is_floating(at):
            return float(value)
        if pa.types.is_boolean(at):
            return bool(value)
        if pa.types.is_string(at) or pa.types.is_large_string(at):
            if isinstance(value, Decimal):
                return str(value)
            if isinstance(value, bytes):
                return value.decode('utf-8')
            return str(value)
        if pa.types.is_binary(at) or pa.types.is_large_binary(at):
            return bytes(value)
        if pa.types.is_decimal(at):
            return Decimal(str(value))
        if pa.types.is_timestamp(at) or pa.types.is_date(at):
            return value
        raise ValueError('ScalarCodec: unsupported storage type %s' % at)

    def decode(self, unischema_field, encoded):
        if unischema_field.numpy_dtype is Decimal:
            return Decimal(encoded)
        return unischema_field.numpy_dtype(encoded)

    def decode_batch(self, unischema_field, encoded_iterable):
        if unischema_field.numpy_dtype is Decimal:
            return [Decimal(v) for v in encoded_iterable]
        return np.asarray(list(encoded_iterable)).astype(unischema_field.numpy_dtype)

    def arrow_type(self, unischema_field):
        return self._arrow_type

    def to_json_dict(self):
        return {'type': 'ScalarCodec', 'arrow_type': str(self._arrow_type)}


def _check_ndarray(unischema_field, value):
    if not isinstance(value, np.ndarray):
        raise ValueError('Field %r: expected ndarray, got %s'
                         % (unischema_field.name, type(value)))
    want = np.dtype(unischema_field.numpy_dtype)
    # Flexible dtypes (str/bytes) carry an item length; compare by kind only.
    matches = (want.kind == value.dtype.kind if want.kind in 'SU'
               else want == value.dtype)
    if not matches:
        raise ValueError('Field %r dtype %s != value dtype %s'
                         % (unischema_field.name, unischema_field.numpy_dtype, value.dtype))
    if not unischema_field.is_shape_compliant(value.shape):
        raise ValueError('Field %r: shape %s does not match %s'
                         % (unischema_field.name, value.shape, unischema_field.shape))


# ---------------------------------------------------------------------------
# storage-type conversions
# ---------------------------------------------------------------------------

_ARROW_TYPE_PARSERS = {
    'bool': pa.bool_, 'int8': pa.int8, 'uint8': pa.uint8, 'int16': pa.int16,
    'uint16': pa.uint16, 'int32': pa.int32, 'uint32': pa.uint32,
    'int64': pa.int64, 'uint64': pa.uint64, 'halffloat': pa.float16,
    'float': pa.float32, 'double': pa.float64, 'string': pa.string,
    'large_string': pa.large_string, 'binary': pa.binary,
    'large_binary': pa.large_binary,
}


def _parse_arrow_type(type_str):
    if type_str in _ARROW_TYPE_PARSERS:
        return _ARROW_TYPE_PARSERS[type_str]()
    if type_str.startswith('timestamp'):
        inner = type_str[type_str.index('[') + 1:type_str.index(']')]
        if ',' in inner:  # e.g. 'timestamp[us, tz=UTC]'
            unit, tz_part = (s.strip() for s in inner.split(',', 1))
            tz = tz_part.split('=', 1)[1] if '=' in tz_part else None
            return pa.timestamp(unit, tz)
        return pa.timestamp(inner)
    if type_str.startswith('date32'):
        return pa.date32()
    if type_str.startswith('date64'):
        return pa.date64()
    if type_str.startswith('decimal'):
        inner = type_str[type_str.index('(') + 1:type_str.index(')')]
        precision, scale = (int(x) for x in inner.split(','))
        return pa.decimal128(precision, scale)
    raise ValueError('Cannot parse arrow type string %r' % type_str)


def _as_arrow_type(storage_type):
    """Accept an arrow DataType, a numpy dtype, or a Spark DataType."""
    if isinstance(storage_type, pa.DataType):
        return storage_type
    if isinstance(storage_type, str):
        return _parse_arrow_type(storage_type)
    try:
        return numpy_to_arrow_type(storage_type)
    except (ValueError, TypeError):
        pass
    # Possibly a Spark type instance; map via its simpleString.
    simple = getattr(storage_type, 'simpleString', None)
    if callable(simple):
        return _spark_simple_string_to_arrow(simple())
    raise ValueError('Cannot interpret %r as a storage type' % (storage_type,))


_SPARK_SIMPLE_TO_ARROW = {
    'boolean': pa.bool_(), 'tinyint': pa.int8(), 'smallint': pa.int16(),
    'int': pa.int32(), 'bigint': pa.int64(), 'float': pa.float32(),
    'double': pa.float64(), 'string': pa.string(), 'binary': pa.binary(),
    'timestamp': pa.timestamp('us'), 'date': pa.date32(),
}


def _spark_simple_string_to_arrow(simple):
    if simple in _SPARK_SIMPLE_TO_ARROW:
        return _SPARK_SIMPLE_TO_ARROW[simple]
    if simple.startswith('decimal'):
        inner = simple[simple.index('(') + 1:simple.index(')')]
        precision, scale = (int(x) for x in inner.split(','))
        return pa.decimal128(precision, scale)
    raise ValueError('Cannot map spark type %r to arrow' % simple)


#: arrow type (by ``str()``) → pyspark type class name. The single source of
#: truth for the arrow↔spark bridge: used by :func:`arrow_to_spark_type`
#: (live pyspark instances) and by the footer's reference-compatible schema
#: export (class names only, no pyspark needed) in ``etl/legacy.py``.
ARROW_TO_SPARK_TYPE_NAME = {
    'bool': 'BooleanType', 'int8': 'ByteType', 'int16': 'ShortType',
    'int32': 'IntegerType', 'int64': 'LongType',
    'uint8': 'ShortType', 'uint16': 'IntegerType', 'uint32': 'LongType',
    'halffloat': 'FloatType', 'float': 'FloatType', 'double': 'DoubleType',
    'string': 'StringType', 'large_string': 'StringType',
    'binary': 'BinaryType', 'large_binary': 'BinaryType',
    'date32[day]': 'DateType',
}


def arrow_to_spark_type(arrow_type):
    """Map an arrow DataType to a Spark DataType (requires pyspark)."""
    from pyspark.sql import types as T
    name = ARROW_TO_SPARK_TYPE_NAME.get(str(arrow_type))
    if name is not None:
        return getattr(T, name)()
    if pa.types.is_timestamp(arrow_type):
        return T.TimestampType()
    if pa.types.is_decimal(arrow_type):
        return T.DecimalType(arrow_type.precision, arrow_type.scale)
    if pa.types.is_list(arrow_type):
        return T.ArrayType(arrow_to_spark_type(arrow_type.value_type))
    raise ValueError('Cannot map arrow type %s to spark' % arrow_type)


# ---------------------------------------------------------------------------
# JSON (de)serialization of codec descriptions
# ---------------------------------------------------------------------------

def codec_to_json(codec):
    if codec is None:
        return None
    return codec.to_json_dict()


def codec_from_json(d):
    if d is None:
        return None
    kind = d['type']
    if kind == 'CompressedImageCodec':
        return CompressedImageCodec(d['image_codec'], d['quality'])
    if kind == 'NdarrayCodec':
        return NdarrayCodec()
    if kind == 'CompressedNdarrayCodec':
        return CompressedNdarrayCodec()
    if kind == 'ScalarCodec':
        return ScalarCodec(_parse_arrow_type(d['arrow_type']))
    raise ValueError('Unknown codec type in schema JSON: %r' % kind)
