"""Native batched NPY decoder tests (C extension, with Python fallback
parity checks)."""

from io import BytesIO

import numpy as np
import pytest

from petastorm_tpu.codecs import NdarrayCodec
from petastorm_tpu.native import get_native_module
from petastorm_tpu.unischema import UnischemaField


def _npy(arr):
    buf = BytesIO()
    np.save(buf, arr, allow_pickle=False)
    return buf.getvalue()


def _shape_str(out):
    return "'shape': %r" % (out.shape[1:],)


@pytest.fixture(scope='module')
def native():
    module = get_native_module()
    if module is None:
        pytest.skip('native extension could not be built')
    return module


class TestNativeDecoder:
    def test_roundtrip_matches_source(self, native):
        rng = np.random.RandomState(0)
        arrs = [rng.rand(4, 6).astype(np.float32) for _ in range(20)]
        out = np.empty((20, 4, 6), np.float32)
        assert native.decode_npy_batch([_npy(a) for a in arrs], out, '<f4', _shape_str(out)) == 20
        for i in range(20):
            np.testing.assert_array_equal(out[i], arrs[i])

    def test_dtype_variants(self, native):
        for dtype in (np.int64, np.uint8, np.float64, np.bool_):
            arr = (np.arange(12) % 2).astype(dtype).reshape(3, 4)
            out = np.empty((1, 3, 4), dtype)
            assert native.decode_npy_batch([_npy(arr)], out,
                                           np.dtype(dtype).str,
                                           _shape_str(out)) == 1
            np.testing.assert_array_equal(out[0], arr)

    def test_stops_at_none(self, native):
        arr = np.ones((2, 2), np.float32)
        out = np.empty((3, 2, 2), np.float32)
        cells = [_npy(arr), None, _npy(arr)]
        assert native.decode_npy_batch(cells, out, '<f4', _shape_str(out)) == 1

    def test_stops_at_wrong_shape(self, native):
        good = np.ones((2, 2), np.float32)
        bad = np.ones((3, 3), np.float32)
        out = np.empty((2, 2, 2), np.float32)
        assert native.decode_npy_batch([_npy(good), _npy(bad)], out, '<f4', _shape_str(out)) == 1

    def test_rejects_wrong_dtype(self, native):
        arr = np.ones((2, 2), np.float64)
        out = np.empty((1, 2, 2), np.float32)
        assert native.decode_npy_batch([_npy(arr)], out, '<f4', _shape_str(out)) == 0

    def test_rejects_garbage(self, native):
        out = np.empty((1, 2, 2), np.float32)
        assert native.decode_npy_batch([b'not-an-npy'], out, '<f4', _shape_str(out)) == 0

    def test_rejects_fortran_order(self, native):
        arr = np.asfortranarray(np.arange(6, dtype=np.float32).reshape(2, 3))
        out = np.empty((1, 2, 3), np.float32)
        # np.save of a fortran array records fortran_order True
        assert native.decode_npy_batch([_npy(arr)], out, '<f4', _shape_str(out)) == 0

    def test_rejects_transposed_shape_same_bytes(self, native):
        # (3,2) and (2,3) have equal byte counts; memcpy'ing the former into
        # the latter would silently reinterpret the data (ADVICE r1, medium).
        arr = np.arange(6, dtype=np.float32).reshape(3, 2)
        out = np.empty((1, 2, 3), np.float32)
        assert native.decode_npy_batch([_npy(arr)], out, '<f4', _shape_str(out)) == 0

    def test_rejects_flat_vs_square_same_bytes(self, native):
        arr = np.arange(4, dtype=np.float32)  # (4,) vs declared (2, 2)
        out = np.empty((1, 2, 2), np.float32)
        assert native.decode_npy_batch([_npy(arr)], out, '<f4', _shape_str(out)) == 0

    def test_threads_arg_parity_and_prefix(self, native):
        """The internal-pool spelling (trailing threads arg) decodes the
        same bytes to the same rows as the serial call, and a mid-batch
        oddball keeps the decoded-prefix contract."""
        rng = np.random.RandomState(3)
        arrs = [rng.rand(8, 16).astype(np.float32) for _ in range(24)]
        cells = [_npy(a) for a in arrs]
        serial = np.empty((24, 8, 16), np.float32)
        pooled = np.empty_like(serial)
        assert native.decode_npy_batch(cells, serial, '<f4',
                                       _shape_str(serial)) == 24
        assert native.decode_npy_batch(cells, pooled, '<f4',
                                       _shape_str(pooled), 4) == 24
        np.testing.assert_array_equal(serial, pooled)
        bad = list(cells)
        bad[5] = b'not-an-npy'
        prefix = np.empty_like(serial)
        assert native.decode_npy_batch(bad, prefix, '<f4',
                                       _shape_str(prefix), 4) == 5
        np.testing.assert_array_equal(prefix[:5], serial[:5])


class TestCodecIntegration:
    def test_codec_batch_equals_per_cell(self):
        field = UnischemaField('m', np.float32, (5, 7), NdarrayCodec(), False)
        codec = field.codec
        rng = np.random.RandomState(1)
        arrs = [rng.rand(5, 7).astype(np.float32) for _ in range(10)]
        cells = [codec.encode(field, a) for a in arrs]
        batch = codec.decode_batch(field, cells)
        for got, expected in zip(batch, arrs):
            np.testing.assert_array_equal(got, expected)

    def test_codec_mixed_valid_cells_fall_back(self):
        field = UnischemaField('m', np.float32, (2, 2), NdarrayCodec(), False)
        codec = field.codec
        a = np.ones((2, 2), np.float32)
        # wildcard-free field but one cell is float64: full parity via fallback
        weird = BytesIO()
        np.save(weird, np.ones((2, 2), np.float64), allow_pickle=False)
        batch = codec.decode_batch(field, [codec.encode(field, a),
                                           weird.getvalue()])
        np.testing.assert_array_equal(batch[0], a)
        assert batch[1].dtype == np.float64

    def test_codec_transposed_cell_falls_back_with_true_shape(self):
        field = UnischemaField('m', np.float32, (2, 3), NdarrayCodec(), False)
        codec = field.codec
        good = np.arange(6, dtype=np.float32).reshape(2, 3)
        transposed = np.arange(6, dtype=np.float32).reshape(3, 2)
        batch = codec.decode_batch(field, [codec.encode(field, good),
                                           _npy(transposed)])
        np.testing.assert_array_equal(batch[0], good)
        # the mismatched cell must keep its true shape, not be reinterpreted
        assert batch[1].shape == (3, 2)
        np.testing.assert_array_equal(batch[1], transposed)

    def test_wildcard_shape_uses_python_path(self):
        field = UnischemaField('m', np.float32, (None, 3), NdarrayCodec(), False)
        codec = field.codec
        arrs = [np.ones((i + 1, 3), np.float32) for i in range(3)]
        batch = codec.decode_batch(field, [codec.encode(field, a) for a in arrs])
        assert [b.shape for b in batch] == [(1, 3), (2, 3), (3, 3)]


class TestBuildStaleness:
    """The staleness probe covers the BUILD IDENTITY, not just .c mtime:
    a compiler/linker-flag change (e.g. adding -pthread) must trigger a
    rebuild instead of loading a stale extension (ISSUE 9 satellite)."""

    def test_current_build_is_found(self, native):
        import petastorm_tpu.native as nat
        assert nat._find_built_extension('_npy_batch') is not None

    def test_flag_identity_change_marks_stale(self, native, monkeypatch):
        import petastorm_tpu.native as nat
        monkeypatch.setattr(nat, '_build_identity',
                            lambda name: 'changed-flags')
        assert nat._find_built_extension('_npy_batch') is None

    def test_missing_identity_sidecar_marks_stale(self, native, monkeypatch,
                                                  tmp_path):
        # a .so that predates identity tracking has nothing vouching for
        # its flags: rebuild once rather than trust it
        import petastorm_tpu.native as nat
        monkeypatch.setattr(nat, '_identity_path',
                            lambda name: str(tmp_path / 'absent'))
        assert nat._find_built_extension('_npy_batch') is None

    def test_identity_covers_compile_flags(self):
        # the identity hashes the generated build script, which embeds
        # the flags — so the -pthread addition itself re-keys every build
        import petastorm_tpu.native as nat
        script = nat._build_script('_npy_batch')
        assert '-pthread' in script
        assert nat._build_identity('_npy_batch') \
            != __import__('hashlib').md5(b'other').hexdigest()


@pytest.fixture(scope='module')
def jpeg_native():
    from petastorm_tpu.native import get_jpeg_module
    module = get_jpeg_module()
    if module is None:
        pytest.skip('native jpeg extension could not be built '
                    '(no libjpeg dev files?)')
    return module


def _jpeg_cells(n, h=48, w=64, seed=0, quality=90):
    import cv2
    rng = np.random.RandomState(seed)
    cells, images = [], []
    for _ in range(n):
        base = cv2.resize((rng.rand(8, 8, 3) * 200).astype(np.uint8), (w, h),
                          interpolation=cv2.INTER_CUBIC)
        img = np.clip(base.astype(np.float64) + rng.rand(h, w, 3) * 40,
                      0, 255).astype(np.uint8)
        ok, enc = cv2.imencode('.jpeg',
                               cv2.cvtColor(img, cv2.COLOR_RGB2BGR),
                               [int(cv2.IMWRITE_JPEG_QUALITY), quality])
        assert ok
        cells.append(enc.tobytes())
        images.append(img)
    return cells, images


class TestNativeJpegDecoder:
    def test_bit_exact_with_cv2_under_fancy_env(self, jpeg_native,
                                                monkeypatch):
        """PETASTORM_TPU_JPEG_FANCY=1 restores libjpeg defaults, which are
        bit-identical to cv2's decode of the same bytes (both ride
        libjpeg-turbo) — the strict-compat escape hatch."""
        import cv2
        monkeypatch.setenv('PETASTORM_TPU_JPEG_FANCY', '1')
        cells, _ = _jpeg_cells(6)
        out = np.empty((6, 48, 64, 3), np.uint8)
        assert jpeg_native.decode_jpeg_batch(cells, out) == 6
        for i, cell in enumerate(cells):
            ref = cv2.imdecode(np.frombuffer(cell, np.uint8),
                               cv2.IMREAD_COLOR_RGB)
            np.testing.assert_array_equal(out[i], ref)

    def test_default_fast_path_close_to_cv2(self, jpeg_native, monkeypatch):
        """The default (merged-upsampling) decode differs from cv2 only in
        chroma interpolation: small mean deviation, never the luma-scale
        corruption a wrong-stride/wrong-colorspace bug would produce."""
        import cv2
        monkeypatch.delenv('PETASTORM_TPU_JPEG_FANCY', raising=False)
        cells, _ = _jpeg_cells(6)
        out = np.empty((6, 48, 64, 3), np.uint8)
        assert jpeg_native.decode_jpeg_batch(cells, out) == 6
        refs = np.stack([cv2.imdecode(np.frombuffer(c, np.uint8),
                                      cv2.IMREAD_COLOR_RGB) for c in cells])
        diff = np.abs(out.astype(int) - refs.astype(int))
        assert diff.mean() < 8.0, diff.mean()
        assert np.percentile(diff, 99) < 48, np.percentile(diff, 99)

    def test_ifast_dct_close_to_default(self, jpeg_native, monkeypatch):
        """PETASTORM_TPU_JPEG_DCT=ifast opts into turbo's fast integer DCT
        (for builds whose ISLOW has no SIMD path); output stays a faithful
        decode — tiny deviation from the default-path decode, no
        corruption."""
        monkeypatch.delenv('PETASTORM_TPU_JPEG_FANCY', raising=False)
        cells, _ = _jpeg_cells(4)
        default_out = np.empty((4, 48, 64, 3), np.uint8)
        monkeypatch.delenv('PETASTORM_TPU_JPEG_DCT', raising=False)
        assert jpeg_native.decode_jpeg_batch(cells, default_out) == 4
        ifast_out = np.empty((4, 48, 64, 3), np.uint8)
        monkeypatch.setenv('PETASTORM_TPU_JPEG_DCT', 'ifast')
        assert jpeg_native.decode_jpeg_batch(cells, ifast_out) == 4
        # the knob must actually take effect: IFAST and ISLOW provably
        # differ on q90 4:2:0 cells, so identical output means the env
        # parse is dead and both runs decoded ISLOW
        assert (ifast_out != default_out).any()
        diff = np.abs(ifast_out.astype(int) - default_out.astype(int))
        assert diff.mean() < 4.0, diff.mean()
        assert diff.max() < 64, diff.max()

    def test_corrupt_cell_stops_prefix(self, jpeg_native):
        cells, _ = _jpeg_cells(5)
        cells[2] = cells[2][:40]
        out = np.empty((5, 48, 64, 3), np.uint8)
        assert jpeg_native.decode_jpeg_batch(cells, out) == 2

    def test_wrong_size_stops(self, jpeg_native):
        cells, _ = _jpeg_cells(3)
        out = np.empty((3, 32, 32, 3), np.uint8)
        assert jpeg_native.decode_jpeg_batch(cells, out) == 0

    def test_grayscale_rejected_to_python_path(self, jpeg_native):
        import cv2
        gray = (np.arange(48 * 64, dtype=np.uint8).reshape(48, 64))
        ok, enc = cv2.imencode('.jpeg', gray)
        cells, _ = _jpeg_cells(2)
        out = np.empty((3, 48, 64, 3), np.uint8)
        assert jpeg_native.decode_jpeg_batch(
            [cells[0], enc.tobytes(), cells[1]], out) == 1

    def test_threads_arg_parity_and_prefix(self, jpeg_native, monkeypatch):
        """decode_jpeg_batch(cells, out, fancy, threads): the internal
        pthread pool decodes bit-identically to the serial loop (same
        mode, same libjpeg), and a corrupt mid-batch cell keeps the
        decoded-prefix contract across chunk boundaries."""
        monkeypatch.delenv('PETASTORM_TPU_JPEG_FANCY', raising=False)
        cells, _ = _jpeg_cells(11)
        serial = np.empty((11, 48, 64, 3), np.uint8)
        pooled = np.empty_like(serial)
        assert jpeg_native.decode_jpeg_batch(cells, serial, 1) == 11
        assert jpeg_native.decode_jpeg_batch(cells, pooled, 1, 4) == 11
        np.testing.assert_array_equal(serial, pooled)
        bad = list(cells)
        bad[3] = bad[3][:40]
        prefix = np.empty_like(serial)
        assert jpeg_native.decode_jpeg_batch(bad, prefix, 1, 4) == 3
        np.testing.assert_array_equal(prefix[:3], serial[:3])

    def test_arrow_buffer_cells(self, jpeg_native):
        import pyarrow as pa
        cells, _ = _jpeg_cells(4)
        arr = pa.array(cells, pa.binary())
        out = np.empty((4, 48, 64, 3), np.uint8)
        assert jpeg_native.decode_jpeg_batch(
            [v.as_buffer() for v in arr], out) == 4

    def test_bad_out_array_raises(self, jpeg_native):
        cells, _ = _jpeg_cells(1)
        with pytest.raises(ValueError, match='uint8'):
            jpeg_native.decode_jpeg_batch(cells,
                                          np.empty((1, 4, 4, 4), np.uint8))

    def test_explicit_mode_argument(self, jpeg_native, monkeypatch):
        """decode_jpeg_batch(cells, out, fancy) overrides the env parse:
        1 is bit-identical to cv2 (fancy), 0 (merged) provably differs on
        4:2:0 cells, and -1 defers to the env default."""
        import cv2
        monkeypatch.delenv('PETASTORM_TPU_JPEG_FANCY', raising=False)
        cells, _ = _jpeg_cells(4)
        fancy_out = np.empty((4, 48, 64, 3), np.uint8)
        merged_out = np.empty((4, 48, 64, 3), np.uint8)
        env_out = np.empty((4, 48, 64, 3), np.uint8)
        assert jpeg_native.decode_jpeg_batch(cells, fancy_out, 1) == 4
        assert jpeg_native.decode_jpeg_batch(cells, merged_out, 0) == 4
        assert jpeg_native.decode_jpeg_batch(cells, env_out, -1) == 4
        refs = np.stack([cv2.imdecode(np.frombuffer(c, np.uint8),
                                      cv2.IMREAD_COLOR_RGB) for c in cells])
        np.testing.assert_array_equal(fancy_out, refs)
        assert (merged_out != fancy_out).any()
        # env unset: -1 means the historical merged default
        np.testing.assert_array_equal(env_out, merged_out)
        # explicit mode wins over a set env var, in both directions
        monkeypatch.setenv('PETASTORM_TPU_JPEG_FANCY', '1')
        assert jpeg_native.decode_jpeg_batch(cells, merged_out, 0) == 4
        assert (merged_out != fancy_out).any()


class TestJpegCodecIntegration:
    def test_codec_batch_bit_exact_with_per_cell(self, monkeypatch):
        from petastorm_tpu.codecs import CompressedImageCodec
        monkeypatch.setenv('PETASTORM_TPU_JPEG_FANCY', '1')  # strict mode
        codec = CompressedImageCodec('jpeg', quality=92)
        field = UnischemaField('im', np.uint8, (48, 64, 3), codec, False)
        cells = [codec.encode(field, img)
                 for img in _jpeg_cells(8, seed=3)[1]]
        batch = codec.decode_batch(field, cells)
        assert isinstance(batch, np.ndarray) and batch.shape == (8, 48, 64, 3)
        for i, cell in enumerate(cells):
            np.testing.assert_array_equal(batch[i], codec.decode(field, cell))

    def test_codec_batch_with_mid_batch_oddball(self):
        # a grayscale cell mid-batch: native rejects it, _decode_into
        # raises on the shape mismatch, the codec falls back to the
        # per-cell list path preserving the odd cell's true shape
        import cv2
        from petastorm_tpu.codecs import CompressedImageCodec
        codec = CompressedImageCodec('jpeg')
        field = UnischemaField('im', np.uint8, (48, 64, 3), codec, False)
        cells = [codec.encode(field, img)
                 for img in _jpeg_cells(5, seed=4)[1]]
        gray = (np.arange(48 * 64, dtype=np.uint8).reshape(48, 64))
        ok, enc = cv2.imencode('.jpeg', gray)
        cells.insert(2, bytearray(enc.tobytes()))
        decoded = codec.decode_batch(field, cells)
        assert isinstance(decoded, list) and len(decoded) == 6
        assert decoded[2].shape == (48, 64)
        assert decoded[0].shape == (48, 64, 3)

    def test_upsampling_auto_calibration(self, jpeg_native, monkeypatch):
        """With the env unset, the first sizeable batch calibrates the
        chroma-upsampling mode (times both, caches the winner) and the
        decoded batch matches that mode's direct native decode exactly."""
        from petastorm_tpu import codecs
        from petastorm_tpu.codecs import CompressedImageCodec
        monkeypatch.delenv('PETASTORM_TPU_JPEG_FANCY', raising=False)
        monkeypatch.setattr(codecs, '_JPEG_FANCY_MODE', None)
        # hermetic: never read/write the real per-host mode cache
        monkeypatch.setattr(codecs, '_jpeg_mode_cache_path', lambda fn: None)
        codec = CompressedImageCodec('jpeg')
        field = UnischemaField('im', np.uint8, (48, 64, 3), codec, False)
        cells = [codec.encode(field, img)
                 for img in _jpeg_cells(8, seed=6)[1]]
        batch = codec.decode_batch(field, cells)
        assert isinstance(batch, np.ndarray) and batch.shape == (8, 48, 64, 3)
        assert codecs._JPEG_FANCY_MODE in (0, 1)
        ref = np.empty_like(batch)
        assert jpeg_native.decode_jpeg_batch(cells, ref,
                                             codecs._JPEG_FANCY_MODE) == 8
        np.testing.assert_array_equal(batch, ref)

    def test_calibration_host_cache_round_trip(self, jpeg_native,
                                               monkeypatch, tmp_path):
        """The calibrated winner persists to a per-host cache file keyed
        by the native build, and a later process (fresh module state)
        restores it without re-timing — run-to-run pixel stability on a
        host (advisor r4)."""
        from petastorm_tpu import codecs
        from petastorm_tpu.codecs import CompressedImageCodec
        monkeypatch.delenv('PETASTORM_TPU_JPEG_FANCY', raising=False)
        cache_file = str(tmp_path / 'jpeg_mode_cache')
        monkeypatch.setattr(codecs, '_jpeg_mode_cache_path',
                            lambda fn: cache_file)
        monkeypatch.setattr(codecs, '_JPEG_FANCY_MODE', None)
        codec = CompressedImageCodec('jpeg')
        field = UnischemaField('im', np.uint8, (48, 64, 3), codec, False)
        cells = [codec.encode(field, img)
                 for img in _jpeg_cells(8, seed=9)[1]]
        codec.decode_batch(field, cells)
        first = codecs._JPEG_FANCY_MODE
        assert first in (0, 1)
        with open(cache_file) as f:
            assert f.read().strip() == str(first)
        # a "new process": poison the cache with the OTHER mode and clear
        # module state — restore must adopt the cached pick, proving no
        # re-calibration happened (timing would likely re-pick `first`)
        with open(cache_file, 'w') as f:
            f.write(str(1 - first))
        monkeypatch.setattr(codecs, '_JPEG_FANCY_MODE', None)
        codec.decode_batch(field, cells)
        assert codecs._JPEG_FANCY_MODE == 1 - first

    def test_cache_path_keyed_by_native_build(self, jpeg_native):
        from petastorm_tpu import codecs
        path = codecs._jpeg_mode_cache_path(jpeg_native.decode_jpeg_batch)
        assert path is not None and 'petastorm_tpu_jpeg_fancy' in path
        # unidentifiable builds opt out of caching rather than colliding
        assert codecs._jpeg_mode_cache_path(len) is None

    def test_forced_env_skips_calibration(self, monkeypatch):
        """A set PETASTORM_TPU_JPEG_FANCY disables calibration entirely
        (the C env parse keeps authority) and =1 stays bit-identical to
        per-cell cv2 decode."""
        from petastorm_tpu import codecs
        from petastorm_tpu.codecs import CompressedImageCodec
        monkeypatch.setenv('PETASTORM_TPU_JPEG_FANCY', '1')
        monkeypatch.setattr(codecs, '_JPEG_FANCY_MODE', None)
        codec = CompressedImageCodec('jpeg')
        field = UnischemaField('im', np.uint8, (48, 64, 3), codec, False)
        cells = [codec.encode(field, img)
                 for img in _jpeg_cells(8, seed=7)[1]]
        batch = codec.decode_batch(field, cells)
        assert codecs._JPEG_FANCY_MODE is None  # calibration never ran
        for i, cell in enumerate(cells):
            np.testing.assert_array_equal(batch[i], codec.decode(field, cell))

    def test_mid_batch_png_cell_keeps_native_tail(self, monkeypatch):
        # a PNG cell in a jpeg-codec batch: native rejects it, cv2 decodes
        # it into its row, and the native loop RE-ENTERS for the tail (the
        # dense array comes back fully populated, not a list). Strict mode
        # so the jpeg rows compare exactly against per-cell decode.
        import cv2
        monkeypatch.setenv('PETASTORM_TPU_JPEG_FANCY', '1')
        from petastorm_tpu.codecs import CompressedImageCodec
        codec = CompressedImageCodec('jpeg')
        field = UnischemaField('im', np.uint8, (48, 64, 3), codec, False)
        images = _jpeg_cells(6, seed=5)[1]
        cells = [codec.encode(field, img) for img in images]
        ok, png = cv2.imencode('.png', cv2.cvtColor(images[3],
                                                    cv2.COLOR_RGB2BGR))
        cells[3] = bytearray(png.tobytes())
        batch = codec.decode_batch(field, cells)
        assert isinstance(batch, np.ndarray) and batch.shape == (6, 48, 64, 3)
        np.testing.assert_array_equal(batch[3], images[3])  # png lossless
        for i in (0, 1, 2, 4, 5):
            np.testing.assert_array_equal(batch[i],
                                          codec.decode(field, cells[i]))


@pytest.fixture(scope='module')
def png_native():
    from petastorm_tpu.native import get_png_module
    module = get_png_module()
    if module is None:
        pytest.skip('native png extension could not be built '
                    '(no libpng dev files?)')
    return module


class TestNativePngDecoder:
    def _png_cells(self, n, h=32, w=32, seed=0):
        import cv2
        rng = np.random.RandomState(seed)
        cells, images = [], []
        for _ in range(n):
            img = rng.randint(0, 255, (h, w, 3), dtype=np.uint8)
            ok, enc = cv2.imencode('.png', cv2.cvtColor(img,
                                                        cv2.COLOR_RGB2BGR))
            assert ok
            cells.append(enc.tobytes())
            images.append(img)
        return cells, images

    def test_lossless_roundtrip(self, png_native):
        cells, images = self._png_cells(6)
        out = np.empty((6, 32, 32, 3), np.uint8)
        assert png_native.decode_png_batch(cells, out) == 6
        for i in range(6):
            np.testing.assert_array_equal(out[i], images[i])

    def test_corrupt_cell_stops_prefix(self, png_native):
        cells, _ = self._png_cells(4)
        cells[1] = cells[1][:30]
        out = np.empty((4, 32, 32, 3), np.uint8)
        assert png_native.decode_png_batch(cells, out) == 1

    def test_gray_or_wrong_size_rejected(self, png_native):
        import cv2
        cells, _ = self._png_cells(2)
        gray = np.arange(32 * 32, dtype=np.uint8).reshape(32, 32)
        ok, enc = cv2.imencode('.png', gray)
        out = np.empty((3, 32, 32, 3), np.uint8)
        assert png_native.decode_png_batch(
            [cells[0], enc.tobytes(), cells[1]], out) == 1
        small = np.empty((2, 16, 16, 3), np.uint8)
        assert png_native.decode_png_batch(cells, small) == 0

    def test_threads_arg_parity_and_prefix(self, png_native):
        cells, images = self._png_cells(9)
        serial = np.empty((9, 32, 32, 3), np.uint8)
        pooled = np.empty_like(serial)
        assert png_native.decode_png_batch(cells, serial) == 9
        assert png_native.decode_png_batch(cells, pooled, 4) == 9
        np.testing.assert_array_equal(serial, pooled)
        bad = list(cells)
        bad[2] = bad[2][:30]
        prefix = np.empty_like(serial)
        assert png_native.decode_png_batch(bad, prefix, 4) == 2
        np.testing.assert_array_equal(prefix[:2], serial[:2])

    def test_internal_pool_takes_one_native_call(self, png_native,
                                                 monkeypatch):
        """With PETASTORM_TPU_IMAGE_DECODER_THREADS > 1 and a current
        build, the codec issues ONE native call carrying the threads
        argument — the C pool fans out; the Python executor is never
        engaged for the batch (the knob must not multiply into
        threads x threads, docs/env_knobs.md)."""
        from petastorm_tpu import codecs
        from petastorm_tpu.codecs import CompressedImageCodec
        monkeypatch.setenv('PETASTORM_TPU_IMAGE_DECODER_THREADS', '3')
        # start from no cached executor so the assertion below really
        # proves the native path never consults one into existence
        monkeypatch.setattr(codecs, '_IMAGE_POOL', None)
        calls = []
        real = png_native.decode_png_batch

        def spy(cells, out, *args):
            calls.append((len(cells), args))
            return real(cells, out, *args)

        monkeypatch.setattr(png_native, 'decode_png_batch', spy)
        monkeypatch.setitem(codecs._NATIVE_THREADS_SUPPORT, spy, True)
        codec = CompressedImageCodec('png')
        field = UnischemaField('im', np.uint8, (32, 32, 3), codec, False)
        cells, images = self._png_cells(8, seed=11)
        batch = codec.decode_batch(field, cells)
        real_calls = [(n, args[:1]) for n, args in calls if n > 0]
        assert real_calls == [(8, (3,))], calls
        # ... and the buffer the pool threads' CPU comes back in
        (cpu_out,) = [args[1] for n, args in calls if n > 0]
        assert cpu_out.dtype == np.int64 and cpu_out[0] >= 0
        # the C pool took the batch, so the Python-side executor was
        # never even created (one pool per batch, docs/env_knobs.md)
        assert codecs._IMAGE_POOL is None
        for i in range(8):
            np.testing.assert_array_equal(batch[i], images[i])

    def test_codec_batch_uses_native_and_matches(self, png_native,
                                                 monkeypatch):
        from petastorm_tpu.codecs import CompressedImageCodec
        calls = []
        real = png_native.decode_png_batch
        monkeypatch.setattr(
            png_native, 'decode_png_batch',
            lambda cells, out: calls.append(len(cells)) or real(cells, out))
        codec = CompressedImageCodec('png')
        field = UnischemaField('im', np.uint8, (32, 32, 3), codec, False)
        images = self._png_cells(8, seed=9)[1]
        cells = [codec.encode(field, img) for img in images]
        batch = codec.decode_batch(field, cells)
        assert calls, 'native png path was not used'
        assert isinstance(batch, np.ndarray) and batch.shape == (8, 32, 32, 3)
        for i in range(8):
            np.testing.assert_array_equal(batch[i], images[i])


def test_concurrent_first_load_waits_for_the_loader(native, monkeypatch):
    """Decode threads hitting the first load together must all get the
    module: a thread that finds the load merely started must wait, not
    fall back to the Python decoders (cv2's jpeg upsampling differs from
    the native calibrated mode, so pixels would depend on timing)."""
    import sys
    import threading
    import time

    from petastorm_tpu import native as native_pkg
    monkeypatch.setattr(native_pkg, '_loaded', {})
    monkeypatch.setattr(native_pkg, '_attempted', set())
    real_find = native_pkg._find_built_extension

    def slow_find(name):
        time.sleep(0.05)   # widen the load window the race lives in
        return real_find(name)

    monkeypatch.setattr(native_pkg, '_find_built_extension', slow_find)
    n = 16
    barrier = threading.Barrier(n)
    got = [None] * n

    def load(i):
        barrier.wait(timeout=10)
        got[i] = native_pkg.get_native_module()

    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        threads = [threading.Thread(target=load, args=(i,))
                   for i in range(n)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=30)
    finally:
        sys.setswitchinterval(interval)
    assert not any(t.is_alive() for t in threads)
    assert all(m is not None for m in got)
    assert len({id(m) for m in got}) == 1
