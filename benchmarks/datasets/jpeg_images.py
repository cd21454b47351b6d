"""ImageNet-shaped JPEG rows: the dataset that ``"dataset": {"kind":
"jpeg_images", ...}`` names, generated from the seed, its plain read-back,
and the comparison of delivered rows with that read-back.

Rows as in ``chip_smoke.build_dataset`` (PR 21): JPEG (quality 90) images
of low-frequency content plus noise, about 25 KB each at 224x224x3, with
an int32 ``label`` in ``[0, n_classes)`` and an int32 ``id``. Each
row-group draws its content from ``(seed, row-group index)`` alone, so the
same seed gives the same bytes whatever the number of worker processes.
The noise is drawn as integers in ``[0, noise)`` rather than scaled floats:
the same sizes at a tenth of the generation time.

:func:`read_rows` is the plain reference read: pyarrow and cv2 only, none
of the program's reader, codecs or native decoders. :func:`compare_rows`
holds labels exactly and images by their luma: the program's JPEG decoder
may pick libjpeg's merged chroma upsampling where cv2 uses the fancy one;
the two differ in chroma alone, so luma compares the decode and not that
choice.
"""

import collections
import os

import numpy as np

from lib import pool

BASE_GRID = 8      # low-frequency content: an 8x8 grid, cubic-upsampled
BASE_LEVELS = 180  # its values lie in [0, 180)
NOISE_LEVELS = 60  # per-row noise in [0, 60)


def _rowgroup(task):
    """Encoded rows of row-group ``index``: ``(ids, labels, jpeg bytes)``."""
    seed, index, rows, shape, quality, n_classes = task
    import cv2
    rng = np.random.default_rng([seed, index])
    base = rng.integers(0, BASE_LEVELS, (BASE_GRID, BASE_GRID, shape[2]),
                        dtype=np.uint8)
    smooth = cv2.resize(base, (shape[1], shape[0]),
                        interpolation=cv2.INTER_CUBIC).astype(np.int16)
    labels = rng.integers(0, n_classes, rows, dtype=np.int32)
    encoded = []
    for _ in range(rows):
        noise = rng.integers(0, NOISE_LEVELS, shape, dtype=np.int16)
        image = np.clip(smooth + noise, 0, 255).astype(np.uint8)
        bgr = cv2.cvtColor(image, cv2.COLOR_RGB2BGR)
        ok, jpeg = cv2.imencode('.jpg', bgr,
                                [int(cv2.IMWRITE_JPEG_QUALITY), quality])
        if not ok:
            raise RuntimeError('cv2.imencode failed')
        encoded.append(jpeg.tobytes())
    ids = np.arange(index * rows, (index + 1) * rows, dtype=np.int32)
    return ids, labels, encoded


def tiny(dataset):
    """``dataset`` cut to a size a CPU test run holds: 256 rows of 32px
    images, 16 to a row-group, in four files."""
    return dict(dataset, rows=256, rowgroup_rows=16, files=4,
                image_shape=[32, 32, 3])


def generate(directory, seed, dataset, workers):
    """Write the dataset that ``dataset`` (the configuration's ``dataset``
    group) describes under ``directory`` and return its URL.

    Row-groups are encoded in ``workers`` spawned processes (numpy and cv2
    only: they never touch JAX) and written in order, ``rows / files`` rows
    to a file, as Parquet with the program's metadata footer."""
    import pyarrow as pa
    import pyarrow.parquet as pq

    shape = tuple(dataset['image_shape'])
    rg_rows = dataset['rowgroup_rows']
    n_groups = dataset['rows'] // rg_rows
    per_file = n_groups // dataset['files']
    if n_groups * rg_rows != dataset['rows'] or per_file * dataset[
            'files'] != n_groups:
        raise ValueError('rows must split evenly into row-groups and files')
    tasks = [(seed, g, rg_rows, shape, dataset['jpeg_quality'],
              dataset['n_classes']) for g in range(n_groups)]
    arrow_schema = pa.schema([('id', pa.int32()), ('label', pa.int32()),
                              ('image', pa.binary())])
    os.makedirs(directory)
    writer = None
    encoded = pool.map_file(os.path.abspath(__file__), '_rowgroup', tasks,
                            workers)
    for g, (ids, labels, images) in enumerate(encoded):
        if g % per_file == 0:
            if writer is not None:
                writer.close()
            writer = pq.ParquetWriter(
                os.path.join(directory, 'part-%05d.parquet'
                             % (g // per_file)), arrow_schema)
        writer.write_table(pa.table(
            [pa.array(ids), pa.array(labels),
             pa.array(images, pa.binary())], schema=arrow_schema),
            row_group_size=rg_rows)
    writer.close()
    url = 'file://' + os.path.abspath(directory)
    _write_footer(url, shape, dataset['jpeg_quality'])
    return url


def _write_footer(url, shape, quality):
    """The program's dataset metadata (its Unischema) over the files
    :func:`generate` wrote, so that its reader can open them."""
    import pyarrow as pa

    from petastorm_tpu.codecs import CompressedImageCodec, ScalarCodec
    from petastorm_tpu.etl.dataset_metadata import materialize_dataset
    from petastorm_tpu.unischema import Unischema, UnischemaField
    schema = Unischema('ImagenetBench', [
        UnischemaField('id', np.int32, (), ScalarCodec(pa.int32()), False),
        UnischemaField('label', np.int32, (), ScalarCodec(pa.int32()),
                       False),
        UnischemaField('image', np.uint8, shape,
                       CompressedImageCodec('jpeg', quality=quality), False),
    ])
    with materialize_dataset(url, schema):
        pass


def read_rows(directory, ids, dataset):
    """``{id: (label, RGB uint8 image)}`` for ``ids``, read with pyarrow
    (only the row-groups that hold them) and decoded with cv2."""
    import cv2
    import pyarrow.parquet as pq
    rg_rows = dataset['rowgroup_rows']
    per_file = dataset['rows'] // rg_rows // dataset['files']
    wanted = collections.defaultdict(set)
    for i in ids:
        wanted[int(i) // rg_rows].add(int(i))
    rows = {}
    for group, members in sorted(wanted.items()):
        table = pq.ParquetFile(os.path.join(
            directory, 'part-%05d.parquet' % (group // per_file))
        ).read_row_group(group % per_file)
        for i, label, jpeg in zip(table['id'].to_pylist(),
                                  table['label'].to_pylist(),
                                  table['image'].to_pylist()):
            if i in members:
                bgr = cv2.imdecode(np.frombuffer(jpeg, np.uint8),
                                   cv2.IMREAD_COLOR)
                rows[i] = (label, cv2.cvtColor(bgr, cv2.COLOR_BGR2RGB))
    return rows


LUMA = np.array([0.299, 0.587, 0.114], np.float32)


def luma(images):
    """ITU-R BT.601 luma of RGB uint8 images, as float32."""
    return np.asarray(images, np.float32) @ LUMA


def compare_rows(samples, plain):
    """``samples``: host copies of delivered batches, each a dict with
    ``id``, ``label`` and ``image`` (uint8). ``plain``: ``{id: (label,
    image)}`` from :func:`read_rows`. Returns ``{name: (value, detail)}``:
    labels that differ (or ids the plain read does not hold) and the
    largest mean absolute luma difference of a row."""
    label_errors, luma_gap = 0, 0.0
    for sample in samples:
        for i, label, image in zip(sample['id'], sample['label'],
                                   sample['image']):
            if int(i) not in plain:
                label_errors += 1
                continue
            want_label, want_image = plain[int(i)]
            label_errors += int(label) != want_label
            luma_gap = max(luma_gap, float(np.mean(np.abs(
                luma(image) - luma(want_image)))))
    return {'label_errors': (label_errors, None),
            'luma_gap': (luma_gap, None)}
