"""Plain exact-value references for the reader's and the loader's tests.

The fast paths are held to these functions: the late-materializing
row-group load (``arrow_worker``), the statistics planner (``pushdown``)
and the JAX loader's staging arena (``jax/staging``). They are written
against pyarrow, numpy and the codecs alone and import nothing from
``petastorm_tpu.jax``, ``petastorm_tpu.buffers``,
``petastorm_tpu.arrow_worker`` or ``petastorm_tpu.pushdown``, so a fault
in the reader, its buffers or the staging engine cannot show up on both
sides of a comparison.

* :func:`read_rows` reads a parquet store with
  ``pyarrow.parquet.read_table``. Given the dataset's ``schema``, each
  codec field decodes cell by cell through its codec's ``decode``.
  ``filters=`` is evaluated by pyarrow's own filter implementation.
  ``tests/test_pushdown.py`` and ``tests/test_write.py`` compare the
  reader's selective reads with it.
* :func:`matching_rows` keeps the rows a predicate's ``do_include``
  accepts — the reference for predicates that pyarrow cannot express.
* :func:`loader_batches` turns rows (read by :func:`read_rows`, or the
  rows a test wrote) into the batches a JAX loader delivers:
  ``last_batch`` (``drop``/``short``/``pad`` with ``valid_mask``),
  ``dtypes`` casts, ``pad_ragged`` and ``bucket_boundaries`` in numpy,
  then the dtype the device holds. ``tests/test_staging.py``,
  ``tests/test_sharded_staging.py`` and ``tests/test_fused_decode.py``
  compare the loader with it through
  :func:`~petastorm_tpu.test_util.parity.assert_same_rows_by_id`, since
  pools deliver row-groups in completion order.
"""

import numpy as np

#: the column ``last_batch='pad'`` adds (the loader's ``MASK_FIELD``)
MASK = 'valid_mask'
#: suffix of the true-length column ``pad_ragged``/``bucket_boundaries``
#: add (the loader's ``LEN_SUFFIX``)
LEN = '_len'


def read_rows(url, columns=None, schema=None, filters=None):
    """Rows of a local (``file://``) parquet store as ``{name: value}``
    dicts in file order. ``schema`` (a Unischema) names the codec of each
    stored field; its cells decode with ``codec.decode``. Plain columns
    convert with arrow's ``to_pylist``. ``filters`` are DNF filters in
    the ``pyarrow.parquet.read_table`` form."""
    import pyarrow.parquet as pq
    path = url[len('file://'):] if url.startswith('file://') else url
    table = pq.read_table(path, columns=columns, filters=filters)
    decoded = {}
    for name in table.column_names:
        values = table.column(name).to_pylist()
        field = schema.fields.get(name) if schema is not None else None
        if field is not None and field.codec is not None:
            values = [None if v is None else field.codec.decode(field, v)
                      for v in values]
        decoded[name] = values
    return [{name: decoded[name][i] for name in decoded}
            for i in range(table.num_rows)]


def matching_rows(rows, predicate):
    """The rows ``predicate.do_include`` accepts, in order."""
    return [row for row in rows if predicate.do_include(row)]


def loader_batches(rows, batch_size, fields=None, last_batch='drop',
                   dtypes=None, pad_ragged=None, bucket_boundaries=None,
                   num_epochs=1):
    """The numpy batches a JAX loader delivers for ``rows``, with the
    loader's own options. ``fields`` are plain column names. Rows batch
    in the order given (epochs back to back), and only the last batch
    (per bucket) is short; compare with the loader by id."""
    fields = list(fields) if fields is not None else list(rows[0])
    rows = [{name: row[name] for name in fields} for row in rows]
    rows = rows * num_epochs
    for name, targets in (pad_ragged or {}).items():
        template = _template(rows, name)
        rows = [_densify(row, name, _as_tuple(targets), template)
                for row in rows]
    if bucket_boundaries:
        ((name, bounds),) = bucket_boundaries.items()
        template = _template(rows, name)
        groups = {}
        for row in rows:
            length = 0 if row[name] is None else len(row[name])
            bound = next((b for b in bounds if b >= length), bounds[-1])
            groups.setdefault(bound, []).append(
                _densify(row, name, (bound,), template))
        groups = [groups[b] for b in sorted(groups)]
    else:
        groups = [rows]
    batches = []
    for group in groups:
        for start in range(0, len(group), batch_size):
            chunk = group[start:start + batch_size]
            if len(chunk) < batch_size and last_batch == 'drop':
                continue
            batches.append(_batch(chunk, batch_size, last_batch,
                                  dtypes or {}))
    return batches


def _as_tuple(targets):
    return (targets,) if np.ndim(targets) == 0 else tuple(targets)


def _template(rows, name):
    """The first non-None cell of ``name``: its dtype and trailing shape
    stand for a None cell's."""
    return next(np.asarray(row[name]) for row in rows
                if row[name] is not None)


def _densify(row, name, targets, template):
    """``row`` with ``name`` zero-padded (or clipped) to ``targets`` in
    its leading dims and the true size in ``<name>_len``; a None cell is
    all zeros with size 0."""
    k = len(targets)
    cell = row[name]
    cell = (np.zeros((0,) * k + template.shape[k:], template.dtype)
            if cell is None else np.asarray(cell))
    row = dict(row)
    dense = np.zeros(targets + cell.shape[k:], cell.dtype)
    keep = tuple(slice(0, min(cell.shape[d], targets[d])) for d in range(k))
    dense[keep] = cell[keep]
    lens = np.asarray(cell.shape[:k], np.int32)
    row[name] = dense
    row[name + LEN] = lens[0] if k == 1 else lens
    return row


def _batch(chunk, batch_size, last_batch, dtypes):
    n = len(chunk)
    out = {}
    for name in chunk[0]:
        arr = np.stack([np.asarray(row[name]) for row in chunk])
        if name in dtypes:
            arr = arr.astype(dtypes[name])
        if last_batch == 'pad' and n < batch_size:
            pad = np.zeros((batch_size - n,) + arr.shape[1:], arr.dtype)
            arr = np.concatenate([arr, pad])
        out[name] = arr.astype(_device_dtype(arr.dtype))
    if last_batch == 'pad':
        out[MASK] = np.arange(batch_size) < n
    return out


def _device_dtype(dtype):
    """The dtype a host array of ``dtype`` holds once on the device (64-bit
    types narrow unless JAX runs with x64 enabled)."""
    import jax
    return np.dtype(jax.dtypes.canonicalize_dtype(dtype))
