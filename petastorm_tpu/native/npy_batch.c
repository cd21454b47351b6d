/* Native batched NPY decode: the hot inner loop of NdarrayCodec.
 *
 * decode_npy_batch(cells, out): parse each .npy payload's header (magic,
 * version, dict literal) in C and memcpy the raw data into row i of a
 * preallocated output batch — no per-cell Python object churn, no BytesIO,
 * no np.load. Falls back (returns 0 at the failing index) when a cell's
 * dtype/shape disagrees with the output, so the caller can route that cell
 * through the generic Python path.
 *
 * The framework-level rationale (SURVEY.md section 7.1): the reference's
 * native surface lived in its dependencies (pyarrow C++, OpenCV); this
 * framework owns the decode loop, so the batched inner loop is first-party
 * native code.
 */

#define PY_SSIZE_T_CLEAN
#define NPY_NO_DEPRECATED_API NPY_1_7_API_VERSION
#include <Python.h>
#include <numpy/arrayobject.h>
#include <pthread.h>
#include <string.h>
#include <stdint.h>
#include <time.h>

/* same internal-pool clamp as jpeg_batch.c / png_batch.c */
#define PT_MAX_THREADS 32

/* CPU the calling thread has used so far, in ns. A pool thread reads it
 * just before it returns: the pool's CPU, which the caller's own thread
 * clock cannot see. */
static long long
pt_thread_cpu_ns(void)
{
    struct timespec ts;
    if (clock_gettime(CLOCK_THREAD_CPUTIME_ID, &ts) != 0)
        return 0;
    return (long long)ts.tv_sec * 1000000000LL + (long long)ts.tv_nsec;
}

/* Add ns to the int64 held in the first 8 bytes of cpu_out, a writable
 * buffer the caller passes to learn the pool threads' CPU (the calling
 * thread's own CPU is not in it); NULL/None is ignored. Needs the GIL. */
static int
pt_add_cpu_out(PyObject *cpu_out, long long ns)
{
    Py_buffer view;
    long long total;

    if (cpu_out == NULL || cpu_out == Py_None)
        return 0;
    if (PyObject_GetBuffer(cpu_out, &view, PyBUF_WRITABLE) != 0)
        return -1;
    if (view.len < (Py_ssize_t)sizeof(total)) {
        PyBuffer_Release(&view);
        PyErr_SetString(PyExc_ValueError,
                        "cpu_out must be a writable buffer of one int64");
        return -1;
    }
    memcpy(&total, view.buf, sizeof(total));
    total += ns;
    memcpy(view.buf, &total, sizeof(total));
    PyBuffer_Release(&view);
    return 0;
}

static const char NPY_MAGIC[6] = {'\x93', 'N', 'U', 'M', 'P', 'Y'};

/* Parse an npy header; on success sets *data_offset to the payload start
 * and returns the header dict substring (borrowed pointers into buf). */
static int
parse_npy_header(const unsigned char *buf, Py_ssize_t len,
                 Py_ssize_t *data_offset, const char **header,
                 Py_ssize_t *header_len)
{
    uint32_t hlen;
    if (len < 10 || memcmp(buf, NPY_MAGIC, 6) != 0)
        return -1;
    if (buf[6] == 1) {
        hlen = (uint32_t)buf[8] | ((uint32_t)buf[9] << 8);
        *data_offset = 10 + (Py_ssize_t)hlen;
        *header = (const char *)buf + 10;
    } else if (buf[6] == 2 || buf[6] == 3) {
        if (len < 12)
            return -1;
        hlen = (uint32_t)buf[8] | ((uint32_t)buf[9] << 8)
             | ((uint32_t)buf[10] << 16) | ((uint32_t)buf[11] << 24);
        *data_offset = 12 + (Py_ssize_t)hlen;
        *header = (const char *)buf + 12;
    } else {
        return -1;
    }
    if (*data_offset > len)
        return -1;
    *header_len = (Py_ssize_t)hlen;
    return 0;
}

/* Verify the header's fortran_order is False, that its descr matches
 * `descr` (e.g. "<f4"), and that its literal shape entry matches
 * `shape_str` (e.g. "'shape': (2, 3)" — numpy's canonical header repr).
 * A stored cell whose true shape differs from the declared per-row shape
 * but has an equal byte count must NOT be memcpy'd into the declared
 * shape (silent data reinterpretation); shape mismatch routes the cell to
 * the Python decode path, which preserves the true shape and lets
 * collation surface the error. */
static int
header_compatible(const char *header, Py_ssize_t header_len,
                  const char *descr, const char *shape_str)
{
    /* fortran_order must be False: C-contiguous copy only */
    const char *fo = NULL;
    char needle[64];
    size_t descr_len = strlen(descr);
    if (header_len <= 0 || header_len > 65536)
        return 0;
    {
        /* bounded search: header is not NUL-terminated */
        char *tmp = (char *)PyMem_Malloc((size_t)header_len + 1);
        int ok;
        if (tmp == NULL)
            return 0;
        memcpy(tmp, header, (size_t)header_len);
        tmp[header_len] = '\0';
        fo = strstr(tmp, "'fortran_order': False");
        if (fo == NULL)
            fo = strstr(tmp, "\"fortran_order\": False");
        ok = (fo != NULL);
        if (ok && descr_len + 2 < sizeof(needle)) {
            snprintf(needle, sizeof(needle), "'%s'", descr);
            if (strstr(tmp, needle) == NULL) {
                snprintf(needle, sizeof(needle), "\"%s\"", descr);
                ok = (strstr(tmp, needle) != NULL);
            }
        }
        if (ok)
            ok = (strstr(tmp, shape_str) != NULL);
        PyMem_Free(tmp);
        return ok;
    }
}

/* One contiguous range of validated (src, dst) payload copies performed
 * by one pool thread; parallel memcpy engages multiple memory channels,
 * so wide rows batch-copy faster than one core's streaming bandwidth. */
struct pt_npy_task {
    const char *const *srcs;
    char *out_data;
    Py_ssize_t row_bytes;
    Py_ssize_t lo, hi;
    long long cpu_ns;  /* a pool thread's CPU, read as it returns */
};

static void *
pt_npy_worker(void *arg)
{
    struct pt_npy_task *t = (struct pt_npy_task *)arg;
    Py_ssize_t i;

    for (i = t->lo; i < t->hi; i++)
        memcpy(t->out_data + i * t->row_bytes, t->srcs[i],
               (size_t)t->row_bytes);
    return NULL;
}

static void *
pt_npy_thread(void *arg)
{
    pt_npy_worker(arg);
    ((struct pt_npy_task *)arg)->cpu_ns = pt_thread_cpu_ns();
    return NULL;
}

/* decode_npy_batch(cells: sequence of bytes-like or None,
 *                  out: ndarray (n, ...) C-contiguous, writable,
 *                  descr: str like '<f4',
 *                  shape_str: str like "'shape': (2, 3)",
 *                  threads: int = 0,
 *                  cpu_out: writable int64 buffer or None = None)
 * Returns: number of successfully decoded leading cells. A cell that is
 * None or incompatible stops fast-path decoding at its index (caller
 * finishes those via the Python path).
 *
 * Two-phase row-group-batch shape: headers parse and validate under the
 * GIL (cheap, Python buffer API), then every validated payload memcpys
 * with the GIL RELEASED — fanned across an internal pthread pool when
 * `threads > 1` (sized by the caller from
 * PETASTORM_TPU_IMAGE_DECODER_THREADS). */
static PyObject *
decode_npy_batch(PyObject *self, PyObject *args)
{
    PyObject *cells;
    PyArrayObject *out;
    const char *descr;
    const char *shape_str;
    Py_ssize_t n, i, n_ok;
    Py_ssize_t row_bytes;
    char *out_data;
    int threads_arg = 0;
    PyObject *cpu_out = NULL;
    long long pool_cpu_ns = 0;
    Py_buffer *views = NULL;
    const char **srcs = NULL;

    if (!PyArg_ParseTuple(args, "OO!ss|iO", &cells, &PyArray_Type, &out,
                          &descr, &shape_str, &threads_arg, &cpu_out))
        return NULL;
    if (pt_add_cpu_out(cpu_out, 0) != 0)
        return NULL;
    if (!PyArray_IS_C_CONTIGUOUS(out) || !PyArray_ISWRITEABLE(out)) {
        PyErr_SetString(PyExc_ValueError,
                        "out must be C-contiguous and writable");
        return NULL;
    }
    n = PySequence_Length(cells);
    if (n < 0)
        return NULL;
    if (PyArray_DIM(out, 0) < n) {
        PyErr_SetString(PyExc_ValueError, "out batch dimension too small");
        return NULL;
    }
    row_bytes = (Py_ssize_t)(PyArray_NBYTES(out) / (PyArray_DIM(out, 0) > 0
                             ? PyArray_DIM(out, 0) : 1));
    out_data = (char *)PyArray_DATA(out);

    views = PyMem_Calloc((size_t)(n ? n : 1), sizeof(Py_buffer));
    srcs = PyMem_Malloc(sizeof(const char *) * (size_t)(n ? n : 1));
    if (views == NULL || srcs == NULL) {
        PyMem_Free(views);
        PyMem_Free(srcs);
        return PyErr_NoMemory();
    }

    /* phase 1 (GIL held): acquire buffers + validate headers; the
     * decoded prefix ends at the first None/incompatible cell */
    for (i = 0; i < n; i++) {
        PyObject *cell = PySequence_GetItem(cells, i);
        Py_ssize_t data_offset, header_len;
        const char *header;
        int ok;

        if (cell == NULL) {
            PyErr_Clear();
            break;
        }
        if (cell == Py_None) {
            Py_DECREF(cell);
            break;
        }
        if (PyObject_GetBuffer(cell, &views[i], PyBUF_SIMPLE) != 0) {
            PyErr_Clear();
            Py_DECREF(cell);
            break;
        }
        Py_DECREF(cell);
        ok = (parse_npy_header((const unsigned char *)views[i].buf,
                               views[i].len, &data_offset, &header,
                               &header_len) == 0)
             && header_compatible(header, header_len, descr, shape_str)
             && (views[i].len - data_offset == row_bytes);
        if (!ok) {
            PyBuffer_Release(&views[i]);
            break;
        }
        srcs[i] = (const char *)views[i].buf + data_offset;
    }
    n_ok = i;

    /* phase 2 (GIL released): copy every validated payload */
    if (n_ok > 0 && row_bytes > 0) {
        struct pt_npy_task tasks[PT_MAX_THREADS];
        Py_ssize_t n_tasks, t, chunk;

        n_tasks = threads_arg;
        if (n_tasks > PT_MAX_THREADS)
            n_tasks = PT_MAX_THREADS;
        if (n_tasks > n_ok)
            n_tasks = n_ok;
        if (n_tasks < 1)
            n_tasks = 1;
        chunk = (n_ok + n_tasks - 1) / n_tasks;
        for (t = 0; t < n_tasks; t++) {
            tasks[t].srcs = srcs;
            tasks[t].out_data = out_data;
            tasks[t].row_bytes = row_bytes;
            tasks[t].lo = t * chunk;
            tasks[t].hi = (t + 1) * chunk < n_ok ? (t + 1) * chunk : n_ok;
        }
        Py_BEGIN_ALLOW_THREADS
        {
            pthread_t tids[PT_MAX_THREADS];
            int created[PT_MAX_THREADS] = {0};
            for (t = 1; t < n_tasks; t++)
                created[t] = pthread_create(&tids[t], NULL, pt_npy_thread,
                                            &tasks[t]) == 0;
            pt_npy_worker(&tasks[0]);
            for (t = 1; t < n_tasks; t++) {
                if (created[t]) {
                    pthread_join(tids[t], NULL);
                    pool_cpu_ns += tasks[t].cpu_ns;
                } else
                    pt_npy_worker(&tasks[t]);  /* spawn failed: copy inline */
            }
        }
        Py_END_ALLOW_THREADS
    }

    for (i = 0; i < n_ok; i++)
        PyBuffer_Release(&views[i]);
    PyMem_Free(views);
    PyMem_Free(srcs);
    if (pt_add_cpu_out(cpu_out, pool_cpu_ns) != 0)
        return NULL;
    return PyLong_FromSsize_t(n_ok);
}

static PyMethodDef NpyBatchMethods[] = {
    {"decode_npy_batch", decode_npy_batch, METH_VARARGS,
     "decode_npy_batch(cells, out, descr, shape_str, threads=0, "
     "cpu_out=None): batched .npy decode into a preallocated array; "
     "returns the decoded prefix count. Payload memcpys run with the GIL "
     "released, fanned across an internal pthread pool when threads > 1; "
     "cpu_out, a writable int64 buffer, gains the pool threads' CPU ns"},
    {NULL, NULL, 0, NULL}
};

static struct PyModuleDef npy_batch_module = {
    PyModuleDef_HEAD_INIT, "_npy_batch",
    "Native batched NPY decoder", -1, NpyBatchMethods
};

PyMODINIT_FUNC
PyInit__npy_batch(void)
{
    PyObject *m = PyModule_Create(&npy_batch_module);
    if (m == NULL)
        return NULL;
    import_array();
    return m;
}
