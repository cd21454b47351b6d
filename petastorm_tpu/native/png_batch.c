/* Native batched PNG decode: the 8-bit RGB fast path of
 * CompressedImageCodec, sibling of jpeg_batch.c.
 *
 * decode_png_batch(cells, out, threads=0): decode each PNG cell straight
 * into row i of a preallocated (N, H, W, 3) uint8 batch with libpng —
 * PNG stores RGB natively, so rows land in the output with no channel
 * conversion at all, bit-identical to the cv2 path. The whole loop runs
 * with the GIL RELEASED in one native call; per cell, libpng's own
 * decode cost equals cv2's (~10us for a 32x32 cell, measured), so the
 * win is the removed per-cell Python dispatch/alloc (~5us/cell, ~40% of
 * the small-image path). `threads > 1` fans the cells across an internal
 * pthread pool (sized by the caller from
 * PETASTORM_TPU_IMAGE_DECODER_THREADS), decoding disjoint output rows in
 * parallel with zero Python-side task churn.
 *
 * Returns the count of successfully decoded leading cells; a cell that is
 * not a non-interlaced 8-bit RGB PNG of exactly the declared (H, W) stops
 * the loop, and the caller routes the remainder through the generic cv2
 * path (same prefix-count contract as jpeg_batch.c / npy_batch.c).
 *
 * Framework rationale (SURVEY.md section 7.3): the hello-world headline
 * rate is png-decode-bound; the reference left this loop to per-cell
 * OpenCV calls (petastorm/codecs.py:102-130) — here it is first-party
 * native code.
 */

#define PY_SSIZE_T_CLEAN
#include <Python.h>
#include <pthread.h>
#include <setjmp.h>
#include <stddef.h>
#include <string.h>
#include <time.h>
#include <png.h>

/* same internal-pool clamp as jpeg_batch.c */
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

struct pt_mem_reader {
    const unsigned char *data;
    size_t len;
    size_t pos;
};

static void
pt_read_fn(png_structp png, png_bytep out, png_size_t n)
{
    struct pt_mem_reader *r = (struct pt_mem_reader *)png_get_io_ptr(png);
    if (r->pos + n > r->len)
        png_error(png, "premature end of PNG data");
    memcpy(out, r->data + r->pos, n);
    r->pos += n;
}

static void
pt_png_warn(png_structp png, png_const_charp msg)
{
    /* no stderr chatter from a data-loader hot loop */
    (void)png;
    (void)msg;
}

static void
pt_png_error(png_structp png, png_const_charp msg)
{
    /* libpng's DEFAULT error handler prints to stderr before jumping;
     * corrupt cells are an expected input here (they fall back to the
     * Python path), so jump silently */
    (void)msg;
    png_longjmp(png, 1);
}

/* Decode one cell; returns 0 on success, -1 on mismatch/corruption. */
static int
decode_one(const unsigned char *buf, size_t len, unsigned char *dst,
           int height, int width)
{
    struct pt_mem_reader rd = { buf, len, 0 };
    png_structp png;
    png_infop info;
    int r;

    png = png_create_read_struct(PNG_LIBPNG_VER_STRING, NULL, pt_png_error,
                                 pt_png_warn);
    if (png == NULL)
        return -1;
    info = png_create_info_struct(png);
    if (info == NULL) {
        png_destroy_read_struct(&png, NULL, NULL);
        return -1;
    }
    if (setjmp(png_jmpbuf(png))) {
        png_destroy_read_struct(&png, &info, NULL);
        return -1;
    }
    png_set_read_fn(png, &rd, pt_read_fn);
    png_read_info(png, info);
    if (png_get_color_type(png, info) != PNG_COLOR_TYPE_RGB
        || png_get_bit_depth(png, info) != 8
        || png_get_interlace_type(png, info) != PNG_INTERLACE_NONE
        || (int)png_get_image_height(png, info) != height
        || (int)png_get_image_width(png, info) != width) {
        /* grayscale / palette / RGBA / 16-bit / interlaced / wrong size:
         * the Python path owns these */
        png_destroy_read_struct(&png, &info, NULL);
        return -1;
    }
    for (r = 0; r < height; r++)
        png_read_row(png, dst + (size_t)r * (size_t)width * 3, NULL);
    png_destroy_read_struct(&png, &info, NULL);
    return 0;
}

/* One contiguous cell range decoded by one pool thread; decode_one is
 * fully self-contained (per-cell libpng read struct, local jmp buffer),
 * so the only shared state is the disjoint output rows. `fail` is the
 * first rejected index in [lo, hi) (== hi when the range decoded). */
struct pt_png_task {
    const Py_buffer *views;
    unsigned char *out_base;
    size_t row_bytes;
    Py_ssize_t lo, hi;
    Py_ssize_t fail;
    int height, width;
    long long cpu_ns;  /* a pool thread's CPU, read as it returns */
};

static void *
pt_png_worker(void *arg)
{
    struct pt_png_task *t = (struct pt_png_task *)arg;
    Py_ssize_t i;

    for (i = t->lo; i < t->hi; i++) {
        const Py_buffer *v = &t->views[i];
        if (decode_one((const unsigned char *)v->buf, (size_t)v->len,
                       t->out_base + (size_t)i * t->row_bytes,
                       t->height, t->width) != 0)
            break;
    }
    t->fail = i;
    return NULL;
}

static void *
pt_png_thread(void *arg)
{
    pt_png_worker(arg);
    ((struct pt_png_task *)arg)->cpu_ns = pt_thread_cpu_ns();
    return NULL;
}

/* Fan the ranges across pool threads (calling thread = worker 0) and
 * fold per-range failures into the batch-wide decoded prefix — the
 * first rejected index overall (same contract as jpeg_batch.c); adds
 * the pool threads' CPU to *pool_cpu_ns. */
static Py_ssize_t
pt_png_run(struct pt_png_task *tasks, Py_ssize_t n_tasks,
           Py_ssize_t n_views, long long *pool_cpu_ns)
{
    pthread_t tids[PT_MAX_THREADS];
    int created[PT_MAX_THREADS] = {0};
    Py_ssize_t t, decoded;

    for (t = 1; t < n_tasks; t++) {
        if (pthread_create(&tids[t], NULL, pt_png_thread, &tasks[t]) != 0) {
            tasks[t].fail = tasks[t].lo;
            continue;
        }
        created[t] = 1;
    }
    pt_png_worker(&tasks[0]);
    for (t = 1; t < n_tasks; t++) {
        if (created[t]) {
            pthread_join(tids[t], NULL);
            *pool_cpu_ns += tasks[t].cpu_ns;
        }
    }
    decoded = n_views;
    for (t = 0; t < n_tasks; t++) {
        if (tasks[t].fail < tasks[t].hi && tasks[t].fail < decoded)
            decoded = tasks[t].fail;
    }
    return decoded;
}

static PyObject *
decode_png_batch(PyObject *self, PyObject *args)
{
    PyObject *cells;
    PyObject *out_obj;
    Py_buffer out_view;
    Py_ssize_t n, i, decoded;
    Py_buffer *views = NULL;
    int height, width;
    int threads_arg = 0;
    PyObject *cpu_out = NULL;
    long long pool_cpu_ns = 0;

    (void)self;
    if (!PyArg_ParseTuple(args, "OO|iO", &cells, &out_obj, &threads_arg,
                          &cpu_out))
        return NULL;
    if (pt_add_cpu_out(cpu_out, 0) != 0)
        return NULL;
    if (PyObject_GetBuffer(out_obj, &out_view,
                           PyBUF_WRITABLE | PyBUF_ND
                           | PyBUF_C_CONTIGUOUS) != 0)
        return NULL;

    if (out_view.ndim != 4 || out_view.itemsize != 1
        || out_view.shape[3] != 3) {
        PyBuffer_Release(&out_view);
        PyErr_SetString(PyExc_ValueError,
                        "out must be a C-contiguous (N, H, W, 3) uint8 array");
        return NULL;
    }
    n = out_view.shape[0];
    height = (int)out_view.shape[1];
    width = (int)out_view.shape[2];

    if (!PySequence_Check(cells) || PySequence_Size(cells) != n) {
        PyBuffer_Release(&out_view);
        PyErr_SetString(PyExc_ValueError,
                        "cells must be a sequence matching out's batch dim");
        return NULL;
    }

    views = PyMem_Calloc((size_t)(n ? n : 1), sizeof(Py_buffer));
    if (views == NULL) {
        PyBuffer_Release(&out_view);
        return PyErr_NoMemory();
    }
    for (i = 0; i < n; i++) {
        PyObject *cell = PySequence_GetItem(cells, i);
        int rc;
        if (cell == NULL) {
            PyErr_Clear();  /* decode the prefix; Python path owns the rest */
            break;
        }
        rc = PyObject_GetBuffer(cell, &views[i], PyBUF_SIMPLE);
        Py_DECREF(cell);
        if (rc != 0) {
            PyErr_Clear();
            break;
        }
    }
    {
        Py_ssize_t n_views = i;
        size_t row_bytes = (size_t)height * (size_t)width * 3;
        unsigned char *out_base = (unsigned char *)out_view.buf;
        struct pt_png_task tasks[PT_MAX_THREADS];
        Py_ssize_t n_tasks, t, chunk;

        n_tasks = threads_arg;
        if (n_tasks > PT_MAX_THREADS)
            n_tasks = PT_MAX_THREADS;
        if (n_tasks > n_views)
            n_tasks = n_views;
        if (n_tasks < 1)
            n_tasks = 1;
        chunk = (n_views + n_tasks - 1) / (n_tasks ? n_tasks : 1);
        for (t = 0; t < n_tasks; t++) {
            tasks[t].views = views;
            tasks[t].out_base = out_base;
            tasks[t].row_bytes = row_bytes;
            tasks[t].lo = t * chunk;
            tasks[t].hi = (t + 1) * chunk < n_views
                              ? (t + 1) * chunk : n_views;
            tasks[t].fail = tasks[t].lo;
            tasks[t].height = height;
            tasks[t].width = width;
        }
        Py_BEGIN_ALLOW_THREADS
        decoded = pt_png_run(tasks, n_tasks, n_views, &pool_cpu_ns);
        Py_END_ALLOW_THREADS

        for (i = 0; i < n_views; i++)
            PyBuffer_Release(&views[i]);
    }
    PyMem_Free(views);
    PyBuffer_Release(&out_view);
    if (pt_add_cpu_out(cpu_out, pool_cpu_ns) != 0)
        return NULL;
    return PyLong_FromSsize_t(decoded);
}

static PyMethodDef png_batch_methods[] = {
    {"decode_png_batch", decode_png_batch, METH_VARARGS,
     "decode_png_batch(cells, out, threads=0, cpu_out=None): batched RGB "
     "PNG decode into a preallocated (N,H,W,3) uint8 array; returns the "
     "decoded prefix count. threads > 1 fans the cells across an internal "
     "pthread pool (GIL released); cpu_out, a writable int64 buffer, "
     "gains the pool threads' CPU ns"},
    {NULL, NULL, 0, NULL}
};

static struct PyModuleDef png_batch_module = {
    PyModuleDef_HEAD_INIT, "_png_batch",
    "Native batched PNG decoder (libpng)", -1, png_batch_methods,
    NULL, NULL, NULL, NULL
};

PyMODINIT_FUNC
PyInit__png_batch(void)
{
    return PyModule_Create(&png_batch_module);
}
