/* Native batched JPEG decode: the hot inner loop of CompressedImageCodec.
 *
 * decode_jpeg_batch(cells, out, fancy=-1, threads=0): decode each JPEG
 * cell straight into row i of a preallocated (N, H, W, 3) uint8 batch
 * with libjpeg(-turbo), RGB output, ISLOW DCT (turbo's SIMD path). The
 * whole loop runs with the GIL RELEASED in one native call: no per-cell
 * Python dispatch, no thread-pool task churn, no intermediate
 * Mat/ndarray per cell. `threads > 1` fans the cells across an internal
 * pthread pool (one decompress object + row scratch per thread, disjoint
 * output rows) — true row-group-batch decode without Python-side task
 * churn or GIL round trips between chunks; the pool is sized by the
 * caller from PETASTORM_TPU_IMAGE_DECODER_THREADS so the C pool and the
 * Python-side executor never multiply.
 *
 * Upsampling policy: WHICH of libjpeg's two 4:2:0/4:2:2 chroma paths is
 * faster depends on the host's libjpeg build — merged upsampling skips a
 * pass, but libjpeg-turbo SIMD-vectorizes the fancy (triangle-filter)
 * upsampler while its merged RGB path is scalar on some configurations.
 * (Single-run timings on the shared dev boxes running this file have
 * shown BOTH orderings by large factors, which interleaved re-measurement
 * exposed as machine noise — hence measure-don't-assume, and measure
 * robustly.) The optional third argument `fancy` selects the mode
 * explicitly: 1 = fancy (bit-identical to OpenCV's imdecode of the same
 * bytes — both ride libjpeg; the mode the bit-exactness tests pin),
 * 0 = merged, and -1 (default) defers to the PETASTORM_TPU_JPEG_FANCY
 * env var (unset or 0 = merged). The Python caller
 * (codecs._native_image_batch) times both modes interleaved once per
 * process on the first real batch and passes the winner; direct C
 * callers keep the env-driven contract. 4:4:4 jpegs have no upsampling
 * step and decode identically either way.
 *
 * Returns the count of successfully decoded leading cells; a cell that is
 * not an 8-bit 3-component JPEG of exactly the declared (H, W) stops the
 * loop, and the caller routes the remainder through the generic cv2 path
 * (same prefix-count contract as npy_batch.c).
 *
 * Framework rationale (SURVEY.md section 7.3): jpeg decode throughput is
 * where the imagenet-style input rate is won or lost; the reference left
 * this loop to per-cell OpenCV calls (petastorm/codecs.py:102-130) — here
 * it is first-party native code.
 */

#define PY_SSIZE_T_CLEAN
#include <Python.h>
#include <pthread.h>
#include <setjmp.h>
#include <stddef.h>
#include <stdio.h>
#include <stdlib.h>
#include <string.h>
#include <strings.h>  /* strcasecmp: POSIX, not ISO string.h */
#include <time.h>
#include <jpeglib.h>

/* clamp on the internal decode pool: beyond this, thread-spawn cost and
 * memory-bandwidth contention dominate any decode parallelism win */
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

struct pt_jpeg_error_mgr {
    struct jpeg_error_mgr pub;
    jmp_buf setjmp_buffer;
};

static void
pt_error_exit(j_common_ptr cinfo)
{
    struct pt_jpeg_error_mgr *err = (struct pt_jpeg_error_mgr *)cinfo->err;
    longjmp(err->setjmp_buffer, 1);
}

static void
pt_emit_message(j_common_ptr cinfo, int msg_level)
{
    /* decode warnings (e.g. premature EOF) must not write to stderr from
     * a data-loader hot loop; corrupt-data errors still longjmp out */
    (void)cinfo;
    (void)msg_level;
}

/* Decode one cell with a REUSED decompress object (created once per
 * batch: jpeg_create_decompress allocates its memory pools, and paying
 * that per cell is pure overhead in a batch loop). On success returns 0
 * and the object is ready for the next cell; on mismatch/corruption
 * returns -1 after jpeg_abort_decompress (which resets the object's
 * state while keeping its pools). The caller owns create/destroy and the
 * setjmp target: a longjmp from inside libjpeg lands in the caller so
 * the object can be destroyed exactly once.
 *
 * rows[] is a caller-provided scratch of >= height JSAMPROWs: handing
 * jpeg_read_scanlines the full remaining window per call lets turbo
 * process rec_outbuf_height rows per call instead of one. */
static int
decode_one(struct jpeg_decompress_struct *cinfo, const unsigned char *buf,
           size_t len, unsigned char *dst, int height, int width,
           JSAMPROW *rows, boolean fancy_upsampling, J_DCT_METHOD dct)
{
    size_t stride = (size_t)width * 3;
    int r;

    jpeg_mem_src(cinfo, buf, (unsigned long)len);
    if (jpeg_read_header(cinfo, TRUE) != JPEG_HEADER_OK) {
        jpeg_abort_decompress(cinfo);
        return -1;
    }
    if (cinfo->data_precision != 8 || cinfo->num_components != 3) {
        /* grayscale / CMYK / 12-bit: the Python path owns these */
        jpeg_abort_decompress(cinfo);
        return -1;
    }
    cinfo->out_color_space = JCS_RGB;
    /* FALSE selects merged chroma upsampling (the fast path); see the
     * module comment for the policy and the env escape hatch */
    cinfo->do_fancy_upsampling = fancy_upsampling;
    cinfo->dct_method = dct;
    jpeg_start_decompress(cinfo);
    if ((int)cinfo->output_height != height
        || (int)cinfo->output_width != width
        || cinfo->output_components != 3) {
        jpeg_abort_decompress(cinfo);
        return -1;
    }
    for (r = 0; r < height; r++)
        rows[r] = dst + (size_t)r * stride;
    while (cinfo->output_scanline < cinfo->output_height) {
        JDIMENSION done = cinfo->output_scanline;
        jpeg_read_scanlines(cinfo, rows + done,
                            cinfo->output_height - done);
    }
    jpeg_finish_decompress(cinfo);
    return 0;
}

/* One contiguous cell range decoded by one pool thread: each worker owns
 * its decompress object, error jmp target and row-pointer scratch, so the
 * only shared state is the disjoint output rows. `fail` is the first
 * index in [lo, hi) whose cell was rejected (== hi when the whole range
 * decoded); the dispatcher folds the per-range failures back into the
 * batch-wide decoded-prefix contract. */
struct pt_jpeg_task {
    const Py_buffer *views;
    unsigned char *out_base;
    size_t row_bytes;
    Py_ssize_t lo, hi;
    Py_ssize_t fail;
    int height, width;
    boolean fancy;
    J_DCT_METHOD dct;
    long long cpu_ns;  /* a pool thread's CPU, read as it returns */
};

static void *
pt_jpeg_worker(void *arg)
{
    struct pt_jpeg_task *t = (struct pt_jpeg_task *)arg;
    struct jpeg_decompress_struct cinfo;
    struct pt_jpeg_error_mgr jerr;
    JSAMPROW *rows;
    /* mutated between setjmp and a possible longjmp: must be volatile or
     * its post-longjmp value is indeterminate */
    volatile Py_ssize_t i_v = t->lo;

    t->fail = t->lo;
    rows = (JSAMPROW *)malloc(sizeof(JSAMPROW)
                              * (size_t)(t->height ? t->height : 1));
    if (rows == NULL)
        return NULL;
    cinfo.err = jpeg_std_error(&jerr.pub);
    jerr.pub.error_exit = pt_error_exit;
    jerr.pub.emit_message = pt_emit_message;
    if (setjmp(jerr.setjmp_buffer) == 0) {
        jpeg_create_decompress(&cinfo);
        for (; i_v < t->hi; i_v = i_v + 1) {
            const Py_buffer *v = &t->views[i_v];
            if (decode_one(&cinfo, (const unsigned char *)v->buf,
                           (size_t)v->len,
                           t->out_base + (size_t)i_v * t->row_bytes,
                           t->height, t->width, rows, t->fancy,
                           t->dct) != 0)
                break;
        }
        t->fail = i_v;
    } else {
        /* corrupt-data longjmp mid-cell: that cell is the failure */
        t->fail = i_v;
    }
    jpeg_destroy_decompress(&cinfo);
    free(rows);
    return NULL;
}

static void *
pt_jpeg_thread(void *arg)
{
    pt_jpeg_worker(arg);
    ((struct pt_jpeg_task *)arg)->cpu_ns = pt_thread_cpu_ns();
    return NULL;
}

/* Fan n_views cells across `threads` pool threads (the calling thread is
 * worker 0) and fold the per-range failures into the batch-wide decoded
 * prefix: the first rejected index overall. Cells after that index in
 * OTHER ranges were decoded too — harmless, the caller may re-dispatch
 * them — but the prefix contract only promises the leading run. Adds the
 * pool threads' CPU to *pool_cpu_ns. Runs with the GIL released; workers
 * never touch the Python API. */
static Py_ssize_t
pt_jpeg_run(struct pt_jpeg_task *tasks, Py_ssize_t n_tasks,
            Py_ssize_t n_views, long long *pool_cpu_ns)
{
    pthread_t tids[PT_MAX_THREADS];
    int created[PT_MAX_THREADS] = {0};
    Py_ssize_t t, decoded;

    for (t = 1; t < n_tasks; t++) {
        if (pthread_create(&tids[t], NULL, pt_jpeg_thread,
                           &tasks[t]) != 0) {
            /* spawn failure: this range decodes 0 cells (fail == lo,
             * pre-set by the dispatcher) and the prefix fold below
             * reports it honestly */
            tasks[t].fail = tasks[t].lo;
            continue;
        }
        created[t] = 1;
    }
    pt_jpeg_worker(&tasks[0]);
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
decode_jpeg_batch(PyObject *self, PyObject *args)
{
    PyObject *cells;
    PyObject *out_obj;
    Py_buffer out_view;
    Py_ssize_t n, i, decoded;
    Py_buffer *views = NULL;
    int height, width;
    int fancy_arg = -1;
    int threads_arg = 0;
    PyObject *cpu_out = NULL;
    long long pool_cpu_ns = 0;

    (void)self;
    if (!PyArg_ParseTuple(args, "OO|iiO", &cells, &out_obj, &fancy_arg,
                          &threads_arg, &cpu_out))
        return NULL;
    if (pt_add_cpu_out(cpu_out, 0) != 0)
        return NULL;
    /* C-contiguous + ND so shape[] is populated (a plain "w*" request
     * yields a 1-D view with no shape information) */
    if (PyObject_GetBuffer(out_obj, &out_view,
                           PyBUF_WRITABLE | PyBUF_ND
                           | PyBUF_C_CONTIGUOUS) != 0)
        return NULL;

    /* out must be a C-contiguous writable (N, H, W, 3) uint8 buffer */
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

    /* acquire every cell's buffer up front (needs the GIL), then run the
     * whole decode loop without it */
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
            PyErr_Clear();  /* non-buffer cell: decode the prefix only */
            break;
        }
    }
    {
        Py_ssize_t n_views = i;
        size_t row_bytes = (size_t)height * (size_t)width * 3;
        unsigned char *out_base = (unsigned char *)out_view.buf;
        struct pt_jpeg_task tasks[PT_MAX_THREADS];
        Py_ssize_t n_tasks, t, chunk;
        boolean fancy;
        J_DCT_METHOD dct;

        if (fancy_arg >= 0) {
            /* caller-selected mode (the Python calibration path) */
            fancy = fancy_arg ? TRUE : FALSE;
        } else {
            /* value-parsed, not presence-tested: FANCY=0 / FANCY=
             * must keep the merged default (docs say "set ...=1") */
            const char *fancy_env = getenv("PETASTORM_TPU_JPEG_FANCY");
            fancy = (fancy_env != NULL && fancy_env[0] != '\0'
                     && strcmp(fancy_env, "0") != 0) ? TRUE : FALSE;
        }
        /* DCT selector: "ifast" opts into turbo's fast integer DCT
         * (a further ~few-%% rate win at a small accuracy cost some
         * tf.data imagenet pipelines also take via INTEGER_FAST);
         * default ISLOW — turbo's SIMD path, and the method cv2 /
         * tf.data use by default, keeping the bit-exactness contract
         * under PETASTORM_TPU_JPEG_FANCY=1 intact. */
        {
            const char *dct_env = getenv("PETASTORM_TPU_JPEG_DCT");
            dct = (dct_env != NULL && strcasecmp(dct_env, "ifast") == 0)
                      ? JDCT_IFAST : JDCT_ISLOW;
        }
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
            tasks[t].fancy = fancy;
            tasks[t].dct = dct;
        }
        Py_BEGIN_ALLOW_THREADS
        decoded = pt_jpeg_run(tasks, n_tasks, n_views, &pool_cpu_ns);
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

static PyMethodDef jpeg_batch_methods[] = {
    {"decode_jpeg_batch", decode_jpeg_batch, METH_VARARGS,
     "decode_jpeg_batch(cells, out, fancy=-1, threads=0, cpu_out=None): "
     "batched RGB JPEG decode into a preallocated (N,H,W,3) uint8 array; "
     "returns the decoded prefix count. fancy: 1 = fancy upsampling "
     "(cv2-bit-identical), 0 = merged, -1 = PETASTORM_TPU_JPEG_FANCY env "
     "default. threads > 1 fans the cells across an internal pthread "
     "pool (GIL released; one decompress object per thread); cpu_out, a "
     "writable int64 buffer, gains the pool threads' CPU ns"},
    {NULL, NULL, 0, NULL}
};

static struct PyModuleDef jpeg_batch_module = {
    PyModuleDef_HEAD_INIT, "_jpeg_batch",
    "Native batched JPEG decoder (libjpeg-turbo)", -1, jpeg_batch_methods,
    NULL, NULL, NULL, NULL
};

PyMODINIT_FUNC
PyInit__jpeg_batch(void)
{
    return PyModule_Create(&jpeg_batch_module);
}
