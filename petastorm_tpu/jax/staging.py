"""Zero-copy staging arena + double-buffered async host→device transfer.

The final hop of the JAX path: every batch the loader emits goes through a
:class:`StagingEngine`, which collates, pads and casts it straight into
the buffers the transfer reads (never an ``astype``/``np.concatenate``
intermediate) and dispatches the transfer without waiting for it. The
engine picks the cheapest correct strategy per batch and per backend:

* **Direct dispatch** — a batch that needs NO assembly (one chunk view, no
  dtype cast, no tail pad) skips every copy: the source views go straight
  to ``put_fn`` (``jax.device_put`` or the sharded
  ``make_array_from_process_local_data`` build), which transfers
  asynchronously. The source chunk is never written again, so this is safe
  on every backend. Under ``last_batch='pad'`` a full batch rides with one
  shared immutable all-true ``valid_mask``.
* **Arena ring** (accelerator backends) — per batch signature (field
  shapes + target dtypes; one signature per bucket under
  ``bucket_boundaries``), a ring of ``PETASTORM_TPU_STAGING_SLOTS``
  (default 2, ≥2 so the filler never races the in-flight transfer)
  preallocated host slots at ``(batch_size, *shape)`` in the TARGET dtype.
  Collate/pad/cast write INTO the slot (``np.copyto`` with
  cast-during-copy — one pass, no intermediate ``astype`` array), the
  transfer dispatches asynchronously, and the slot is recycled only after
  the transfer *previously dispatched from that slot* reports complete
  (``block_until_ready`` on the PREVIOUS handoff, never the current one).
  Steady state performs **zero per-batch host-buffer allocations**
  (``tests/test_staging.py`` holds this with tracemalloc), so the staging
  thread stops paying allocator+page-fault costs per batch while transfers
  overlap consumption.
* **Fresh assembly** (host-backed backends, e.g. XLA:CPU) — reusing slots
  is counterproductive there: the runtime zero-copies suitably-aligned
  host arrays into device handles (measured on this jaxlib: a 64-byte-
  aligned array aliases; a fresh large numpy allocation is page-aligned),
  so a reused slot would either alias (corrupting a held batch on
  recycle) or force a second real copy. Instead each batch assembles into
  fresh buffers — one copy — and the dispatch aliases
  them for free; the buffers are never touched again, making aliasing
  harmless by construction.

The engine starts on the ring path and switches to fresh assembly as soon
as the first dispatch reveals a host-backed (``'cpu'`` platform) target;
unknown array types conservatively count as host-backed (fresh assembly is
the always-correct strategy).

Knob (docs/env_knobs.md): ``PETASTORM_TPU_STAGING_SLOTS`` sizes the ring.
It is read once and cached; :func:`refresh_staging` re-reads it (the
established knob discipline). The tests hold the engine's values to a
plain numpy reference (``petastorm_tpu/test_util/reference.py``).

Telemetry (docs/telemetry.md): per-batch ``stage_fill`` /
``h2d_dispatch`` / ``h2d_ready`` spans (which double as trace events when
``PETASTORM_TPU_TRACE=1``, so Perfetto dumps show fill/transfer/consume
overlap) and the ``petastorm_tpu_h2d_bytes_total`` counter;
``pipeline_report`` derives ``h2d_overlap_share`` from the three stages.

Sanitizer (docs/troubleshoot.md): ``PETASTORM_TPU_SANITIZE=1`` arms the
pipesan runtime guards on the ring path — slot slabs get poisoned red
zones verified before every refill, and a weakref census of each
dispatch's outbound views aborts a recycle (fresh buffers, escaped
holder keeps the old memory) when a consumer held a view past the slot's
documented lifetime. Resolved once at engine construction: the unarmed
hot path pays nothing.
"""

import logging

import numpy as np

from petastorm_tpu import faults, sanitizer
from petastorm_tpu.fused import (
    FUSED_BYTES, FUSED_ROWS, EncodedImageColumn, count_fallback,
)
from petastorm_tpu.telemetry import (
    get_registry, knobs, metrics_disabled, register_refresh, span,
)

logger = logging.getLogger(__name__)

#: registry counter: bytes handed to the device transfer path
H2D_BYTES = 'petastorm_tpu_h2d_bytes_total'

#: name of the validity-mask column added under ``last_batch='pad'`` — the
#: canonical definition (jax.loader re-exports it)
MASK_FIELD = 'valid_mask'

_MIN_SLOTS = 2

# knob cache (refresh_staging() re-reads); None = not yet resolved
_slots = None


def staging_slots():
    """Ring size from ``PETASTORM_TPU_STAGING_SLOTS`` (default and floor:
    2 — one slot filling while the other's transfer is in flight)."""
    global _slots
    if _slots is None:
        _slots = knobs.get_int('PETASTORM_TPU_STAGING_SLOTS', _MIN_SLOTS,
                               floor=_MIN_SLOTS)
    return _slots


def refresh_staging():
    """Re-read the staging knob (tests, long-lived processes flipping
    the env); the next :func:`make_stager` call sees the new value.
    Also runs as part of ``petastorm_tpu.telemetry.refresh()``, the
    process's one re-read-every-knob entry point."""
    global _slots
    _slots = None


register_refresh(refresh_staging)


def make_stager(batch_size, dtypes, last_batch, put_fn):
    """A :class:`StagingEngine` for one staging pass."""
    return StagingEngine(batch_size, dtypes, last_batch, put_fn,
                         num_slots=staging_slots())


def _is_host_backed(leaf):
    """True when the dispatched array lives in host memory ('cpu'
    platform) — where ``device_put`` can alias the source buffer and
    fresh assembly beats slot reuse. Unknown array types count as
    host-backed (fresh assembly is the always-correct strategy)."""
    devices = getattr(leaf, 'devices', None)
    if devices is None:
        return True
    try:
        return all(getattr(d, 'platform', 'cpu') == 'cpu'
                   for d in devices())
    except Exception:  # noqa: BLE001 - duck-typed runtimes
        return True


def _check_deviceable(name, arr):
    """The shared undevicable-column diagnosis (object → classified ragged
    message, fixed-width strings → string message)."""
    if arr.dtype == object:
        from petastorm_tpu.ragged import reject_object_column
        reject_object_column(name, arr)
    if arr.dtype.kind in 'US':
        from petastorm_tpu.ragged import STRING_MESSAGE
        raise TypeError(STRING_MESSAGE % name)


class _Slot:
    """One ring slot: preallocated per-field host buffers plus the device
    arrays of the transfer most recently dispatched from it."""

    __slots__ = ('buffers', 'in_flight', 'census')

    def __init__(self, buffers, census=None):
        self.buffers = buffers      # {field: ndarray(batch_size, *shape)}
        self.in_flight = None       # leaves of the last dispatch
        self.census = census        # sanitizer.ViewCensus when armed

    def await_retired(self):
        """Block until the transfer previously dispatched from this slot
        completes — only then may the buffers be overwritten (an in-flight
        ``device_put`` may still be reading them)."""
        leaves = self.in_flight
        if leaves is not None:
            for leaf in leaves:
                leaf.block_until_ready()
            self.in_flight = None


class _Ring:
    """Round-robin ring of slots for one batch signature."""

    __slots__ = ('slots', 'cursor')

    def __init__(self, slots):
        self.slots = slots
        self.cursor = 0

    def next_slot(self):
        slot = self.slots[self.cursor]
        self.cursor = (self.cursor + 1) % len(self.slots)
        return slot


class StagingEngine:
    """Per-pass staging engine for :class:`JaxLoader`.

    Single-threaded by contract: only the loader's staging thread calls
    :meth:`stage`. ``put_fn(host_pytree) -> device_pytree`` is the
    loader's dispatch (plain ``device_put`` or the sharded build).
    """

    def __init__(self, batch_size, dtypes, last_batch, put_fn, num_slots=2):
        self._batch_size = batch_size
        self._dtypes = dict(dtypes or {})
        self._last_batch = last_batch
        self._put_fn = put_fn
        self._num_slots = max(_MIN_SLOTS, num_slots)
        self._rings = {}            # signature -> _Ring (ring mode only)
        # None until the first dispatch reveals the backend; True routes
        # every assembled batch to fresh buffers (see module docstring)
        self._host_backed = None
        # shared immutable all-true mask for full batches on the direct
        # path; allocated once on first use
        self._full_mask = None
        # test/diagnostic hooks: in ring mode, slot-slab allocations are
        # startup-only (steady growth = the arena is not being reused)
        self.slabs_allocated = 0
        self.batches_staged = 0
        # pipesan: resolved ONCE here so the unarmed per-batch path costs
        # a single attribute read, not a knob parse
        self._sanitize = sanitizer.sanitize_enabled()
        self.slabs_quarantined = 0
        # fused-decode accounting (petastorm_tpu/fused.py): rows decoded
        # straight into destination buffers by _fill, and WHERE the last
        # fused fill landed — 'fused-into-slot' (recycled arena slot,
        # the zero-extra-copy regime) or 'fused-into-slab' (host-backed
        # fresh assembly; still one decode pass, buffer not recycled)
        self.fused_rows = 0
        self.fused_mode = None

    # -- arena ---------------------------------------------------------------

    def _target_dtype(self, name, arr):
        want = self._dtypes.get(name)
        return np.dtype(want) if want is not None else arr.dtype

    def _resolve_dtypes(self, parts):
        """Per-field assembly dtype: the ``dtypes=`` policy wins;
        otherwise mixed-dtype parts PROMOTE exactly like
        ``np.concatenate`` would (an int32 chunk
        followed by an int64 one must yield int64, never a wrapping
        downcast into the first chunk's dtype)."""
        resolved = {}
        for name, arr in parts[0].items():
            want = self._dtypes.get(name)
            if want is not None:
                resolved[name] = np.dtype(want)
                continue
            dtype = arr.dtype
            if any(p[name].dtype != dtype for p in parts[1:]):
                dtype = np.result_type(*[p[name].dtype for p in parts])
            resolved[name] = dtype
        return resolved

    def _signature(self, columns, dtype_map, with_mask):
        # leading (batch) dim excluded: a short tail reuses the full-size
        # slots through [:n] views instead of allocating a one-off ring
        return (with_mask,) + tuple(
            (name, arr.shape[1:], dtype_map[name].str)
            for name, arr in sorted(columns.items()))

    def _new_buffers(self, columns, dtype_map, with_mask, guarded=None):
        # armed sanitizer: slabs carry poisoned red zones on both sides
        # of the visible array, verified before every refill. Only ring
        # slots are worth guarding (``guarded=False`` on the fresh-
        # assembly path): fresh buffers are never recycled, so their red
        # zones would never be checked.
        if guarded is None:
            guarded = self._sanitize
        alloc = sanitizer.allocate_guarded if guarded else np.empty
        buffers = {
            name: alloc((self._batch_size,) + arr.shape[1:],
                        dtype_map[name])
            for name, arr in columns.items()}
        if with_mask:
            buffers[MASK_FIELD] = alloc((self._batch_size,), bool)
        return buffers

    def _ring_for(self, columns, dtype_map, with_mask):
        sig = self._signature(columns, dtype_map, with_mask)
        ring = self._rings.get(sig)
        if ring is None:
            slots = [_Slot(self._new_buffers(columns, dtype_map, with_mask),
                           census=(sanitizer.ViewCensus()
                                   if self._sanitize else None))
                     for _ in range(self._num_slots)]
            self.slabs_allocated += len(slots)
            ring = self._rings[sig] = _Ring(slots)
        elif len(ring.slots) < self._num_slots:
            # the autotuner deepened the ring mid-pass (set_num_slots):
            # grow lazily, at the ring's next use, on the staging thread —
            # the only thread allowed to touch slot state
            add = self._num_slots - len(ring.slots)
            ring.slots.extend(
                _Slot(self._new_buffers(columns, dtype_map, with_mask),
                      census=(sanitizer.ViewCensus()
                              if self._sanitize else None))
                for _ in range(add))
            self.slabs_allocated += add
        return ring

    @property
    def num_slots(self):
        """Current ring depth target (slots per batch signature)."""
        return self._num_slots

    def set_num_slots(self, num_slots):
        """Deepen (never shrink) the per-signature ring depth — the
        staging autotuner's adjustment seam. Existing rings grow lazily
        at their next use on the staging thread; shrinking is not
        supported (a removed slot's in-flight transfer would lose its
        recycle gate). Returns the effective depth."""
        self._num_slots = max(self._num_slots,
                              max(_MIN_SLOTS, int(num_slots)))
        return self._num_slots

    # -- staging -------------------------------------------------------------

    def stage(self, columns, n_valid):
        """Assemble + dispatch one batch; ``columns`` is one column dict
        or a LIST of column-dict parts (chunk views from the noop
        re-batcher, copied in sequentially so the concatenated
        intermediate never exists). Parts may carry still-encoded
        :class:`~petastorm_tpu.fused.EncodedImageColumn` columns — those
        DECODE during the fill, straight into the destination buffer
        (the fused path), so such a batch always takes an assembly path,
        never the direct dispatch. Returns the device batch WITHOUT
        waiting for the transfer to complete."""
        parts = columns if isinstance(columns, list) else [columns]
        parts = [{name: (arr if isinstance(arr, EncodedImageColumn)
                         else np.asarray(arr))
                  for name, arr in p.items()}
                 for p in parts]
        has_encoded = False
        for p in parts:
            for name, arr in p.items():
                if isinstance(arr, EncodedImageColumn):
                    # fixed-shape numeric by the worker's deferral gate
                    has_encoded = True
                    continue
                _check_deviceable(name, arr)
        with_mask = self._last_batch == 'pad'
        full = n_valid >= self._batch_size
        if (not has_encoded and len(parts) == 1 and (full or not with_mask)
                and all(self._target_dtype(name, arr) == arr.dtype
                        for name, arr in parts[0].items())):
            # one ready chunk view, no cast, no pad: dispatch the source
            # directly — it is never written again, so no copy is needed
            # on any backend; the transfer is still async
            return self._stage_direct(parts[0], with_mask)
        dtype_map = self._resolve_dtypes(parts)
        if self._host_backed:
            return self._stage_fresh(parts, dtype_map, n_valid, with_mask)
        return self._stage_ring(parts, dtype_map, n_valid, with_mask)

    def _stage_direct(self, cols, with_mask):
        """Zero-copy dispatch of a ready single-chunk batch (plus the
        shared immutable all-true mask under ``last_batch='pad'``)."""
        if with_mask:
            if self._full_mask is None:
                self._full_mask = np.ones(self._batch_size, bool)
            cols = dict(cols)
            cols[MASK_FIELD] = self._full_mask
        with span('h2d_dispatch'):
            device_batch = self._put(cols)
        self._account(cols.values())
        self._learn_backend(device_batch)
        return device_batch

    def _stage_fresh(self, parts, dtype_map, n, with_mask):
        """Host-backed backends: assemble into FRESH buffers (one copy)
        and let the runtime zero-copy
        them into the device handle — never reused, so aliasing is
        harmless by construction."""
        with span('stage_fill'):
            buffers = self._new_buffers(parts[0], dtype_map, with_mask,
                                        guarded=False)
            views = self._fill(buffers, parts, n, with_mask)
        with span('h2d_dispatch'):
            device_batch = self._put(views)
        self._account(views.values())
        return device_batch

    def _stage_ring(self, parts, dtype_map, n, with_mask):
        """Accelerator backends: fill a recycled arena slot (no per-batch
        host allocation) and dispatch the async transfer."""
        ring = self._ring_for(parts[0], dtype_map, with_mask)
        slot = ring.next_slot()
        with span('h2d_ready'):
            # gate recycling on the slot's PREVIOUS handoff — with ≥2
            # slots this is never the batch just returned to the consumer
            slot.await_retired()
        if self._sanitize:
            self._sanitize_recycle(slot, parts, dtype_map, with_mask)
        with span('stage_fill'):
            views = self._fill(slot.buffers, parts, n, with_mask)
        if self._sanitize:
            # hand out fresh VIEW OBJECTS over the slot memory (still
            # zero-copy) so the census can tell a consumer-held view
            # from the slot's own reference to its buffers
            views = {name: v[:] for name, v in views.items()}
        with span('h2d_dispatch'):
            device_batch = self._put(views)
        self._account(views.values())
        if self._learn_backend(device_batch):
            # first dispatch revealed a host-backed target: the runtime
            # may have aliased this slot into the returned arrays, so the
            # ring (including this slot) is abandoned, never recycled —
            # every later batch takes the fresh-assembly path
            self._rings = {}
        else:
            slot.in_flight = list(device_batch.values())
            if self._sanitize:
                # census the views just handed to the transfer: any that
                # still resolve when THIS slot comes up for recycling
                # were kept past the documented lifetime
                slot.census.register(views.values())
        return device_batch

    def _sanitize_recycle(self, slot, parts, dtype_map, with_mask):
        """pipesan recycle gate (armed mode only): abort the recycle when
        a previously-dispatched view is still alive (the escaped holder
        keeps the old slab — quarantine, no corruption), and verify the
        red zones before letting the fill overwrite the slab."""
        escaped = slot.census.escaped() if slot.census is not None else 0
        if escaped:
            sanitizer.record_violation(
                'staging-use-after-recycle',
                '%d staged view(s) still alive when their slot came up '
                'for recycling; recycle aborted — slot re-backed with '
                'fresh buffers' % escaped)
            slot.buffers = self._new_buffers(parts[0], dtype_map,
                                             with_mask)
            slot.census = sanitizer.ViewCensus()
            self.slabs_quarantined += 1
            return
        for name, buf in slot.buffers.items():
            if not sanitizer.check_canaries(buf):
                sanitizer.record_violation(
                    'staging-canary-trampled',
                    'red zone around staging slot field %r overwritten '
                    'while the slot was out — an escaped view wrote past '
                    'its bounds' % name)

    def _fill(self, buffers, parts, n, with_mask):
        """Cast/pad/mask-assemble ``parts`` into ``buffers``; returns the
        dict to dispatch (``[:n]`` views for a maskless short tail).
        Encoded image parts DECODE here — the fused pass: the native
        batch decoders write pixels straight into the destination rows
        (``decode_batch(out=)``, internal C thread pool), so decoded
        bytes exist exactly once, at their final host address."""
        full = n >= self._batch_size
        for name in parts[0]:
            dst = buffers[name]
            offset = 0
            for p in parts:
                column = p[name]
                m = len(column)
                if column.shape[1:] != dst.shape[1:]:
                    # explicit, BEFORE the copy: np.copyto would happily
                    # BROADCAST a narrower chunk into the slot — silent
                    # corruption where np.concatenate would raise
                    raise ValueError(
                        'staging: field %r chunk of shape %s does not '
                        'fit the batch slot of shape %s; variable-shape '
                        'fields need pad_ragged= or bucket_boundaries='
                        % (name, column.shape, dst.shape))
                if isinstance(column, EncodedImageColumn):
                    self._fill_fused(column, dst[offset:offset + m])
                else:
                    # cast-during-copy: the single copy this path performs
                    # (same 'unsafe' semantics as .astype())
                    np.copyto(dst[offset:offset + m], column,
                              casting='unsafe')
                offset += m
            if with_mask and not full:
                dst[n:] = 0
        if with_mask:
            mask = buffers[MASK_FIELD]
            mask[:n] = True
            mask[n:] = False
        if full or with_mask:
            return buffers
        return {name: buf[:n] for name, buf in buffers.items()}

    def _fill_fused(self, column, dst):
        """Decode one encoded part into its destination rows under the
        ``decode_fused`` stage. The destination is the fused contract's
        whole point: a recycled arena slot (ring mode) or the fresh
        page-aligned assembly buffer (host-backed mode) — either way the
        transfer dispatches from the very rows the decoder wrote. A
        dtype-retargeted slot (defensive; the loader materializes those
        upstream) decodes to a scratch batch and cast-copies — that
        branch is a FALLBACK and must not count as fused: the rows/bytes
        counters and ``fused_mode`` are exactly what the troubleshoot
        runbook and the bench attribution read."""
        if dst.dtype != column.dtype:
            count_fallback('dtype-cast')
            with span('decode'):
                np.copyto(dst, column.materialize(), casting='unsafe')
            return
        with span('decode_fused'):
            column.decode_into(dst)
        self.fused_rows += len(column)
        self.fused_mode = ('fused-into-slab' if self._host_backed
                           else 'fused-into-slot')
        if not metrics_disabled():
            registry = get_registry()
            registry.counter(FUSED_ROWS).inc(len(column))
            registry.counter(FUSED_BYTES).inc(dst.nbytes)

    def release(self):
        """Pass end: drop the slot slabs and the in-flight device-array
        references they hold — otherwise up to ``num_slots`` device
        batches per signature (plus every host slab) stay pinned between
        epochs. The engine object itself survives for the diagnostics
        counters."""
        self._rings = {}
        self._full_mask = None

    def _put(self, cols):
        """The one H2D dispatch seam (all three staging modes route
        here): the ``staging.h2d`` faultpoint sits in front of the
        loader's ``put_fn`` so chaos runs can inject transfer errors or
        link latency without touching a runtime."""
        if faults.ARMED:
            faults.fault_hit('staging.h2d')
        return self._put_fn(cols)

    def _account(self, arrays):
        self.batches_staged += 1
        if not metrics_disabled():
            get_registry().counter(H2D_BYTES).inc(
                sum(arr.nbytes for arr in arrays))

    def _learn_backend(self, device_batch):
        """Resolve ``_host_backed`` from the first dispatched batch;
        returns True exactly once, when a host-backed target is first
        detected (the ring-mode caller must then retire its slots)."""
        if self._host_backed is None:
            self._host_backed = _is_host_backed(
                next(iter(device_batch.values())))
            if self._host_backed:
                logger.debug('staging: host-backed target; using fresh '
                             'assembly (zero-copy dispatch) over slot '
                             'reuse')
                return True
        return False
