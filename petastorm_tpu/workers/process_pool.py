"""Spawned-process worker pool over ZeroMQ.

Re-design of ``petastorm/workers_pool/process_pool.py`` (protocol diagram at
``:52-74``) for a TPU host. Topology is the same three-socket pattern —

    main:   PUSH work ──► worker PULL
    main:   PUB control ─► worker SUB          (stop broadcast)
    worker: PUSH results ► main PULL

— with these differences:

* **ipc:// endpoints** (unix domain sockets in a private temp dir) instead of
  random localhost TCP ports: no port collisions, lower latency, and no
  loopback TCP stack on the data path.
* Workers are spawned (never forked) via
  :func:`~petastorm_tpu.workers.exec_in_new_process.exec_in_new_process` and
  pinned to ``JAX_PLATFORMS=cpu`` so they can never grab the trainer's TPU.
* Results ride a pluggable :mod:`~petastorm_tpu.serializers` codec via its
  multipart frame API — the default :class:`PickleSerializer` ships every
  ndarray payload as its own pickle-5 out-of-band ZMQ frame, and the
  consumer receives with ``copy=False`` so deserialization is zero-copy
  (arrays view the wire buffers); back-pressure = ZMQ high-water marks
  sized from ``results_queue_size``.
* Same failure model as the reference: worker exceptions are serialized onto
  the results channel and re-raised in the consumer; an orphan-monitor thread
  in each worker exits when the main process dies (``process_pool.py:320-327``);
  all workers must check in within a startup timeout (``process_pool.py:38-39``);
  the stop broadcast repeats until every (possibly slow-joining) worker exits
  (``process_pool.py:289-294``).
"""

import logging
import os
import shutil
import tempfile
import threading
import time

import dill

from petastorm_tpu import sanitizer
from petastorm_tpu.serializers import PickleSerializer
from petastorm_tpu.telemetry import (
    STALL_NOTE_FLOOR_S, dump_delta_frame, load_delta_frame,
    merge_worker_delta, note_producer_wait, span, tracing,
)
from petastorm_tpu.telemetry.registry import set_process_role
from petastorm_tpu.workers import (
    EmptyResultError, TimeoutWaitingForResultError, WorkerTerminationRequested,
)

logger = logging.getLogger(__name__)

_STARTUP_TIMEOUT_S = 20
_POLL_INTERVAL_MS = 50
_JOIN_TIMEOUT_S = 10

# Wire message types (first frame).
_MSG_READY = b'R'
_MSG_RESULT = b'D'
_MSG_MARKER = b'M'
_MSG_ERROR = b'E'
_MSG_EXIT = b'X'
_CTRL_STOP = b'stop'


class ProcessPool:
    """N spawned decode processes; contract identical to :class:`ThreadPool`."""

    def __init__(self, workers_count, results_queue_size=50, serializer=None):
        self._workers_count = workers_count
        self._results_queue_size = results_queue_size
        self._serializer = serializer or PickleSerializer()
        self._processes = []
        self._ventilator = None
        self._ventilated_items = 0
        self._processed_items = 0
        self._error = None
        self._stop_event = threading.Event()
        self._ipc_dir = None
        self._context = None
        self._work_socket = None
        self._control_socket = None
        self._results_socket = None

    @property
    def workers_count(self):
        return self._workers_count

    # -- lifecycle ----------------------------------------------------------

    def start(self, worker_class, worker_args=None, ventilator=None,
              start_ventilator=True):
        import zmq
        if self._processes:
            raise RuntimeError('ProcessPool already started')
        self._context = zmq.Context()
        self._ipc_dir = tempfile.mkdtemp(prefix='petastorm_tpu_pool_')
        work_ep = 'ipc://%s/work' % self._ipc_dir
        control_ep = 'ipc://%s/control' % self._ipc_dir
        results_ep = 'ipc://%s/results' % self._ipc_dir

        self._work_socket = self._context.socket(zmq.PUSH)
        self._work_socket.set_hwm(0)  # work items are tiny dicts; never block
        self._work_socket.bind(work_ep)
        self._control_socket = self._context.socket(zmq.PUB)
        self._control_socket.bind(control_ep)
        # ZMQ high-water marks are PER CONNECTION; split the global results
        # bound across the send and receive sides of every worker connection
        # so total buffering stays ≈ results_queue_size items, matching the
        # ThreadPool's single shared bounded queue.
        per_worker_hwm = max(1, self._results_queue_size
                             // (2 * self._workers_count))
        self._per_worker_hwm = per_worker_hwm
        self._results_socket = self._context.socket(zmq.PULL)
        self._results_socket.set_hwm(per_worker_hwm)
        self._results_socket.bind(results_ep)

        from petastorm_tpu.workers.exec_in_new_process import exec_in_new_process
        payload = dill.dumps((worker_class, worker_args))
        for worker_id in range(self._workers_count):
            proc = exec_in_new_process(
                _worker_bootstrap, worker_id, os.getpid(), work_ep, control_ep,
                results_ep, payload, dill.dumps(self._serializer),
                per_worker_hwm)
            self._processes.append(proc)

        self._await_checkins()
        self._ventilator = ventilator
        if ventilator is not None and start_ventilator:
            ventilator.start()

    def _await_checkins(self):
        import zmq
        poller = zmq.Poller()
        poller.register(self._results_socket, zmq.POLLIN)
        checked_in = 0
        deadline = time.monotonic() + _STARTUP_TIMEOUT_S
        while checked_in < self._workers_count:
            dead = self._dead_workers()
            if dead:
                self._abort_startup()
                raise RuntimeError(
                    'Pool worker process(es) %s died during startup — see '
                    'their stderr for the traceback' % dead)
            if time.monotonic() > deadline:
                self._abort_startup()
                raise RuntimeError(
                    'Only %d of %d pool workers checked in within %ds'
                    % (checked_in, self._workers_count, _STARTUP_TIMEOUT_S))
            if poller.poll(_POLL_INTERVAL_MS):
                frames = self._results_socket.recv_multipart()
                if frames[0] == _MSG_READY:
                    checked_in += 1
                # anything else this early is impossible; drop it

    def _abort_startup(self):
        """Failure during start(): reap children and release every resource
        (join() is unusable here — stop() was never called)."""
        self._terminate_all()
        for p in self._processes:
            try:
                p.wait(timeout=2)
            except Exception:  # noqa: BLE001
                p.kill()
                p.wait()
        self._processes = []
        self._close_sockets()

    def ventilate(self, *args, **kwargs):
        self._ventilated_items += 1
        self._work_socket.send(dill.dumps((args, kwargs)))

    def get_results(self, timeout=None):
        deadline = None if timeout is None else time.monotonic() + timeout
        while True:
            if self._error is not None:
                raise self._error
            if not self._results_socket.poll(_POLL_INTERVAL_MS):
                if self._stop_event.is_set():
                    raise EmptyResultError()
                if (self._ventilated_items == self._processed_items
                        and (self._ventilator is None or self._ventilator.completed())):
                    raise EmptyResultError()
                if deadline is not None and time.monotonic() > deadline:
                    raise TimeoutWaitingForResultError()
                if self._dead_workers() and self._error is None:
                    self._error = RuntimeError(
                        'Pool worker process(es) died unexpectedly: %s'
                        % self._dead_workers())
                continue
            # copy=False: frames stay in ZMQ's receive buffers, exposed as
            # zero-copy memoryviews — what lets the pickle-5 out-of-band
            # result path rebuild ndarrays as views over the wire buffers
            # (no host copy between the socket and the consumer's arrays)
            with span('deserialize'):
                frames = self._results_socket.recv_multipart(copy=False)
                kind = frames[0].bytes
                if kind == _MSG_RESULT:
                    payload = [f.buffer for f in frames[1:]]
                    if sanitizer.sanitize_enabled():
                        # read-only memoryviews over the receive buffers:
                        # arrays pickle-5 rebuilds over them come out
                        # writeable=False, so a consumer's in-place write
                        # raises instead of corrupting ZMQ's buffers
                        payload = [b.toreadonly() for b in payload]
                    return self._serializer.deserialize_frames(payload)
            if kind == _MSG_MARKER:
                self._processed_items += 1
                if self._ventilator is not None:
                    self._ventilator.processed_item()
                # markers piggyback the worker's metric delta (io/decode/
                # transform spans, cache counters, producer-wait clock):
                # fold it into THIS process's registry + stall attributor
                if len(frames) > 1:
                    merge_worker_delta(load_delta_frame(frames[1].bytes))
                continue
            if kind == _MSG_ERROR:
                self._error = dill.loads(frames[1].bytes)
                self.stop()
                self.join()
                raise self._error
            if kind in (_MSG_READY, _MSG_EXIT):
                continue
            logger.warning('Unknown pool message type %r', kind)

    def _dead_workers(self):
        return [p.pid for p in self._processes
                if p.poll() is not None and p.returncode != 0]

    def stop(self):
        if self._ventilator is not None:
            self._ventilator.stop()
        self._stop_event.set()

    def join(self):
        if not self._stop_event.is_set():
            raise RuntimeError('Must call stop() before join()')
        if not self._processes:
            return
        # Slow-joiner-tolerant stop: a worker that connected its SUB socket
        # after our first broadcast would otherwise never hear it.
        deadline = time.monotonic() + _JOIN_TIMEOUT_S
        while any(p.poll() is None for p in self._processes):
            try:
                self._control_socket.send(_CTRL_STOP)
            except Exception:  # noqa: BLE001 - socket may already be closed
                break
            if time.monotonic() > deadline:
                logger.warning('Terminating pool workers that ignored stop')
                self._terminate_all()
                break
            time.sleep(_POLL_INTERVAL_MS / 1000.0)
        for p in self._processes:
            try:
                p.wait(timeout=_JOIN_TIMEOUT_S)
            except Exception:  # noqa: BLE001
                p.kill()
        self._processes = []
        self._close_sockets()

    def _terminate_all(self):
        for p in self._processes:
            if p.poll() is None:
                p.terminate()

    def _close_sockets(self):
        for sock in (self._work_socket, self._control_socket, self._results_socket):
            if sock is not None:
                sock.close(linger=0)
        self._work_socket = self._control_socket = self._results_socket = None
        if self._context is not None:
            self._context.term()
            self._context = None
        if self._ipc_dir:
            shutil.rmtree(self._ipc_dir, ignore_errors=True)
            self._ipc_dir = None

    @property
    def diagnostics(self):
        # Counters are mutated by two different threads (ventilator /
        # consumer); snapshot into locals and clamp so a torn read can
        # never report a negative in-flight gauge.
        ventilated = self._ventilated_items
        processed = self._processed_items
        return {
            'items_ventilated': ventilated,
            'items_processed': processed,
            'items_inflight': max(0, ventilated - processed),
            'workers_alive': sum(1 for p in self._processes if p.poll() is None),
            # SHARED_POOL_GAUGES parity: results buffer in ZMQ (per-socket
            # HWM), not a host-side queue this process can measure — 0 is
            # the honest depth of the (nonexistent) consumer-side queue
            'output_queue_size': 0,
        }


def _worker_bootstrap(worker_id, main_pid, work_ep, control_ep, results_ep,
                      class_payload, serializer_payload, results_hwm):
    """Entry point of a spawned decode process."""
    import zmq
    import psutil

    def _orphan_monitor():
        while True:
            if not psutil.pid_exists(main_pid):
                os._exit(0)
            time.sleep(1.0)

    threading.Thread(target=_orphan_monitor, daemon=True).start()
    set_process_role('worker')

    worker_class, worker_args = dill.loads(class_payload)
    serializer = dill.loads(serializer_payload)

    context = zmq.Context()
    work = context.socket(zmq.PULL)
    work.connect(work_ep)
    control = context.socket(zmq.SUB)
    control.setsockopt(zmq.SUBSCRIBE, b'')
    control.connect(control_ep)
    results = context.socket(zmq.PUSH)
    results.set_hwm(max(1, results_hwm))
    results.connect(results_ep)

    def await_room():
        """Stop-aware wait for room in the results channel (mirrors
        ThreadPool._publish): a worker parked on a full results channel
        must still hear the stop broadcast, or every mid-stream shutdown
        would end in SIGTERM with no clean shutdown(). This thread is the
        channel's only sender, so the room it finds is still there for
        the send that follows.

        Time blocked against the channel's HWM is back-pressure from a
        slow consumer; it lands in this worker's registry (producer-wait
        counter) and reaches the consumer with the next marker's delta."""
        start = time.monotonic()
        try:
            while not results.poll(_POLL_INTERVAL_MS, zmq.POLLOUT):
                if control.poll(0) and control.recv() == _CTRL_STOP:
                    raise WorkerTerminationRequested()
        finally:
            blocked = time.monotonic() - start
            if blocked > STALL_NOTE_FLOOR_S:
                note_producer_wait(blocked)

    def send_or_stop(frames):
        await_room()
        results.send_multipart(frames)

    def publish(value):
        # multipart result: frame 0 the pickle-5 stream, every ndarray
        # payload its own out-of-band frame (serializers.py) — ZMQ sends
        # straight from the exported buffers, one memcpy per array. The
        # framing and that send are the serialize stage; the wait for
        # room before them is producer wait (the framing itself is cheap,
        # so framing after the wait delays nothing that matters)
        await_room()
        with span('serialize'):
            results.send_multipart(
                [_MSG_RESULT] + list(serializer.serialize_frames(value)))

    worker = worker_class(worker_id, publish, worker_args)
    worker.initialize()
    results.send_multipart([_MSG_READY, b''])

    poller = zmq.Poller()
    poller.register(work, zmq.POLLIN)
    poller.register(control, zmq.POLLIN)
    try:
        while True:
            events = dict(poller.poll())
            if control in events:
                if control.recv() == _CTRL_STOP:
                    break
            if work in events:
                args, kwargs = dill.loads(work.recv())
                # traced items carry their context over the work channel;
                # activate it here so this PROCESS's stage spans + attempt
                # event land on the item's timeline (they ship back with
                # the marker's delta frame below)
                ctx = kwargs.pop(tracing.TRACE_CTX_KEY, None)
                try:
                    with tracing.attempt(ctx, 'process-%d' % worker_id):
                        worker.process(*args, **kwargs)
                    # marker piggybacks this worker's metric delta (one
                    # shared framing with the service's DONE piggyback)
                    send_or_stop([_MSG_MARKER, dump_delta_frame()])
                except WorkerTerminationRequested:
                    break
                except Exception as e:  # noqa: BLE001 - forwarded to consumer
                    try:
                        err_payload = dill.dumps(e)
                    except Exception:  # noqa: BLE001 - unpicklable exception
                        err_payload = dill.dumps(
                            RuntimeError('%s: %s' % (type(e).__name__, e)))
                    try:
                        send_or_stop([_MSG_ERROR, err_payload])
                        send_or_stop([_MSG_MARKER, dump_delta_frame()])
                    except WorkerTerminationRequested:
                        break
    finally:
        try:
            worker.shutdown()
        except Exception:  # noqa: BLE001 - best-effort shutdown
            pass
        try:
            results.send_multipart([_MSG_EXIT, b''], flags=zmq.NOBLOCK)
        except Exception:  # noqa: BLE001 - channel may be full/closed
            pass
        for sock in (work, control, results):
            sock.close(linger=1000)
        context.term()
        os._exit(0)
