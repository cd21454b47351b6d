"""Worker server: a standalone decode host process for the service pool.

Runs any :class:`~petastorm_tpu.workers.worker_base.WorkerBase` against work
items streamed from a dispatcher over ``tcp://``:

    python -m petastorm_tpu.service.worker_server \\
        --endpoint tcp://10.0.0.5:7777 --worker-id 0

Design points:

* **Registration with retry/backoff**: REGISTER is re-sent on an
  exponential backoff until the dispatcher answers with the job SPEC, so
  worker servers can start before the dispatcher exists (ZMQ reconnects
  transparently underneath).
* **Network loop owns the socket**: the main thread polls, heartbeats, and
  ships buffered results; a single executor thread runs ``process()``.
  Heartbeats therefore keep flowing during a long decode — a busy worker
  never reads as dead.
* **Atomic item results**: ``publish_func`` appends to a per-item buffer;
  the whole buffer ships in ONE ``DONE`` message after ``process()``
  returns. A worker killed mid-item has delivered nothing for that item, so
  the dispatcher's re-ventilation re-runs it without duplicating rows.
* **Persistence**: after a job ends (STOP, or the dispatcher vanishes —
  no HEARTBEAT_ACK for ``ack_timeout``) the server shuts the worker down
  and goes back to registering, tf.data-service style, so one fleet of
  worker servers outlives any number of reader lifetimes. ``--once`` (or a
  dead ``--parent-pid``) exits instead.
* **Dispatcher-restart survival**: the SPEC carries the dispatcher
  incarnation's random token and every HEARTBEAT_ACK echoes it. When the
  acks suddenly carry a DIFFERENT token, a new dispatcher has taken the
  endpoint (client restart) — this server's job spec and item-id space
  are dead, so it abandons the job immediately and re-registers (fresh
  socket, fresh identity, registration backoff) instead of decoding the
  new dispatcher's items against the old job's spec or waiting out the
  full ack timeout. A vanished-and-silent dispatcher is still caught by
  the ack timeout; both paths converge on re-registration, so a standing
  fleet survives any number of dispatcher restarts
  (docs/service.md, "Failure semantics").
"""

import argparse
import logging
import os
import queue
import signal
import sys
import threading
import time
import uuid

from petastorm_tpu import faults
from petastorm_tpu.service import protocol as proto
from petastorm_tpu.telemetry import (
    count_swallowed, knobs, obs_server, timeseries, tracing,
)

logger = logging.getLogger(__name__)

_POLL_INTERVAL_MS = 50
_REGISTER_BACKOFF_MAX_S = 2.0
_EXECUTOR_JOIN_TIMEOUT_S = 5.0


def _parent_died(parent_pid):
    if parent_pid is None:
        return False
    import psutil

    return not psutil.pid_exists(parent_pid)


def _register(sock, parent_pid, register_timeout_s, term_event=None,
              cache_fps=(), peer_server=None):
    """REGISTER with exponential backoff until the SPEC arrives.

    Returns ``(spec payload, dispatcher token)`` — token None from a
    pre-token dispatcher build — or ``(None, None)`` when the server
    should exit (orphaned, SIGTERMed, or the registration window
    closed).
    """
    import json

    backoff_s = 0.1
    deadline = (None if register_timeout_s is None
                else time.monotonic() + register_timeout_s)
    last_parent_check = 0.0
    # cache-fingerprint advert (JSON list, additive frame like the pid):
    # the dispatcher must see which decoded caches this HOST already
    # holds BEFORE it binds us to a job — placement happens at
    # registration time (docs/service.md, "High availability")
    try:
        advert = json.dumps(list(cache_fps)).encode() if cache_fps else b''
    except Exception:  # noqa: BLE001 - placement is advisory
        count_swallowed('worker-cache-advert')
        advert = b''
    # fleet cache tier: the FULL set of decoded entries this host holds
    # rides REGISTER (one more additive frame), so the dispatcher's peer
    # directory is complete before the first WORK lands — a restarted
    # worker's startup scan re-advertises everything it kept on disk
    peer_advert = b''
    if peer_server is not None:
        try:
            peer_advert = proto.dump_json_params(peer_server.full_advert())
        except Exception:  # noqa: BLE001 - adverts are advisory
            count_swallowed('worker-peer-advert')
            peer_advert = b''
    frames_out = [proto.MSG_REGISTER, b'%d' % os.getpid()]
    if peer_advert:
        # frame order is positional: the placement advert must occupy
        # frame 3 (possibly empty) so the peer advert lands at frame 4
        frames_out.extend([advert, peer_advert])
    elif advert:
        frames_out.append(advert)
    while True:
        # the trailing pid frame is ADVISORY and additive (an old
        # dispatcher ignores extra REGISTER frames): it lets a standing
        # daemon's supervisor tell a worker that is merely between jobs
        # (re-registering, not yet heartbeating) from a wedged one
        sock.send_multipart(frames_out)
        poll_deadline = time.monotonic() + backoff_s
        while time.monotonic() < poll_deadline:
            if term_event is not None and term_event.is_set():
                logger.info('SIGTERM during registration; exiting')
                return None, None
            if sock.poll(_POLL_INTERVAL_MS):
                frames = sock.recv_multipart()
                if frames[0] == proto.MSG_SPEC:
                    return frames[1], (frames[2] if len(frames) > 2
                                       else None)
                # STOP/stray frames during registration are meaningless
                continue
            now = time.monotonic()
            if now - last_parent_check > 1.0:
                last_parent_check = now
                if _parent_died(parent_pid):
                    logger.info('Parent %s died; exiting', parent_pid)
                    return None, None
            if deadline is not None and now > deadline:
                logger.error('No dispatcher answered REGISTER within %.1fs',
                             register_timeout_s)
                return None, None
        backoff_s = min(backoff_s * 2, _REGISTER_BACKOFF_MAX_S)


def _reroot_decoded_cache(worker_args):
    """Point a job's materialized decoded-row-group cache at THIS host's
    shared directory (``--cache-dir`` / ``PETASTORM_TPU_DECODED_CACHE_DIR``).

    The cache object travels inside the job spec with whatever directory
    the *client* configured — meaningless on a remote decode host. With
    the override set, every job a standing worker-server fleet serves
    lands on one local tier, so N jobs over one dataset decode each
    row-group once per HOST, not once per job (the tf.data-service
    "decode once, serve many" shape). Without it, the spec's directory is
    kept (localhost fleets share the client's directory naturally)."""
    cache_dir = knobs.get_str('PETASTORM_TPU_DECODED_CACHE_DIR')
    if not cache_dir or not isinstance(worker_args, dict):
        return
    from petastorm_tpu.materialized_cache import MaterializedRowGroupCache
    cache = worker_args.get('cache')
    if isinstance(cache, MaterializedRowGroupCache) \
            and cache.path != cache_dir:
        logger.info('Rerooting decoded cache %s -> %s', cache.path,
                    cache_dir)
        cache.reroot(cache_dir)


def _run_job(sock, spec_payload, worker_id, heartbeat_interval_s,
             ack_timeout_s, parent_pid, status=None, token=None,
             term_event=None, known_fps=None, endpoint=None,
             peer_server=None, peer_live=None):
    """One job lifetime: build the worker, stream items until STOP, the
    dispatcher vanishes (ack timeout), or a DIFFERENT dispatcher
    incarnation takes the endpoint (heartbeat-ack token mismatch).
    Returns True if the server should serve again."""
    worker_class, worker_args, serializer = proto.load_job_spec(spec_payload)
    _reroot_decoded_cache(worker_args)
    # cache-aware placement: this job's decode fingerprint becomes part
    # of the host's advert set (heartbeat summaries now; a marker file
    # so future server processes advertise it from their first REGISTER)
    from petastorm_tpu.service import placement
    fingerprint = placement.placement_fingerprint(worker_args)
    if fingerprint:
        known_fps = known_fps if known_fps is not None else set()
        known_fps.add(fingerprint)
        placement.note_fingerprint(
            knobs.get_str('PETASTORM_TPU_DECODED_CACHE_DIR'), fingerprint)
    advertised = sorted(known_fps)[:placement.MAX_ADVERTISED] \
        if known_fps else None
    # per-heartbeat observability summary (docs/telemetry.md fleet view):
    # thread-free rates since the previous heartbeat, piggybacked on the
    # HEARTBEAT frame so the dispatcher's endpoint can break the fleet
    # down per worker
    summarizer = timeseries.HeartbeatSummarizer(worker_id)
    status = status if status is not None else {}

    buffer = []
    worker = worker_class(worker_id, buffer.append, worker_args)
    worker.initialize()

    # fleet cache tier (docs/service.md, "Fleet cache tier"): serve this
    # job's decoded cache to peers and fetch what peers already decoded.
    # All best-effort — a failure here costs wire-priced hits, never the
    # job.
    peer_client = None
    peer_cached = None
    if endpoint and not knobs.is_disabled('PETASTORM_TPU_PEER_CACHE'):
        from petastorm_tpu.materialized_cache import (
            MaterializedRowGroupCache,
        )
        cache = worker_args.get('cache') \
            if isinstance(worker_args, dict) else None
        if isinstance(cache, MaterializedRowGroupCache) \
                and not cache.degraded:
            from petastorm_tpu.service import peer_cache
            try:
                if peer_server is None:
                    # no eager --cache-dir server: serve the spec's own
                    # directory for this job's lifetime
                    peer_server = peer_cache.get_server(cache.path)
                peer_client = peer_cache.PeerCacheClient(
                    endpoint, self_endpoint=peer_server.endpoint)
                cache.attach_peer_client(peer_client)
                peer_cached = cache
                if peer_live is not None:
                    peer_live['client'] = peer_client
            except Exception:  # noqa: BLE001 - the tier is advisory
                count_swallowed('peer-client-wire')
                peer_client = None

    work_queue = queue.Queue()
    out_queue = queue.Queue()
    stop_flag = threading.Event()

    def executor():
        while not stop_flag.is_set():
            try:
                item_id, payload = work_queue.get(timeout=0.05)
            except queue.Empty:
                continue
            del buffer[:]
            try:
                args, kwargs = proto.load_work_item(payload)
                # traced items carry their context inside the WORK frame's
                # kwargs; activate it so this server's stage spans + the
                # attempt event (worker id + pid track) join the item's
                # timeline — they ship back inside the DONE's delta frame
                ctx = kwargs.pop(tracing.TRACE_CTX_KEY, None)
                with tracing.attempt(ctx, 'service-%d' % worker_id):
                    worker.process(*args, **kwargs)
                # metrics delta rides the DONE (io/decode/transform spans,
                # cache counters accrued while processing this item); the
                # dispatcher merges it into the client-side registry, so
                # the whole fleet aggregates without a separate channel
                frames = ([proto.MSG_DONE, proto.pack_item_id(item_id),
                           proto.dump_metrics_delta()]
                          + [serializer.serialize(v) for v in buffer])
                status['items_done'] = status.get('items_done', 0) + 1
            except Exception as e:  # noqa: BLE001 - forwarded to consumer
                logger.debug('Worker %d forwarding exception', worker_id,
                             exc_info=True)
                frames = [proto.MSG_ERROR, proto.pack_item_id(item_id),
                          proto.dump_exception(e),
                          proto.dump_metrics_delta()]
                # errored items are NOT done: the fleet view's per-worker
                # breakdown must show a sick worker's completions stalling
                status['items_errored'] = status.get('items_errored',
                                                     0) + 1
            out_queue.put(frames)

    executor_thread = threading.Thread(target=executor, daemon=True)
    executor_thread.start()

    sock.send_multipart([proto.MSG_READY])
    now = time.monotonic()
    last_heartbeat_sent = 0.0
    last_ack = now
    last_parent_check = now
    serve_again = True
    try:
        while True:
            now = time.monotonic()
            if now - last_heartbeat_sent >= heartbeat_interval_s:
                last_heartbeat_sent = now
                if faults.ARMED and faults.fault_hit(
                        'zmq.heartbeat', key=worker_id) == 'drop':
                    pass  # injected: heartbeat lost; dispatcher will lapse
                else:
                    try:
                        summary = summarizer.summary(
                            obs_port=obs_server.server_port())
                        summary['items_done'] = status.get('items_done', 0)
                        if advertised:
                            summary['cache_fp'] = advertised
                        if peer_server is not None:
                            # bounded add/evict/touch delta since the
                            # last heartbeat (carry-over keeps any one
                            # frame small)
                            delta = peer_server.advert_delta()
                            if delta:
                                summary['peer'] = delta
                        frame = proto.dump_obs_summary(summary)
                    except Exception:  # noqa: BLE001 - advisory telemetry
                        count_swallowed('worker-obs-summary')
                        frame = b''
                    if token is not None:
                        # the token rides its OWN frame, never inside the
                        # advisory summary: the dispatcher cross-checks
                        # it to spot foreign-incarnation workers, and
                        # that correctness signal must survive the
                        # summary path degrading to b''
                        sock.send_multipart([proto.MSG_HEARTBEAT, frame,
                                             token])
                    elif frame:
                        sock.send_multipart([proto.MSG_HEARTBEAT, frame])
                    else:
                        sock.send_multipart([proto.MSG_HEARTBEAT])
            while True:
                try:
                    result_frames = out_queue.get_nowait()
                except queue.Empty:
                    break
                if faults.ARMED and faults.fault_hit(
                        'zmq.done', key=result_frames[1]) == 'drop':
                    continue  # injected: completion lost in flight
                sock.send_multipart(result_frames)
            if sock.poll(_POLL_INTERVAL_MS):
                frames = sock.recv_multipart()
                msg = frames[0]
                if msg == proto.MSG_WORK:
                    work_queue.put((proto.unpack_item_id(frames[1]),
                                    frames[2]))
                    if peer_client is not None and len(frames) > 3 \
                            and frames[3]:
                        # piggybacked fleet-directory delta: holders of
                        # recently advertised entries, no DIRGET needed
                        peer_client.update_directory(
                            proto.load_json_params(frames[3]))
                elif msg == proto.MSG_STOP:
                    logger.info('Dispatcher sent STOP; job over')
                    break
                elif msg == proto.MSG_HEARTBEAT_ACK:
                    last_ack = now
                    if peer_server is not None and len(frames) > 2 \
                            and frames[2]:
                        # advisory global-eviction hints (additive
                        # trailing frame); the server re-checks local
                        # atime before dropping anything
                        try:
                            hints = proto.load_json_params(
                                frames[2]).get('evict')
                            if hints:
                                peer_server.apply_evict_hints(hints)
                        except Exception:  # noqa: BLE001 - advisory
                            count_swallowed('peer-evict-hint')
                    if token is not None and len(frames) > 1 \
                            and frames[1] != token:
                        # a NEW dispatcher incarnation answered on this
                        # endpoint: our job spec and item-id space are
                        # dead — re-register for the new job instead of
                        # decoding against the old spec or waiting out
                        # the full ack timeout
                        logger.warning(
                            'Dispatcher incarnation changed (token %r -> '
                            '%r); abandoning job to re-register',
                            token, frames[1])
                        break
                elif msg == proto.MSG_SPEC:
                    pass  # duplicate reply to a re-sent REGISTER
            if now - last_ack > ack_timeout_s:
                logger.warning('No dispatcher heartbeat ack for %.1fs; '
                               'abandoning job', ack_timeout_s)
                break
            if now - last_parent_check > 1.0:
                last_parent_check = now
                if _parent_died(parent_pid):
                    logger.info('Parent %s died; exiting', parent_pid)
                    serve_again = False
                    break
            if term_event is not None and term_event.is_set():
                # graceful release (the supervisor's scale-down path):
                # stop taking work, say BYE, exit — never a heartbeat
                # lapse, so nothing is re-ventilated for a scaling
                # decision
                logger.info('SIGTERM: finishing job and exiting')
                serve_again = False
                break
    finally:
        stop_flag.set()
        executor_thread.join(_EXECUTOR_JOIN_TIMEOUT_S)
        if peer_client is not None:
            # after the join: a live fetch must not race the close
            if peer_cached is not None:
                peer_cached.attach_peer_client(None)
            if peer_live is not None:
                peer_live.pop('client', None)
            peer_client.close()
        if executor_thread.is_alive():
            # A decode is wedged past the join budget: shutting the worker
            # down under the live process() call would close its resources
            # mid-use, and re-registering would stack a second worker on a
            # core the first still burns. Exit the process instead and let
            # the OS reclaim everything.
            logger.warning('Decode still running %.0fs after job end; '
                           'exiting instead of re-registering',
                           _EXECUTOR_JOIN_TIMEOUT_S)
            serve_again = False
        else:
            try:
                worker.shutdown()
            except Exception:  # noqa: BLE001 - best-effort shutdown
                count_swallowed('worker-shutdown')
    return serve_again


def serve(endpoint, worker_id=0, heartbeat_interval_s=1.0,
          ack_timeout_s=None, parent_pid=None, once=False,
          register_timeout_s=None):
    """Serve decode jobs from the dispatcher at ``endpoint`` until orphaned
    (``parent_pid`` died), the registration window closes, or — with
    ``once`` — the first job ends."""
    import zmq

    if ack_timeout_s is None:
        ack_timeout_s = max(10 * heartbeat_interval_s, 10.0)
    # graceful SIGTERM (the supervisor's release path, and any process
    # manager's polite stop): finish the in-flight item, send BYE, exit
    # — instead of the default instant death that reads as a lapse and
    # re-ventilates work. Signal handlers only install on the main
    # thread; an embedded serve() (tests) just skips the grace.
    term_event = threading.Event()
    try:
        signal.signal(signal.SIGTERM,
                      lambda signum, frame: term_event.set())
    except ValueError:
        pass
    # live observability plane: a worker server exposes its OWN /metrics
    # /report /health /trace when PETASTORM_TPU_OBS_PORT is set (use 0 —
    # ephemeral — for multi-worker hosts; the bound port rides every
    # heartbeat summary, so the dispatcher's fleet view says where each
    # worker's endpoint lives). Unarmed: a shared no-op handle.
    status = {'worker_id': worker_id, 'state': 'registering',
              'jobs_served': 0, 'items_done': 0, 'endpoint': endpoint}
    peer_live = {}

    def _health():
        # per-host readahead visibility in fleet mode: each decode host
        # runs its own manager (the plan rides the job spec), so the
        # hit/miss/pool numbers belong on ITS /health, not the client's
        from petastorm_tpu import readahead
        from petastorm_tpu.service import peer_cache
        out = dict(status)
        out['readahead'] = readahead.health_snapshot()
        # fleet cache tier holder view: what this host serves to peers,
        # and (while a job runs) the fetch client's hit/miss/budget
        snap = peer_cache.server_snapshot()
        if snap is not None:
            out['peer_cache'] = snap
        client = peer_live.get('client')
        if client is not None:
            out.setdefault('peer_cache', {})['client'] = client.stats()
        return out

    obs_mount = obs_server.mount('worker-server', health=_health)
    # fingerprints of decoded caches this host holds, advertised on
    # REGISTER and heartbeats: warm markers on disk plus every job this
    # process served (cache-aware placement, docs/service.md)
    from petastorm_tpu.service import placement
    known_fps = set(placement.advertised_fingerprints(
        knobs.get_str('PETASTORM_TPU_DECODED_CACHE_DIR')))
    # fleet cache tier: with a host-local cache dir configured, start the
    # peer serve socket BEFORE registering — the startup scan makes the
    # REGISTER advert carry everything this host kept across restarts,
    # so the directory is complete before the first WORK is assigned
    from petastorm_tpu.service import peer_cache
    peer_server = None
    if peer_cache.peer_cache_enabled():
        cache_dir = knobs.get_str('PETASTORM_TPU_DECODED_CACHE_DIR')
        if cache_dir:
            try:
                peer_server = peer_cache.get_server(cache_dir)
            except Exception:  # noqa: BLE001 - the tier is advisory
                count_swallowed('peer-server-start')
    try:
        while True:
            # Fresh socket (and identity) per job lifetime: a stale
            # DEALER can hold buffered frames from the previous
            # dispatcher incarnation.
            context = zmq.Context()
            sock = context.socket(zmq.DEALER)
            sock.setsockopt(zmq.IDENTITY,
                            ('worker-%d-%s'
                             % (worker_id, uuid.uuid4().hex[:8])).encode())
            sock.setsockopt(zmq.LINGER, 500)
            sock.connect(endpoint)
            try:
                status['state'] = 'registering'
                spec_payload, token = _register(
                    sock, parent_pid, register_timeout_s,
                    term_event=term_event,
                    cache_fps=sorted(known_fps)[:placement.MAX_ADVERTISED],
                    peer_server=peer_server)
                if spec_payload is None:
                    return
                status['state'] = 'serving'
                serve_again = _run_job(sock, spec_payload, worker_id,
                                       heartbeat_interval_s, ack_timeout_s,
                                       parent_pid, status=status,
                                       token=token, term_event=term_event,
                                       known_fps=known_fps,
                                       endpoint=endpoint,
                                       peer_server=peer_server,
                                       peer_live=peer_live)
                status['jobs_served'] += 1
                try:
                    sock.send_multipart([proto.MSG_BYE])
                except Exception:  # noqa: BLE001 - dispatcher may be gone
                    count_swallowed('worker-bye-send')
            finally:
                sock.close(linger=500)
                context.term()
            if once or not serve_again:
                return
    finally:
        peer_cache.close_server()
        obs_mount.close()


def main(argv=None):
    parser = argparse.ArgumentParser(
        description='petastorm_tpu decode worker server')
    parser.add_argument('--endpoint', required=True,
                        help='dispatcher tcp:// endpoint to register with')
    parser.add_argument('--worker-id', type=int, default=0)
    parser.add_argument('--heartbeat-interval', type=float, default=1.0,
                        help='seconds between heartbeats; the dispatcher '
                             'declares a worker dead after its liveness '
                             'timeout without one')
    parser.add_argument('--ack-timeout', type=float, default=None,
                        help='exit the current job after this long without '
                             'a dispatcher heartbeat ack '
                             '(default max(10*interval, 10s))')
    parser.add_argument('--parent-pid', type=int, default=None,
                        help='exit when this process dies (for locally '
                             'spawned fleets)')
    parser.add_argument('--once', action='store_true',
                        help='exit after the first job instead of '
                             're-registering')
    parser.add_argument('--register-timeout', type=float, default=None,
                        help='give up when no dispatcher answers within '
                             'this many seconds (default: retry forever)')
    parser.add_argument('--cache-dir', default=None,
                        help='host-local directory for the materialized '
                             'decoded-row-group cache: every job this '
                             'server (re-)registers for shares it, so N '
                             'jobs over one dataset decode each row-group '
                             'once per host (same as setting '
                             'PETASTORM_TPU_DECODED_CACHE_DIR)')
    parser.add_argument('--obs-port', type=int, default=None,
                        help='expose this server\'s live observability '
                             'endpoint (/metrics /report /health /trace) '
                             'on this port; 0 picks a free one (same as '
                             'setting PETASTORM_TPU_OBS_PORT; the bound '
                             'port rides the heartbeat summaries into '
                             "the dispatcher's fleet view)")
    parser.add_argument('-v', '--verbose', action='store_true')
    args = parser.parse_args(argv)
    # this process decodes for others: its CPU ships as worker CPU
    from petastorm_tpu.telemetry.registry import set_process_role
    set_process_role('worker')
    if args.cache_dir:
        knobs.set_env('PETASTORM_TPU_DECODED_CACHE_DIR', args.cache_dir)
    if args.obs_port is not None:
        knobs.set_env('PETASTORM_TPU_OBS_PORT', str(args.obs_port))
    logging.basicConfig(
        level=logging.DEBUG if args.verbose else logging.INFO,
        format='%(asctime)s worker-server[%(process)d] %(message)s')
    # Decode workers must never grab the TPU chip a trainer owns — hard
    # override, exactly like exec_in_new_process: trainer hosts commonly
    # export JAX_PLATFORMS=tpu and the inherited value must not win.
    os.environ['JAX_PLATFORMS'] = 'cpu'
    serve(args.endpoint, worker_id=args.worker_id,
          heartbeat_interval_s=args.heartbeat_interval,
          ack_timeout_s=args.ack_timeout, parent_pid=args.parent_pid,
          once=args.once, register_timeout_s=args.register_timeout)
    return 0


if __name__ == '__main__':
    sys.exit(main())
