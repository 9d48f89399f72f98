"""Socket search server — asyncio front-end over the wire protocol.

Parity: SearchService (/root/reference/AnnService/src/Server/
SearchService.cpp:90-262) + Socket::Server (inc/Socket/Server.h:20-49,
src/Socket/Connection.cpp): 16-byte packet framing, register handshake
(Connection.cpp:351-371), heartbeat responses (:316-347), SearchRequest ->
RemoteQuery body -> executor -> SearchResponse with RemoteSearchResult body;
interactive stdin mode (SearchService.cpp:157-199).

TPU reshape: instead of one worker thread per query (boost thread_pool,
SearchService.cpp:114-130), concurrent requests are COALESCED — an asyncio
micro-batcher gathers the queries that are waiting and executes them as one
device batch (service.SearchExecutor.execute_batch), which is how the
hardware wants its load delivered.  It waits `batch_window_ms` for company
only where the last window says waiting brings some: not for a lone caller
whose window closed on its one request, and not for what queued while a
batch executed once a window has brought such a backlog next to nothing;
and past the window, for the callers just answered, where the batch they
were in held the executor so long that the wait is a quarter of it at
most (`_batcher`).
"""

from __future__ import annotations

import asyncio
import functools
import logging
import time
from typing import Dict, List, Optional, Tuple

from sptag_tpu.serve import admission as admission_mod
from sptag_tpu.serve import canary as canary_mod
from sptag_tpu.serve import controller as controller_mod
from sptag_tpu.serve import protocol, wire
from sptag_tpu.serve import slo as slo_mod
from sptag_tpu.serve.metrics_http import MetricsHttpServer
from sptag_tpu.serve.service import SearchExecutor, ServiceContext
from sptag_tpu.utils import (faultinject, flightrec, hostprof, locksan,
                             metrics, qualmon, timeline, trace)

log = logging.getLogger(__name__)


def _count_batch(size: int) -> None:
    """One count per gathered batch, under the rung of the shared
    query-count padding ladder (utils.QUERY_BUCKETS) its size falls on:
    which compiled program the batch runs and, as rates, how batch sizes
    are distributed.  (FLAT's own ladder pads 129..512 queries to 512.)
    Literal names, one call each: the registry never expires a name."""
    if size <= 1:
        metrics.inc("server.batches_q1")
    elif size <= 8:
        metrics.inc("server.batches_q8")
    elif size <= 32:
        metrics.inc("server.batches_q32")
    elif size <= 128:
        metrics.inc("server.batches_q128")
    elif size <= 256:
        metrics.inc("server.batches_q256")
    else:
        metrics.inc("server.batches_q1024")


#: body-size ceiling, shared with every framing reader (see wire.py)
MAX_BODY_LENGTH = wire.MAX_BODY_LENGTH

#: backlogs that leave at once after a window brought one a trickle of
#: the callers just answered, before the next waits the window again
#: (`SearchServer._batcher`): one window in 32 cycles costs a saturated
#: server under 1 % of its time, and a changed crowd of callers is
#: misjudged for 32 batches at most
TRICKLE_BATCHES = 32

#: how far past its window a gather waits for the callers just answered,
#: as a share of the time their batch held the executor
#: (`SearchServer._batcher`): 49 ms after a 196 ms graph walk, and
#: nothing where that share would add less than one more window (under
#: 16 ms a batch at the default 2 ms window: where an eighth began to
#: count too, so a batch that earned no wait before PR 45 earns none
#: now).  The wait ends the moment the callers are all back, so the
#: share is what a crowd that does NOT return costs, once:  `trickle`
#: then sends the next 32 backlogs on at once, a quarter of one batch
#: in 33, under 1 % of a saturated server's time.  Callers that do
#: return are worth any wait shorter than the batch itself: sent on
#: without them the batch splits in two that take turns on the device,
#: each padded to its rung.  An eighth (PR 32, walks of 330-420 ms)
#: closed the gather on 111 of 128 callers once a walk took 196 ms and
#: the other 17 waited a whole cycle more: p95 was TWO cycles (PERF.md,
#: PR 45)
PATIENCE_SHARE = 1.0 / 4

#: the event loop's heartbeat (`SearchServer._beat`): a timer re-armed
#: from its own callback records how late the loop ran it
#: (`server.loop_lag`): what a frame arriving at a random instant waits
#: before the loop can read it.  100 wake-ups a second
HEARTBEAT_S = 0.010


class SearchServer:
    def __init__(self, context: ServiceContext,
                 batch_window_ms: float = 2.0,
                 max_batch: int = 1024,
                 max_connections: int = 256,
                 drain_timeout_s: float = 15.0,
                 metrics_port: Optional[int] = None,
                 slow_query_threshold_ms: Optional[float] = None,
                 max_response_tasks: int = 8,
                 flight_recorder: Optional[bool] = None,
                 flight_dump_dir: Optional[str] = None,
                 flight_tier: str = "server",
                 quality_sample_rate: Optional[float] = None,
                 quality_recall_floor: Optional[float] = None,
                 admission: Optional[
                     admission_mod.AdmissionController] = None,
                 fault_spec: Optional[str] = None,
                 fault_seed: Optional[int] = None,
                 host_prof_hz: Optional[float] = None,
                 host_prof_dump_on_slow_query: Optional[bool] = None,
                 timeline_interval_ms: Optional[float] = None,
                 canary_interval_ms: Optional[float] = None,
                 slo_config: Optional[slo_mod.SloConfig] = None,
                 controller_config: Optional[
                     controller_mod.ControllerConfig] = None):
        self.context = context
        self.executor = SearchExecutor(context)
        self.batch_window = batch_window_ms / 1000.0
        self.max_batch = max_batch
        # observability overrides; None = the [Service] ini settings
        # (MetricsPort 0 disables, negative binds OS-ephemeral;
        # SlowQueryThresholdMs 0 disables)
        self.metrics_port = (metrics_port if metrics_port is not None
                             else context.settings.metrics_port)
        self.slow_query_threshold_ms = (
            slow_query_threshold_ms if slow_query_threshold_ms is not None
            else context.settings.slow_query_threshold_ms)
        # flight recorder (ISSUE 5): the recorder itself is process-wide
        # (utils/flightrec.py); this server contributes events under
        # `flight_tier` — tests running several tiers in one process give
        # each a distinct tier so the exported trace keeps one Perfetto
        # process per tier
        self.flight_recorder = (
            flight_recorder if flight_recorder is not None
            else context.settings.flight_recorder)
        self.flight_dump_dir = (
            flight_dump_dir if flight_dump_dir is not None
            else context.settings.flight_dump_on_slow_query)
        self.flight_tier = flight_tier
        # search-quality monitor (utils/qualmon.py, ISSUE 7): process-
        # wide like the flight recorder; ctor overrides are the test
        # surface, [Service] QualitySampleRate/... the deployment one
        self.quality_sample_rate = (
            quality_sample_rate if quality_sample_rate is not None
            else context.settings.quality_sample_rate)
        self.quality_recall_floor = (
            quality_recall_floor if quality_recall_floor is not None
            else context.settings.quality_recall_floor)
        self._metrics_http: Optional[MetricsHttpServer] = None
        # reference parity: ConnectionManager hands out at most 256
        # connection slots (/root/reference/AnnService/inc/Socket/
        # ConnectionManager.h:23-67); excess clients are closed at accept
        self.max_connections = max_connections
        # bound on how long one connection's drain() may block the batcher
        # (slow-reader eviction; see _send)
        self.drain_timeout_s = drain_timeout_s
        self._next_cid = 1
        self._conns: Dict[int, Tuple[asyncio.StreamWriter,
                                     asyncio.Lock]] = {}
        # bounded: 256 pipelining connections could otherwise queue
        # requests without limit (memory exhaustion the connection cap
        # alone doesn't prevent); a full queue answers Dropped immediately
        # — the reference's thread-pool depth plays the same role
        self._queue: asyncio.Queue = asyncio.Queue(maxsize=8 * max_batch)
        self._server: Optional[asyncio.AbstractServer] = None
        self._batcher_task: Optional[asyncio.Task] = None
        self._heartbeat: Optional[asyncio.Handle] = None
        # what the arrival and departure sums are counted from
        # (`server.clock_origin_s`): a perf_counter reading, taken in
        # start()
        self._clock_origin = 0.0
        # the loop thread's CPU seconds at the last batch's t_assembled
        self._loop_cpu: Optional[float] = None
        # response handoff (ISSUE 4 satellite): encoding + draining a
        # batch's responses runs in a SEPARATE task so the batcher
        # assembles and executes batch N+1 while batch N's responses
        # drain.  The semaphore bounds in-flight response batches — a
        # slow drain backpressures the batcher instead of queueing
        # unbounded encoded responses.
        self._response_sem = asyncio.Semaphore(max(1, max_response_tasks))
        self._response_tasks: set = set()
        # per-query streamed sends are bounded too: past this many live
        # response tasks a query's response falls back to the batch-tail
        # task (which rides the semaphore) instead of spawning — without
        # it a slow-reading client accumulates one task + encoded body
        # per streamed query across every batch in its drain window
        self._max_stream_tasks = max_batch
        # overload defense (ISSUE 8, serve/admission.py): the controller
        # reads queue fill + scheduler slot-wait p99 + pool occupancy and
        # moves normal -> degrade -> shed; ctor override is the test
        # surface, [Service] AdmissionControl the deployment one.  None =
        # off: one `is None` test per request.
        if admission is not None:
            self.admission: Optional[
                admission_mod.AdmissionController] = admission
            admission.bind_signals(self._admission_signals)
        elif context.settings.admission_control:
            self.admission = admission_mod.AdmissionController(
                admission_mod.config_from_settings(context.settings),
                signals=self._admission_signals)
        else:
            self.admission = None
        # host sampling profiler (utils/hostprof.py, ISSUE 10): process-
        # wide like the flight recorder; ctor overrides are the test
        # surface, [Service] HostProfHz/... the deployment one
        self.host_prof_hz = (
            host_prof_hz if host_prof_hz is not None
            else context.settings.host_prof_hz)
        self.host_prof_dump_on_slow_query = (
            host_prof_dump_on_slow_query
            if host_prof_dump_on_slow_query is not None
            else context.settings.host_prof_dump_on_slow_query)
        # serving timeline + SLO engine + canary prober (ISSUE 15): all
        # process-wide-off by default; ctor overrides are the test
        # surface, [Service] TimelineIntervalMs/Slo*/Canary* the
        # deployment one
        self.timeline_interval_ms = (
            timeline_interval_ms if timeline_interval_ms is not None
            else context.settings.timeline_interval_ms)
        self.canary_interval_ms = (
            canary_interval_ms if canary_interval_ms is not None
            else context.settings.canary_interval_ms)
        self._slo_config = (slo_config if slo_config is not None
                            else slo_mod.config_from_settings(
                                context.settings))
        self._controller_config = (
            controller_config if controller_config is not None
            else controller_mod.config_from_settings(context.settings))
        self._controller: Optional[controller_mod.Controller] = None
        self._slo: Optional[slo_mod.SloEngine] = None
        self._canary: Optional[canary_mod.CanaryProber] = None
        # connections whose decoded rids identified them as canary
        # traffic: excluded from admission fair shares from their next
        # request on (the canary keeps one persistent connection, so
        # only its very first probe is share-charged)
        self._canary_cids: set = set()
        # default per-request deadline (requests carrying their own —
        # wire trailer or $deadlinems text option — keep it)
        self.deadline_ms = context.settings.deadline_ms
        # wire-layer fault injection (utils/faultinject.py): a per-server
        # injector when a spec is given (tests run several differently-
        # faulty shards in one process), else the process-global one
        # (env SPTAG_FAULTINJECT; disabled when unset)
        spec = (fault_spec if fault_spec is not None
                else context.settings.fault_inject)
        if spec:
            self._fault = faultinject.Injector(
                spec, fault_seed if fault_seed is not None
                else context.settings.fault_inject_seed)
        else:
            self._fault = faultinject.global_injector()

    def _admission_signals(self) -> dict:
        """Live pressure signals for the admission controller: request
        queue fill, the continuous-batching scheduler's slot-wait p99
        and pool occupancy (both zero for dense/FLAT-only serving — the
        queue fraction then carries the whole signal).  With MeshServe
        (ISSUE 11) the slot pools span the shard axis, so these same
        gauges are MESH-WIDE readings; the shard-count gauge rides along
        so /debug/admission shows the scope a decision covered."""
        h = metrics.histogram_or_none("scheduler.slot_wait")
        return {
            "queue_frac": (self._queue.qsize()
                           / max(self._queue.maxsize, 1)),
            "slot_wait_p99_ms": (h.percentile(99) * 1000.0
                                 if h is not None else 0.0),
            "occupancy": metrics.gauge_value("scheduler.occupancy"),
            "mesh_shards": metrics.gauge_value("scheduler.mesh_shards"),
        }

    # ------------------------------------------------------------- lifecycle

    async def start(self, host: Optional[str] = None,
                    port: Optional[int] = None) -> Tuple[str, int]:
        host = host or self.context.settings.listen_addr
        port = port if port is not None else self.context.settings.listen_port
        if self.metrics_port or self.slow_query_threshold_ms > 0:
            # the slow-query log wants request-id-stamped records even
            # with the HTTP endpoint disabled
            metrics.install_request_id_logging()
        if self.flight_recorder:
            flightrec.configure(
                enabled=True,
                max_events=self.context.settings.flight_recorder_events
                or None,
                dump_dir=self.flight_dump_dir or None)
        if self.context.settings.lock_contention_ledger:
            # ctor-built contexts (tests) never ran from_ini's early
            # enable; late enabling still covers every SanLock at its
            # next acquire
            locksan.enable_contention()
        if self.host_prof_hz > 0:
            # arm + start the host sampler (utils/hostprof.py).  At the
            # default HostProfHz=0 this branch never runs: no sampler
            # thread, stage pins stay one flag test (the parity contract)
            hostprof.configure(
                hz=self.host_prof_hz,
                max_samples=self.context.settings.host_prof_events
                or None,
                dump_on_slow_query=self.host_prof_dump_on_slow_query
                or None)
            hostprof.start()
        if self.context.settings.mesh_serve:
            # in-mesh sharded serving (ISSUE 11): arm the mesh-wide
            # continuous-batching spine on every registered mesh index
            # (parallel/sharded.py ServingAdapter) — shard-local search
            # + ICI top-k merge run as one compiled dispatch and
            # responses stream in retire order.  Default off: mesh
            # adapters keep the synchronous whole-batch path and serve
            # bytes stay byte-identical (the ci_check.sh parity pass).
            for name, index in self.context.indexes.items():
                enable = getattr(index, "enable_mesh_serve", None)
                if enable is None:
                    continue
                kw = {}
                if self.context.settings.mesh_serve_slots > 0:
                    kw["slots"] = self.context.settings.mesh_serve_slots
                if self.context.settings.mesh_serve_segment_iters > 0:
                    kw["segment_iters"] = (
                        self.context.settings.mesh_serve_segment_iters)
                if enable(**kw):
                    metrics.inc("server.mesh_serve_indexes")
                    log.info("MeshServe armed on index %s", name)
        if self.quality_sample_rate > 0:
            qualmon.configure(
                sample_rate=self.quality_sample_rate,
                recall_floor=self.quality_recall_floor,
                shadow_budget_gflops=self.context.settings
                .quality_shadow_budget,
                window=self.context.settings.quality_window or None)
            # seed the per-shard health series under the serving index
            # names (mutation paths republish under the same labels)
            for name, index in self.context.indexes.items():
                if hasattr(index, "publish_quality_health"):
                    index.publish_quality_health(shard=name)
        # serving timeline + SLO engine (ISSUE 15): the SLO engine
        # needs history, so declaring any objective arms the timeline
        # implicitly at the default cadence
        slo_armed = slo_mod.armed(self._slo_config)
        if self.timeline_interval_ms > 0 or slo_armed \
                or self.canary_interval_ms > 0:
            timeline.configure(
                enabled=True,
                interval_ms=(self.timeline_interval_ms
                             if self.timeline_interval_ms > 0 else None),
                capacity=self.context.settings.timeline_events or None)
            timeline.start()
        if slo_armed:
            self._slo = slo_mod.SloEngine(self._slo_config,
                                          tier=self.flight_tier)
            timeline.add_tick_listener(self._slo.evaluate)
        if controller_mod.armed(self._controller_config):
            # closed loop (ISSUE 17): the controller acts on the SLO
            # engine's judgement — with no declared objective there is
            # nothing to act on, so the loop stays open rather than
            # actuating blind
            if self._slo is None:
                log.warning("Controller=1 but no SLO objective "
                            "declared; controller stays off")
            else:
                self._controller = controller_mod.Controller(
                    self._controller_config, tier=self.flight_tier)
                self._controller.bind_slo(self._slo)
                for name, index in self.context.indexes.items():
                    self._controller.bind_index(name, index)
                if self.admission is not None:
                    adm_cfg = self.admission.config
                    self._controller.bind_tier_knob(
                        "DegradeMaxCheckFloor",
                        read=lambda c=adm_cfg: float(
                            c.degrade_max_check_floor),
                        apply=lambda v, c=adm_cfg: setattr(
                            c, "degrade_max_check_floor", int(v)))
                timeline.add_tick_listener(self._controller.evaluate)
        if self.metrics_port:
            # bind the metrics listener FIRST: an EADDRINUSE here must
            # fail start() before the serve socket accepts or the batcher
            # exists — no half-started server to clean up
            self._metrics_http = MetricsHttpServer(
                self.metrics_port, health=self._healthz,
                host=self.context.settings.metrics_host,
                admission=self._admission_debug,
                mutation=self._mutation_debug,
                slo=self._slo_debug,
                controller=self._controller_debug)
            self._metrics_http.start()
        # every stamp a request gets is on time.perf_counter(): on Linux
        # CLOCK_MONOTONIC, one clock for every process of the machine, so
        # a caller that stamps its sends and reads with it can place the
        # server's mean arrival and departure instants (the two
        # record_sum entries of _serve_batch and _respond_*) on its own
        # time line.  The origin keeps those sums small
        self._clock_origin = time.perf_counter()
        metrics.set_gauge("server.clock_origin_s", self._clock_origin)
        self._server = await asyncio.start_server(self._on_client, host, port)
        self._batcher_task = asyncio.create_task(self._batcher())
        loop = asyncio.get_event_loop()
        self._heartbeat = loop.call_soon(self._beat, loop, loop.time())
        addr = self._server.sockets[0].getsockname()
        log.info("search server listening on %s:%d", addr[0], addr[1])
        if self.canary_interval_ms > 0:
            # ground-truth canary (serve/canary.py): probes pinned via
            # the oracle at (re)start, replayed through THIS server's
            # own socket — armed after the listen socket exists
            probes = canary_mod.probes_from_context(
                self.context, count=self.context.settings.canary_probes,
                k=self.context.settings.canary_k)
            self._canary = canary_mod.CanaryProber(
                addr[0], addr[1], probes,
                interval_ms=self.canary_interval_ms,
                tier=self.flight_tier)
            self._canary.start()
        return addr[0], addr[1]

    async def stop(self) -> None:
        if self._canary is not None:
            # run the (blocking, up-to-join-timeout) prober shutdown off
            # the loop thread
            canary_ref = self._canary
            self._canary = None
            await asyncio.get_event_loop().run_in_executor(
                None, canary_ref.stop)
        if self._controller is not None:
            timeline.remove_tick_listener(self._controller.evaluate)
            self._controller = None
        if self._slo is not None:
            timeline.remove_tick_listener(self._slo.evaluate)
            self._slo = None
        if self._metrics_http:
            self._metrics_http.shutdown()
            self._metrics_http = None
        if self._heartbeat is not None:
            self._heartbeat.cancel()
            self._heartbeat = None
        if self._batcher_task:
            self._batcher_task.cancel()
        for task in list(self._response_tasks):
            task.cancel()
        if self._server:
            self._server.close()
            await self._server.wait_closed()

    def _beat(self, loop: asyncio.AbstractEventLoop, due: float) -> None:
        """The heartbeat: how long after `due` the loop got to this
        callback — its own callbacks ahead of it plus its wait for the
        interpreter lock — then the next one, HEARTBEAT_S on.  A timer
        may fire a clock tick early: no lag is negative."""
        now = loop.time()
        trace.record("server.loop_lag", max(0.0, now - due))
        due = now + HEARTBEAT_S
        self._heartbeat = loop.call_at(due, self._beat, loop, due)

    def _healthz(self) -> dict:
        """/healthz payload: load state per registered index (sample count,
        value type, non-default params) plus live connection/queue depth."""
        indexes = {}
        for name, index in self.context.indexes.items():
            info = {"samples": int(getattr(index, "num_samples", -1))}
            vt = getattr(index, "value_type", None)
            if vt is not None:
                info["value_type"] = getattr(vt, "name", str(vt))
            params = getattr(index, "params", None)
            if params is not None and hasattr(params, "non_default_items"):
                info["non_default_params"] = dict(params.non_default_items())
            ms = getattr(index, "mutation_state", None)
            if ms is not None:
                # swap/durability state (ISSUE 9): epoch, WAL accounting,
                # delta occupancy, in-flight refine — the numbers an
                # operator watches to see a snapshot swap land
                info["mutation"] = ms()
            indexes[name] = info
        return {"status": "ok" if indexes else "empty",
                "indexes": indexes,
                "connections": len(self._conns),
                "queue_depth": self._queue.qsize()}

    def _admission_debug(self) -> dict:
        """GET /debug/admission payload: controller state + fault-
        injection plan + deadline accounting for this tier."""
        out = {"enabled": self.admission is not None, "tier": "server"}
        if self.admission is not None:
            out.update(self.admission.snapshot())
        out["faultinject"] = (self._fault.snapshot()
                              if self._fault.enabled
                              else {"enabled": False})
        out["deadline_drops"] = metrics.counter_value(
            "server.deadline_drops")
        return out

    def _slo_debug(self) -> dict:
        """GET /debug/slo payload: the burn-rate engine's objectives
        plus the canary prober's per-index picture (one page tells the
        whole judgement story)."""
        out = (self._slo.snapshot() if self._slo is not None
               else {"enabled": False})
        out["tier"] = self.flight_tier
        if self._canary is not None:
            out["canary"] = self._canary.snapshot()
        return out

    def _controller_debug(self) -> dict:
        """GET /debug/controller payload: the control loop's full
        decision picture — current inputs, actuator positions vs
        baselines, and the audit ring."""
        if self._controller is None:
            return {"enabled": False, "tier": self.flight_tier}
        return self._controller.snapshot()

    def _mutation_debug(self) -> dict:
        """GET /debug/mutation payload: per-index swap/durability state
        plus the process-wide mutation counters."""
        indexes = {}
        for name, index in self.context.indexes.items():
            ms = getattr(index, "mutation_state", None)
            if ms is not None:
                try:
                    indexes[name] = ms()
                except Exception:                        # noqa: BLE001
                    log.exception("mutation_state failed for %s", name)
                    indexes[name] = {"error": True}
        return {
            "tier": "server",
            "indexes": indexes,
            "wal_appends": metrics.counter_value("mutation.wal_appends"),
            "swaps": metrics.counter_value("mutation.swaps"),
            "refine_errors": metrics.counter_value(
                "mutation.refine_errors"),
        }

    # ------------------------------------------------------------ connection

    async def _on_client(self, reader: asyncio.StreamReader,
                         writer: asyncio.StreamWriter) -> None:
        if len(self._conns) >= self.max_connections:
            # slot table full — close at accept, like the reference's
            # ConnectionManager returning no slot
            metrics.inc("server.rejected_connections")
            log.warning("connection limit (%d) reached; rejecting client",
                        self.max_connections)
            writer.close()
            return
        cid = self._next_cid
        self._next_cid += 1
        # per-connection write lock: the reader task (register/heartbeat/
        # shed responses) and the batcher task both write+drain the same
        # StreamWriter; two concurrent drain() waiters trip an assertion
        # inside asyncio's FlowControlMixin on Python 3.10/3.11 and would
        # kill the batcher — all writes serialize through this lock
        self._conns[cid] = (writer, asyncio.Lock())
        metrics.set_gauge("server.connections", len(self._conns))
        try:
            while True:
                head = await reader.readexactly(wire.HEADER_SIZE)
                header = wire.PacketHeader.unpack(head)
                if not 0 <= header.body_length <= MAX_BODY_LENGTH:
                    metrics.inc("server.malformed_packets")
                    log.warning("cid %d: body_length %d exceeds cap; "
                                "closing", cid, header.body_length)
                    break
                body = (await reader.readexactly(header.body_length)
                        if header.body_length else b"")
                await self._dispatch(cid, header, body)
        except (asyncio.IncompleteReadError, ConnectionResetError):
            pass
        except Exception:                                    # noqa: BLE001
            # malformed header/body must cost only THIS connection, never
            # the server: log and drop the client
            metrics.inc("server.malformed_packets")
            log.exception("cid %d: malformed packet; closing", cid)
        finally:
            self._conns.pop(cid, None)
            self._canary_cids.discard(cid)
            metrics.set_gauge("server.connections", len(self._conns))
            writer.close()

    async def _send(self, cid: int, payload: bytes) -> None:
        """Locked write+drain on a connection (see _on_client for why).

        Self-contained failure handling: the ONE batcher task services
        every connection, so a send must never take it down (any OSError
        -> drop that client) nor wedge it (a client that stops reading
        blocks drain() at the high-water mark forever -> bounded wait,
        then evict the slow reader).  Head-of-line blocking across
        connections is otherwise this design's DoS surface."""
        entry = self._conns.get(cid)
        if entry is None:
            return
        writer, lock = entry
        try:
            async with lock:
                writer.write(payload)
                await asyncio.wait_for(writer.drain(),
                                       timeout=self.drain_timeout_s)
        except asyncio.TimeoutError:
            metrics.inc("server.drain_timeouts")
            log.warning("cid %d: response drain exceeded %.0fs (client "
                        "not reading); evicting", cid,
                        self.drain_timeout_s)
            self._conns.pop(cid, None)
            # abort, not close: a graceful close waits for the very write
            # buffer the non-reading peer will never drain — the FD, the
            # buffered bytes, and the wedged reader task would all leak
            # (and the freed connection slot lets the attacker repeat)
            writer.transport.abort()
        except OSError:
            # BrokenPipeError / ConnectionResetError / anything transport:
            # the reader task's readexactly will observe the close and
            # clean up; the batcher must not die
            metrics.inc("server.send_errors")
            self._conns.pop(cid, None)
            writer.transport.abort()

    async def _dispatch(self, cid: int, header: wire.PacketHeader,
                        body: bytes) -> None:
        t = header.packet_type
        if t == wire.PacketType.RegisterRequest:
            # Connection::HandleRegisterRequest (Connection.cpp:351-363)
            resp = wire.PacketHeader(wire.PacketType.RegisterResponse,
                                     wire.PacketProcessStatus.Ok, 0, cid,
                                     header.resource_id)
            await self._send(cid, resp.pack())
        elif t == wire.PacketType.HeartbeatRequest:
            resp = wire.PacketHeader(wire.PacketType.HeartbeatResponse,
                                     wire.PacketProcessStatus.Ok, 0,
                                     header.connection_id,
                                     header.resource_id)
            await self._send(cid, resp.pack())
        elif t == wire.PacketType.SearchRequest:
            with trace.annotate("server.dispatch"):
                shed = self._admit_and_enqueue(cid, header, body)
            if shed is not None:
                await self._send(cid, shed)
        elif wire.is_request(t):
            # HandleNoHandlerResponse (Connection.cpp:374-398)
            resp = wire.PacketHeader(wire.response_type(t),
                                     wire.PacketProcessStatus.Dropped, 0,
                                     cid, header.resource_id)
            await self._send(cid, resp.pack())

    def _admit_and_enqueue(self, cid: int, header: wire.PacketHeader,
                           body: bytes) -> Optional[bytes]:
        """The synchronous part of a SearchRequest: admission, decode,
        deadline, enqueue.  Returns the response to send at once where
        the request is shed (admission, or a full queue), else None."""
        metrics.inc("server.requests")
        rec = flightrec.enabled()
        degraded = False
        if self.admission is not None:
            # canary isolation (ISSUE 15): admission runs pre-decode
            # keyed by connection, so canary connections are marked
            # at their first probe's decode (below) and exempted
            # from fair-share accounting from then on
            decision = self.admission.admit(
                str(cid), canary=cid in self._canary_cids)
            if decision == admission_mod.SHED:
                # reject at the socket edge with a DISTINCT status
                # BEFORE decode cost is paid — under overload, body
                # decode is the attack surface (the body bytes were
                # already read to keep the stream aligned, but never
                # parsed)
                metrics.inc("server.admission_sheds")
                if rec:
                    flightrec.record(self.flight_tier, "shed")
                shed = wire.RemoteSearchResult(
                    wire.ResultStatus.Overloaded, []).pack()
                resp = wire.PacketHeader(
                    wire.PacketType.SearchResponse,
                    wire.PacketProcessStatus.Dropped, len(shed),
                    cid, header.resource_id)
                return resp.pack() + shed
            degraded = decision == admission_mod.DEGRADE
        t_dec0 = time.monotonic_ns() if rec else 0
        hp = hostprof.armed()
        if hp:
            # serve-stage pin (utils/hostprof.py, ISSUE 10): samples
            # landing on the loop thread during decode fold under
            # stage:decode (the rid is unknown until unpack returns)
            hostprof.set_stage("decode")
        with trace.span("server.decode"):
            query = wire.RemoteQuery.unpack(body)
        if query is None:
            # a SearchRequest whose body does not decode still gets a
            # FailedExecute answer downstream, but must be countable
            metrics.inc("server.malformed_packets")
        elif not query.request_id:
            # text-protocol id channel (reference clients can't set
            # the wire field); stays empty if neither is present
            query.request_id = protocol.request_id_of(query.query) or ""
        else:
            # the wire field is attacker-sized (up to the body cap);
            # it rides into every log line and response — bound it
            # like the text channel does
            query.request_id = query.request_id[:64]
        if query is not None and query.request_id \
                and canary_mod.is_canary_rid(query.request_id) \
                and cid not in self._canary_cids:
            self._canary_cids.add(cid)
        if rec:
            flightrec.record(
                self.flight_tier, "decode",
                query.request_id if query is not None else "",
                dur_ns=time.monotonic_ns() - t_dec0)
        if hp:
            hostprof.clear_stage()
        # deadline resolution (ISSUE 8): the wire trailer wins, the
        # $deadlinems text option covers reference clients, then the
        # operator's [Service] DeadlineMs default.  The value is a
        # RELATIVE budget anchored at THIS arrival (clocks across
        # machines are not assumed synchronized).
        deadline_mono = None
        if query is not None:
            dl = query.deadline_ms \
                or (protocol.deadline_of(query.query) or 0.0)
            if dl <= 0:
                dl = self.deadline_ms
            if dl > 0:
                deadline_mono = time.perf_counter() + dl / 1000.0
        try:
            self._queue.put_nowait((cid, header, query,
                                    time.perf_counter(),
                                    deadline_mono, degraded))
            metrics.set_gauge("server.queue_depth", self._queue.qsize())
            if rec:
                flightrec.record(
                    self.flight_tier, "enqueue",
                    query.request_id if query is not None else "",
                    payload={"depth": self._queue.qsize()})
        except asyncio.QueueFull:
            # shed load at the edge rather than buffering unboundedly;
            # the client sees a definitive, well-formed FailedExecute
            # for THIS request (a body-less Dropped header would break
            # result unpacking on the other side)
            metrics.inc("server.queue_full")
            shed = wire.RemoteSearchResult(
                wire.ResultStatus.FailedExecute, [],
                query.request_id if query is not None else "").pack()
            resp = wire.PacketHeader(wire.PacketType.SearchResponse,
                                     wire.PacketProcessStatus.Dropped,
                                     len(shed), cid, header.resource_id)
            return resp.pack() + shed
        return None

    # --------------------------------------------------------- batched serve

    def _drain(self, batch: list) -> None:
        """Move what is queued NOW into `batch`, up to max_batch: no timer."""
        while len(batch) < self.max_batch:
            try:
                batch.append(self._queue.get_nowait())
            except asyncio.QueueEmpty:
                return

    async def _batcher(self) -> None:
        """Gather batches; wait `batch_window` only where waiting can
        bring company, judged by what the last window brought.

        An arrival at an idle server waits the window, unless the last
        such window, waited in full, closed on one request (`futile`: a
        lone caller); a batch of more than one undoes that, and so does
        anything queued when the executor frees (a second caller).

        What is queued when the executor frees waits the window too:
        callers just answered that come straight back join it (sent on
        at once, a handful of callers out of step would alternate in
        half batches for good).  A window that brings back fewer than
        half as many as were just answered is a trickle: the callers
        are not back within a window, the next batch is waiting whenever
        the executor frees, and a window only idles the device.  The
        next TRICKLE_BATCHES backlogs then leave at once, after the
        finished batch's replies, before one waits the window again
        (`trickle` counts them down).

        A window whose last batch earned it one more window at least
        (PATIENCE_SHARE of the time it held the executor, less the
        window) goes on past its end, that share at most, until as many
        requests are here as were
        queued when the executor freed plus the callers just answered
        (`server.gather_patient`).  Where a batch is a 420 ms walk and
        its 128 callers take 20 ms to come back, the window's 2 ms split
        a closed loop into groups that took turns on the device, each
        padded to its rung, and how they split (64 + 64, 26 + 102) was
        the first window's accident and moved with every probe (PERF.md,
        PR 32)."""
        loop = asyncio.get_event_loop()
        t_prev = None                # the previous batch's t_assembled
        replied = None               # set once its replies are written
        answered = 0                 # its size
        held = 0.0                   # seconds it held the executor
        futile = False
        trickle = 0
        while True:
            queued = self._queue.qsize()
            if queued:
                futile = False
            backlog = queued > 0 and trickle > 0
            if backlog:
                trickle -= 1
                if replied is not None:
                    # the finished batch's replies leave first: once the
                    # executor thread parses the next batch, the loop
                    # thread that encodes and writes them waits out its
                    # hold on the interpreter lock (3.7 ms on the chip:
                    # PERF.md, PR 31).  A window at most: a connection
                    # that does not read holds no batch longer than that
                    t_wait = time.perf_counter()
                    try:
                        await asyncio.wait_for(replied.wait(),
                                               self.batch_window)
                    except asyncio.TimeoutError:
                        metrics.inc("server.batch_reply_timeouts")
                    trace.record("server.batch_reply_wait",
                                 time.perf_counter() - t_wait)
            first = await self._queue.get()      # sleeps only when idle
            t_first = time.perf_counter()
            batch = [first]
            with trace.annotate("server.gather"):
                self._drain(batch)
            if backlog:
                metrics.inc("server.gather_backlog")
            elif futile:
                metrics.inc("server.gather_lone")
            else:
                metrics.inc("server.gather_window")
                found = len(batch)               # before the window
                deadline = loop.time() + self.batch_window
                patience = PATIENCE_SHARE * held - self.batch_window
                if not 0 < self.batch_window <= patience:
                    patience = 0.0   # no window asked, or not one more
                patient = False                  # past the window
                while len(batch) < self.max_batch:
                    if patient and len(batch) >= queued + answered:
                        break                    # they are all back
                    try:
                        batch.append(await asyncio.wait_for(
                            self._queue.get(), deadline - loop.time()))
                    except asyncio.TimeoutError:
                        if patience and not patient \
                                and len(batch) < queued + answered:
                            metrics.inc("server.gather_patient")
                            patient = True
                            deadline += patience
                            continue
                        if queued and 2 * (len(batch) - found) < answered:
                            trickle = TRICKLE_BATCHES
                        futile = True
                        break
                    with trace.annotate("server.gather"):
                        self._drain(batch)
            if len(batch) > 1:
                futile = False
            answered = len(batch)
            replied = asyncio.Event()
            t_prev = await self._serve_batch(batch, t_first, t_prev,
                                             replied)
            held = time.perf_counter() - t_prev

    async def _serve_batch(self, batch, t_first: float,
                           t_prev: Optional[float],
                           replied: asyncio.Event) -> float:
        """Execute one gathered batch and hand its responses off; returns
        the instant the batch was assembled (the next one's `t_prev`).
        `replied` is set once the batch's replies are with their sockets
        (or nothing of it is left to answer)."""
        t_assembled = time.perf_counter()
        # the batcher's cycle, from timestamps on either side of awaits
        # (an annotation here would name waiting as if it were work)
        trace.record("server.batch_gather", t_assembled - t_first)
        # this (the loop's) thread's CPU seconds over the same cycle:
        # beside the executor thread's (run_batch) it says who held the
        # interpreter, and for how much of the cycle neither did
        loop_cpu = time.thread_time()
        if t_prev is not None:
            trace.record("server.batch_cycle", t_assembled - t_prev)
            trace.record("server.loop_cpu", loop_cpu - self._loop_cpu)
        self._loop_cpu = loop_cpu
        metrics.set_gauge("server.queue_depth", self._queue.qsize())
        _count_batch(len(batch))
        rec = flightrec.enabled()
        # deadline enforcement at the execute boundary (ISSUE 8): a
        # query whose budget ran out while queued gets a Timeout answer
        # NOW instead of burning device time nobody is waiting for —
        # counted and flight-recorded, never silent
        live, expired = [], []
        for e in batch:
            (expired if e[4] is not None and t_assembled >= e[4]
             else live).append(e)
        if expired:
            batch = live
            metrics.inc("server.deadline_drops", len(expired))
            if rec:
                for entry in expired:
                    flightrec.record(
                        self.flight_tier, "deadline_drop",
                        entry[2].request_id
                        if entry[2] is not None else "")
            await self._spawn_response_task(
                self._respond_expired(expired, t_assembled))
            if not batch:
                replied.set()
                return t_assembled
        texts = []
        rids = []
        arrived = 0.0
        with trace.annotate("server.assemble"):
            for cid, header, query, t_enq, _deadline, _deg in batch:
                texts.append(query.query if query is not None else "")
                rids.append(query.request_id if query is not None else "")
                trace.record("server.queue_wait", t_assembled - t_enq)
                arrived += t_enq
                if rec:
                    flightrec.record(
                        self.flight_tier, "queue_wait", rids[-1],
                        dur_ns=int((t_assembled - t_enq) * 1e9))
            # the batch's arrival instants, summed: their mean over a
            # window, against the callers' mean send instant on the same
            # clock, is a request's way in (socket, loop, decode)
            trace.record_sum("server.arrival_clock",
                             arrived - len(batch) * self._clock_origin,
                             len(batch))
        loop = asyncio.get_event_loop()
        # per-query streaming (continuous batching): the executor invokes
        # on_ready from ITS thread as individual queries finish; each
        # marshals onto the loop and sends immediately — a fast query's
        # response leaves while stragglers are still walking, instead of
        # at whole-batch granularity.  A group whose futures were all
        # resolved at submit makes no on_ready call: its answers leave in
        # _respond_batch's joined writes.  Every on_ready lands on the loop
        # BEFORE run_in_executor's completion wakes this coroutine
        # (call_soon_threadsafe is FIFO), so `streamed` is complete when
        # the batch tail below reads it.
        streamed: set = set()

        def on_ready(i, result):
            loop.call_soon_threadsafe(self._stream_response, batch[i],
                                      result, t_assembled, streamed, i)
        deg_flags = [entry[5] for entry in batch]
        deg_floor = (self.admission.config.degrade_max_check_floor
                     if self.admission is not None and any(deg_flags)
                     else None)
        t_returned = None
        try:
            def run_batch():
                if hostprof.armed():
                    # execute-stage pin: rid attribution is EXACT when
                    # the batch carries one request (the straggler /
                    # slow-query case the profiler exists for); mixed
                    # batches record the stage alone — per-query blame
                    # inside a coalesced device batch would be a lie
                    live = [r for r in rids if r]
                    hostprof.set_stage(
                        "execute", live[0] if len(live) == 1 else "")
                cpu0 = time.thread_time()
                try:
                    with trace.span("server.execute_batch"):
                        out = self.executor.execute_batch(
                            texts, on_ready=on_ready, rids=rids,
                            degraded=deg_flags if deg_floor else None,
                            degrade_floor=deg_floor)
                finally:
                    hostprof.clear_stage()
                # this (an executor) thread's CPU seconds in the batch:
                # the most it can have held the interpreter
                trace.record("server.executor_cpu",
                             time.thread_time() - cpu0)
                return out, time.perf_counter()
            results, t_returned = await loop.run_in_executor(None,
                                                             run_batch)
        except Exception:
            metrics.inc("server.batch_failures")
            log.exception("batch execution failed")
            results = [wire.RemoteSearchResult(
                wire.ResultStatus.FailedExecute, [])] * len(batch)
        t_executed = time.perf_counter()
        if t_returned is not None:
            # how long the finished batch waited for the event loop: its
            # wake-up queues behind any on_ready callbacks it posted
            trace.record("server.batch_resume", t_executed - t_returned)
        if rec:
            flightrec.record(
                self.flight_tier, "execute",
                dur_ns=int((t_executed - t_assembled) * 1e9),
                payload={"batch": len(batch)})
        # response handoff (bounded, counted): the batcher returns to
        # assembling batch N+1 while this batch's responses encode+drain
        # in their own task
        if rec:
            flightrec.record(self.flight_tier, "handoff",
                             payload={"batch": len(batch),
                                      "streamed": len(streamed)})

        async def respond():
            try:
                await self._respond_batch(batch, results, streamed,
                                          t_assembled, t_executed)
            finally:
                # executed -> the batch's last reply with its socket
                trace.record("server.batch_reply",
                             time.perf_counter() - t_executed)
                replied.set()
        if self._fault.enabled:
            replied.set()       # an injected delay holds no batch back
        await self._spawn_response_task(respond())
        return t_assembled

    def _stream_response(self, entry, result, t_assembled: float,
                         streamed: set, i: int) -> None:
        """Loop-thread half of the streaming path: mark the query as
        delivered and send its response in its own (tracked) task.
        NOT marking it (over the task cap) is always safe — the batch
        tail sends whatever was not streamed."""
        with trace.annotate("server.stream_response"):
            if len(self._response_tasks) >= self._max_stream_tasks:
                metrics.inc("server.stream_overflows")
                return
            streamed.add(i)
            metrics.inc("server.streamed_responses")
            task = asyncio.ensure_future(
                self._respond_one(entry, result, t_assembled,
                                  time.perf_counter()))
            self._track_response_task(task)

    async def _spawn_response_task(self, coro) -> None:
        await self._response_sem.acquire()
        task = asyncio.ensure_future(coro)
        task.add_done_callback(lambda _t: self._response_sem.release())
        self._track_response_task(task)

    def _track_response_task(self, task: asyncio.Task) -> None:
        self._response_tasks.add(task)
        metrics.set_gauge("server.response_tasks",
                          len(self._response_tasks))

        def _done(t: asyncio.Task) -> None:
            self._response_tasks.discard(t)
            metrics.set_gauge("server.response_tasks",
                              len(self._response_tasks))
            if not t.cancelled() and t.exception() is not None:
                metrics.inc("server.response_task_errors")
                log.error("response task failed: %r", t.exception())
        task.add_done_callback(_done)

    async def _respond_batch(self, batch, results, streamed: set,
                             t_assembled: float, t_executed: float) -> None:
        """Send what the streaming path did not: answers that arrived as
        a batch leave as one — every reply encoded, then ONE locked
        write + drain per connection of that connection's packets joined
        in batch order (the client matches by resource id).  Each packet
        is `_respond_one`'s, byte for byte, and every per-request duty
        (`_after_response`) stays per request."""
        todo = [(entry, result)
                for i, (entry, result) in enumerate(zip(batch, results))
                if i not in streamed]
        if self._fault.enabled:
            # injected faults delay, garble or cut SINGLE responses
            for entry, result in todo:
                await self._respond_one(entry, result, t_assembled,
                                        t_executed)
            return
        by_cid: Dict[int, list] = {}
        with trace.span("server.encode"):
            for entry, result in todo:
                result, payload = self._encode_response(entry, result)
                by_cid.setdefault(entry[0], []).append(
                    (entry, result, payload))
        for cid, sent in by_cid.items():
            t_send0 = time.perf_counter()
            with trace.span("server.drain"):
                await self._send(cid, b"".join(p for _e, _r, p in sent))
            now = time.perf_counter()
            with trace.annotate("server.after_response"):
                for entry, result, _payload in sent:
                    self._after_response(entry, result, t_assembled,
                                         t_executed, t_send0, now)
            # these replies' departure instants, summed (all `now`): the
            # callers' mean read instant less their mean is the way back
            trace.record_sum("server.departure_clock",
                             (now - self._clock_origin) * len(sent),
                             len(sent))

    async def _respond_expired(self, entries, t_assembled: float) -> None:
        """Answer deadline-expired queries with Timeout — cheap, honest,
        and the client (which may already have given up) stays
        stream-aligned either way.  They reached no batch: they are in
        neither `server.arrival_clock` nor `server.departure_clock`."""
        for entry in entries:
            await self._write_one(
                entry, wire.RemoteSearchResult(wire.ResultStatus.Timeout,
                                               []),
                t_assembled, t_assembled)

    async def _apply_fault(self, fault, cid: int,
                           payload: bytes) -> Optional[bytes]:
        """Apply one injected wire fault to this response (utils/
        faultinject.py; test/chaos surface).  Returns the (possibly
        mutated) payload to send, or None when the fault consumed it."""
        if fault.kind == "delay":
            await asyncio.sleep(fault.delay_s)
            return payload
        if fault.kind == "garble":
            # flip the first body byte (the serialized version prologue):
            # framing stays aligned, the body reliably fails decode —
            # the peer must count a malformed body, not crash
            b = bytearray(payload)
            if len(b) > wire.HEADER_SIZE:
                b[wire.HEADER_SIZE] ^= 0xFF
            return bytes(b)
        if fault.kind == "disconnect":
            # die mid-stream: a payload prefix goes out, then the
            # transport aborts — the peer sees an incomplete read
            entry = self._conns.pop(cid, None)
            if entry is not None:
                writer, _lock = entry
                try:
                    writer.write(payload[:max(1, len(payload) // 2)])
                finally:
                    writer.transport.abort()
            return None
        return None                                       # "drop"

    def _encode_response(self, entry, result) -> tuple:
        """One response's packet: -> (the result as answered, header +
        body bytes).  Echoes the request id, marks a degraded Success;
        synchronous, so a caller's `server.encode` span covers it."""
        cid, header, query, _t_enq, _deadline, degraded = entry
        if query is None or result is None:
            result = wire.RemoteSearchResult(
                wire.ResultStatus.FailedExecute, [])
        # echo the request id so the caller (client or aggregator) can
        # match the response to its trace
        rid = query.request_id if query is not None else ""
        result.request_id = rid
        rec = flightrec.enabled()
        if degraded and result.status == wire.ResultStatus.Success:
            # the degraded marker channel (wire minor 2): clients KNOW
            # this answer traded recall for survival
            if wire.MARKER_DEGRADED not in result.markers:
                result.markers.append(wire.MARKER_DEGRADED)
            metrics.inc("server.degraded_responses")
            if rec:
                flightrec.record(self.flight_tier, "degrade", rid)
        t_enc0 = time.monotonic_ns() if rec else 0
        hp = hostprof.armed()
        if hp:
            # per-query encode runs whole on the loop thread between
            # awaits, so the rid pin is exact here
            hostprof.set_stage("encode", rid)
        body = result.pack()
        if hp:
            hostprof.clear_stage()
        if rec:
            flightrec.record(self.flight_tier, "encode", rid,
                             dur_ns=time.monotonic_ns() - t_enc0)
        resp = wire.PacketHeader(
            wire.PacketType.SearchResponse,
            wire.PacketProcessStatus.Ok, len(body), cid,
            header.resource_id)
        return result, resp.pack() + body

    async def _respond_one(self, entry, result, t_assembled: float,
                           t_executed: float) -> None:
        """One answer of a batch in a write of its own (streamed, or any
        while a fault spec is live), and its departure instant."""
        now = await self._write_one(entry, result, t_assembled, t_executed)
        if now is not None:
            trace.record_sum("server.departure_clock",
                             now - self._clock_origin, 1)

    async def _write_one(self, entry, result, t_assembled: float,
                         t_executed: float) -> Optional[float]:
        """Encode, write, drain, `_after_response` -> the instant the
        drain returned; None where an injected fault consumed the reply."""
        cid = entry[0]
        with trace.span("server.encode"):
            result, payload = self._encode_response(entry, result)
        if self._fault.enabled:
            fault = self._fault.decide("server.respond")
            if fault is not None:
                payload = await self._apply_fault(fault, cid, payload)
                if payload is None:
                    return None     # drop / disconnect consumed it
        t_send0 = time.perf_counter()
        with trace.span("server.drain"):
            await self._send(cid, payload)
        now = time.perf_counter()
        self._after_response(entry, result, t_assembled, t_executed,
                             t_send0, now)
        return now

    def _after_response(self, entry, result, t_assembled: float,
                        t_executed: float, t_send0: float,
                        now: float) -> None:
        """What every answered request is owed once its bytes are with
        the socket (its own write, or its connection's joined one):
        the response count, the `server.request` record, flight records,
        the slow-query log, the quality sample."""
        _cid, _header, query, t_enq, _deadline, _degraded = entry
        rid = query.request_id if query is not None else ""
        rec = flightrec.enabled()
        metrics.inc("server.responses")
        total = now - t_enq
        trace.record("server.request", total)
        if rec:
            flightrec.record(self.flight_tier, "drain", rid,
                             dur_ns=int((now - t_send0) * 1e9))
            flightrec.record(self.flight_tier, "request", rid,
                             dur_ns=int(total * 1e9),
                             payload={"status": int(result.status)})
        thresh = self.slow_query_threshold_ms
        slow = thresh > 0 and total * 1000.0 >= thresh
        if slow:
            # slow-query enrichment (ISSUE 5 satellite): the scheduler's
            # per-rid numbers — slot wait, resident segments, refill
            # batches — logged alongside the per-stage timings, so the
            # log line and a flight dump of the same query agree
            st = flightrec.query_stats(rid) if rid else None
            sched = ("slot_wait=%.2fms segments=%d refills=%d" % (
                st.get("slot_wait_ms", 0.0), st.get("segments", 0),
                st.get("refills", 0))) if st else "sched=-"
            if self._controller is not None:
                # ISSUE 17: which controller state served this query —
                # lines up a slow query against the actuation history
                # at /debug/controller by epoch
                sched += " cepoch=%d" % self._controller.epoch
            token = metrics.set_request_id(rid)
            try:
                log.warning(
                    "slow query rid=%s total=%.2fms queue=%.2fms "
                    "execute=%.2fms send=%.2fms %s results=%d",
                    rid or "-", total * 1000.0,
                    (t_assembled - t_enq) * 1000.0,
                    (t_executed - t_assembled) * 1000.0,
                    (now - t_send0) * 1000.0, sched,
                    sum(len(r.ids) for r in result.results))
            finally:
                metrics.reset_request_id(token)
        if self.flight_dump_dir and rec and (
                slow or result.status != wire.ResultStatus.Success):
            # auto-dump the ring for post-mortem (FlightDumpOnSlowQuery);
            # file IO runs off the event loop, the dump dir is ringed
            asyncio.get_event_loop().run_in_executor(
                None, flightrec.dump_to_file,
                "slow" if slow else "error", rid)
        # online recall estimation (ISSUE 7): AFTER the response is on
        # the wire — the shadow path never touches serve latency or
        # bytes.  Off = this one flag test; on, the deterministic rate
        # gate picks 1-in-N responses for background exact replay.
        # canary probes are EXCLUDED from the live quality windows
        # (they publish their own exact recall; double-counting the
        # probe set as "live" samples would bias the Wilson window —
        # the ISSUE 15 isolation contract)
        if qualmon.enabled() and query is not None \
                and result.status == wire.ResultStatus.Success \
                and not canary_mod.is_canary_rid(rid) \
                and qualmon.maybe_sample():
            self._queue_quality_sample(rid, query.query, result)

    def _queue_quality_sample(self, rid: str, text: str,
                              result) -> None:
        """Hand one served query to the quality monitor's shadow queue
        (bounded, drop-on-overflow — never blocks the loop).  The job
        captures only host data (query text + served ids/dists); the
        exact-scan device work is charged against QualityShadowBudget
        as 2 x num_samples x feature_dim flops a served index: the
        dots of one exact scan of one query."""
        served = [(r.index_name, [int(v) for v in r.ids],
                   [float(d) for d in r.dists]) for r in result.results]
        if not served:
            return
        est = 0.0
        for name, _ids, _d in served:
            index = self.context.indexes.get(name)
            if index is not None:
                est += 2.0 * index.num_samples * index.feature_dim
        qualmon.submit(
            functools.partial(_shadow_replay, self.context, rid, text,
                              served),
            est_flops=est)


def _shadow_replay(context: ServiceContext, rid: str, text: str,
                   served: List[tuple]) -> None:
    """Quality-monitor shadow job (runs on qualmon's worker thread,
    never the serve loop): re-parse the sampled query, replay it
    through each served index's exact FLAT/MXU scan, and fold the
    canonical recall (reference CalcRecall semantics, distance ties
    honored) into the (searchmode, shard) window.  A sample below
    QualityRecallFloor is classified — beam budget exhausted (the
    scheduler's per-rid it/t_limit), dense/sketch prefilter miss — and
    triaged onto the slow-query stats + flight dump."""
    parsed = protocol.parse_query(text)
    for name, ids, dists in served:
        index = context.indexes.get(name)
        if index is None or not ids:
            continue
        vec = parsed.extract_vector(
            parsed.data_type or index.value_type,
            context.settings.vector_separator)
        if vec is None or vec.shape[-1] != index.feature_dim:
            continue
        k = len(ids)
        try:
            ex_d, ex_ids = index.exact_search_batch(
                vec.reshape(1, -1), k)
        except (NotImplementedError, RuntimeError):
            continue                     # no oracle / emptied mid-flight
        mode = (parsed.search_mode
                or getattr(index.params, "search_mode", "flat"))
        # resolve "auto" to the engine that actually executed (beam vs
        # dense is a MaxCheck crossover) — triage must blame the real
        # engine, and the (mode, shard) window should key on it too
        resolver = getattr(index, "resolve_search_mode", None)
        if resolver is not None:
            try:
                mode = resolver(mode, parsed.max_check
                                or int(getattr(index.params,
                                               "max_check", 8192)))
            except Exception:                            # noqa: BLE001
                # unresolvable mode degrades to the wire/configured
                # label — the sample still counts, only less precisely
                log.debug("quality shadow mode resolve failed",
                          exc_info=True)
        sketch = bool(getattr(index.params, "sketch_prefilter", False))
        recall = qualmon.recall_row(ids, ex_ids[0], k, dists=dists,
                                    truth_dists=ex_d[0])
        verdict = detail = ""
        floor = qualmon.recall_floor()
        if floor > 0 and recall < floor:
            # cascade tier triage (ISSUE 14): re-run the shortlist
            # stages for this one sampled query so the verdict can name
            # the starved tier (sketch_budget / int8_budget /
            # host_fetch_drop).  Sampled + already-below-floor only —
            # never the serve path; a triage failure degrades to the
            # legacy verdicts
            tiers = None
            triage = getattr(index, "cascade_triage", None)
            if triage is not None:
                try:
                    tiers = triage(vec.reshape(-1), ex_ids[0][:k], k)
                except Exception:                        # noqa: BLE001
                    log.debug("cascade triage failed", exc_info=True)
            verdict, detail = qualmon.classify_low_recall(
                rid, mode, sketch=sketch, cascade=tiers)
        qualmon.record_sample(mode, name, recall, k, rid=rid,
                              verdict=verdict, detail=detail)


def run_interactive(context: ServiceContext) -> None:
    """Interactive stdin mode (SearchService.cpp:157-199)."""
    executor = SearchExecutor(context)
    import sys
    print("sptag_tpu search server (interactive). Empty line quits.")
    for line in sys.stdin:
        line = line.strip()
        if not line:
            break
        result = executor.execute(line)
        print(f"status={wire.ResultStatus(result.status).name}")
        for idx_res in result.results:
            print(f"[{idx_res.index_name}]")
            for rank, (vid, dist) in enumerate(
                    zip(idx_res.ids, idx_res.dists)):
                meta = ""
                if idx_res.metas is not None:
                    meta = " " + idx_res.metas[rank].decode("utf-8",
                                                            "replace")
                print(f"  {rank}: id={vid} dist={dist:.6g}{meta}")


def main(argv=None) -> int:
    """`python -m sptag_tpu.serve.server -m socket -c config.ini` — parity
    with the reference server CLI (src/Server/main.cpp)."""
    import argparse

    parser = argparse.ArgumentParser(description="sptag_tpu search server")
    parser.add_argument("-c", "--config", required=True)
    parser.add_argument("-m", "--mode", choices=("socket", "interactive"),
                        default="interactive")
    parser.add_argument("--platform", default=None,
                        help="pin the jax platform (e.g. cpu); default "
                        "honors SPTAG_TPU_PLATFORM (utils.pin_platform)")
    args = parser.parse_args(argv)
    from sptag_tpu.utils import pin_platform

    pin_platform(args.platform)
    context = ServiceContext.from_ini(args.config)
    if args.mode == "interactive":
        run_interactive(context)
        return 0

    async def serve():
        server = SearchServer(context)
        await server.start()
        await asyncio.Event().wait()

    asyncio.run(serve())
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
