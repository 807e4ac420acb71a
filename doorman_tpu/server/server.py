"""The capacity server.

Capability parity with reference go/server/doorman/server.go: the four
Capacity RPCs with mastership redirects, glob-templated hot-reloadable
config, learning mode after mastership changes, the intermediate-server role
(lease capacity from a parent and re-template it locally), and status views
for the debug pages / metrics.

TPU-native redesign: instead of running an algorithm per request
(server.go:800-817), the server can run in batch mode — requests only
record demand, and a background tick solves ALL resources at once on device
through doorman_tpu.solver.BatchSolver. The per-request scalar path remains
for brand-new clients (first response) and as `mode="immediate"`, which is
exactly the reference's request-order semantics.

Concurrency model: one asyncio loop owns all state (no locks); the batched
solve runs in an executor thread between snapshot boundaries.
"""

from __future__ import annotations

import asyncio
import contextvars
import logging
import time
from typing import Callable, Dict, List, NamedTuple, Optional

import grpc

from doorman_tpu.admission.policy import RETRY_AFTER_KEY, Shed
from doorman_tpu.algorithms import Request
from doorman_tpu.algorithms.kinds import AlgoKind
from doorman_tpu.core.resource import Resource
from doorman_tpu.obs import metrics as metrics_mod
from doorman_tpu.obs import trace as trace_mod
from doorman_tpu.proto import doorman_pb2 as pb
from doorman_tpu.proto import doorman_stream_pb2 as spb
from doorman_tpu.proto.grpc_api import CapacityServicer, add_capacity_servicer
from doorman_tpu.server import config as config_mod
from doorman_tpu.server.election import Election
from doorman_tpu.solver.engine import PipelinedTicker
from doorman_tpu.utils import dispatch as dispatch_mod
from doorman_tpu.utils.backoff import MAX_BACKOFF, MIN_BACKOFF, VERY_LONG_TIME, backoff

log = logging.getLogger(__name__)

DEFAULT_PRIORITY = 1
# Matching reference defaults (server.go:42-90).
DEFAULT_INTERVAL = 1.0
# How often (at most) GetServerCapacity scans for vanished downstream
# servers' band-composition entries.
BAND_SWEEP_INTERVAL = 10.0

# Separator for per-band sub-lease keys. Control characters are rejected
# by request validation (config.validate_*), so band keys never collide
# with real clients; \x01 (not NUL) keeps the key a valid C string for
# the native store engine's interning table.
_BAND_SEP = "\x01band\x01"


def _band_key(server_id: str, priority: int) -> str:
    """Store key for one priority band of a downstream server's aggregate.

    The reference keeps the full band list on each server record
    (simulation/server.py:300-306); this design flattens each band into its
    own sub-lease so the batched solver sees bands as ordinary rows."""
    return f"{server_id}{_BAND_SEP}{priority}"


def default_resource_template() -> pb.ResourceTemplate:
    """The "*" template an intermediate server starts from
    (server.go:53-63)."""
    return pb.ResourceTemplate(
        identifier_glob="*",
        capacity=0.0,
        safe_capacity=0.0,
        algorithm=pb.Algorithm(
            kind=pb.Algorithm.FAIR_SHARE,
            refresh_interval=int(DEFAULT_INTERVAL),
            lease_length=20,
            learning_mode_duration=20,
        ),
    )


class LanePartition(NamedTuple):
    """The Tick driver's split of the resource registry, in registry
    order: narrow lane resources (the dense resident solver), lane
    resources wider than the dense bucket cap (the chunked solver) and
    PRIORITY_BANDS resources (the BatchSolver's priority part). The
    wide and priority ids are also the rows the stream fanout checks
    every tick (`force_ids`): they solve outside the delta-tracked
    narrow path."""

    narrow: List[Resource]
    wide: List[Resource]
    prio: List[Resource]
    wide_ids: frozenset
    force_ids: frozenset

    @property
    def eligible(self) -> bool:
        """Whether the resident path runs: any lane resource at all."""
        return bool(self.narrow or self.wide)


_NO_PARTITION = LanePartition([], [], [], frozenset(), frozenset())


class CapacityServer(CapacityServicer):
    """A doorman-tpu server: root if parent_addr is empty, else
    intermediate."""

    def __init__(
        self,
        server_id: str,
        election: Election,
        *,
        parent_addr: str = "",
        parent_tls: bool = False,
        parent_tls_ca: Optional[str] = None,
        mode: str = "immediate",  # "immediate" | "batch"
        tick_interval: float = 1.0,
        minimum_refresh_interval: float = 5.0,
        clock: Callable[[], float] = time.time,
        native_store: bool = False,
        profile_dir: Optional[str] = None,
        profile_ticks: int = 8,
        solver_dtype: str = "f64",
        persist=None,  # Optional[doorman_tpu.persist.PersistManager]
        mesh=None,  # Optional[jax.sharding.Mesh] for the resident tick
        admission=None,  # Optional[doorman_tpu.admission.Admission]
        flightrec_capacity: int = 512,
        flightrec_dir: Optional[str] = None,
        fuse_admission: bool = False,
        fused_tick: bool = True,
        scoped_solve: bool = True,
        tick_pipeline_depth: int = 1,
        stream_push: bool = False,
        max_streams_per_band: int = 0,
        stream_shards: int = 1,
        shard: Optional[int] = None,
        history_dir: Optional[str] = None,
        history_capacity: int = 4096,
        audit_sample: int = 0,
        audit_inline: bool = False,
        detect: bool = False,
    ):
        if mode not in ("immediate", "batch"):
            raise ValueError(f"unknown mode {mode!r}")
        self.id = server_id
        # Federation identity: which root shard this server is (None =
        # unsharded deployment). The shard index rides status(), the
        # stream registry's status, and every flight-recorder tick
        # record, so a federated fleet's dumps and debug pages say
        # which slice of the resource space they describe. The shard's
        # election lock and persist namespace are the CALLER's job
        # (election.shard_lock_key / persist.parse_backend(namespace=));
        # this field is identity, not enforcement.
        self.shard = shard
        # Federation counters (the reconciler and the federated
        # intermediate's upstream exchange bump these): straddle-share
        # installs, the capacity those shares currently sum to, and
        # upstream RPCs issued. Plain dict so harness code can extend.
        self.fed_stats: Dict[str, float] = {
            "straddle_updates": 0,
            "straddle_capacity": 0.0,
            "upstream_rpcs": 0,
            "fleet_redirects": 0,
        }
        # resource id -> this shard's currently installed share (feeds
        # fed_stats["straddle_capacity"] as the sum over resources).
        self._straddle_shares: Dict[str, float] = {}
        # Fleet routing: resources an epoch moved AWAY from this shard,
        # mapped to the new owner's address ("" when unknown — the
        # client falls back to discovery). Replaced whole per epoch by
        # set_fleet_routing, so a resource that moves back simply
        # drops out of the table.
        self._fleet_routing: Dict[str, str] = {}
        self._fleet_epoch = 0
        self.election = election
        self.mode = mode
        self.tick_interval = tick_interval
        self.minimum_refresh_interval = minimum_refresh_interval
        self._clock = clock

        # All resources share one native engine when requested (falls back
        # to the Python store if the C++ build is unavailable).
        self._native_store = False
        self._store_factory = None
        self._store_engine = None
        if native_store:
            from doorman_tpu import native

            if native.native_available():
                self._native_store = True
                self._reset_store_engine()
            else:
                log.warning(
                    "%s: native store requested but unavailable; "
                    "using the Python store", server_id,
                )

        self.resources: Dict[str, Resource] = {}
        # Band composition of each downstream server's last request,
        # keyed (resource_id, server_id) -> set of wire priorities; used
        # to release band sub-leases the server stopped reporting (the
        # reference replaces the whole band list per request,
        # simulation/server.py:303-306).
        self._server_bands: Dict[tuple, set] = {}
        self._last_band_sweep = 0.0
        self.is_master = False
        self.became_master_at: float = 0.0
        # Counts every mastership transition (either direction); the
        # flight recorder stamps it on each tick record so a dump reader
        # can see exactly which ticks straddle a flip.
        self.mastership_epoch = 0
        # Durable lease-state snapshots + journal (doorman_tpu.persist);
        # None keeps the reference's wipe-and-relearn behavior. The
        # request path journals every decide/release, the tick pipeline
        # (or the immediate-mode timer loop) flushes and snapshots, and
        # _on_is_master(True) restores for a warm takeover.
        self._persist = persist
        # Summary dict of the last takeover restore (status pages and
        # the chaos warm-takeover invariants read it); None when this
        # server never restored or is not master.
        self.last_restore: Optional[dict] = None
        self.current_master = ""
        self.config: Optional[pb.ResourceRepository] = None
        self.is_configured = asyncio.Event()

        self.parent_addr = parent_addr
        self.parent_tls = parent_tls
        self.parent_tls_ca = parent_tls_ca
        self._parent_conn = None  # created lazily (import cycle + testing)
        self._tasks: List[asyncio.Task] = []
        self._solver = None
        # At most one tick in flight (see tick_once).
        self._tick_lock = asyncio.Lock()
        # Device-resident tick path (native batch servers without
        # priority-band resources): solver, its in-flight tick pipeline,
        # and the cached eligibility decision. The pipelines keep up to
        # `tick_pipeline_depth` ticks in flight per path, so tick N's
        # delivery download lands concurrent with the staging and solve
        # of ticks N+1..N+depth-1 (deeper host/device overlap;
        # engine.PipelinedTicker drops handles whose solver instance
        # was replaced by a flip). Depth 1 is the reference-equivalent
        # collect-before-dispatch pipeline (grants land one tick after
        # their solve); depth d defers a tick's store write-back d-1
        # further ticks — bounded by the delivery rotation's own
        # freshness argument (clients refresh far slower than ticks).
        self._tick_pipeline_depth = max(int(tick_pipeline_depth), 1)
        self._resident = None
        self._resident_pipe = PipelinedTicker(self._tick_pipeline_depth)
        # Admission-fused staging: the coalescer's windows pre-pack their
        # touched rows into the resident solver's staging cache, moving
        # the store pack off the tick's critical path (engine.FusedStaging;
        # requires admission coalescing to be the write path).
        self._fuse_admission = bool(fuse_admission)
        # Fused-tick mode for the resident solvers (the default): one
        # packed staged upload + ONE staging->solve->delta launch + one
        # download stream per tick, byte-identical to the round-trip
        # multi-dispatch path (tests/test_fused_tick.py pins it);
        # fused_tick=False keeps the round-trip path for baseline
        # measurement and triage (doc/operations.md).
        self._fused_tick = bool(fused_tick)
        # Scoped solve for the resident solvers (the default): each
        # fused tick solves only the resource-group closure of the
        # dirty set plus the not-yet-converged frontier in a compact
        # table, byte-identical to the full solve (tests/
        # test_scoped_solve.py pins it); per-tick `solve_mode`
        # escalation reasons ride the flight recorder and
        # /debug/status. scoped_solve=False pins every tick to the
        # full-table solve for triage (doc/operations.md).
        self._scoped_solve = bool(scoped_solve)
        # Optional device mesh for the resident solvers: table rows
        # shard across its devices and each tick is a shard_mapped
        # solve (store contents stay bit-identical to the single-device
        # tick; see doc/parallel.md). The BatchSolver fallback paths
        # (ResidentOverflow, priority part) stay single-device.
        self._solver_mesh = mesh
        # Wide lane resources (wider than the dense bucket cap) tick
        # through their own chunked resident solver.
        self._resident_wide = None
        self._resident_wide_pipe = PipelinedTicker(self._tick_pipeline_depth)
        # The lane partition (native stores), rebuilt only when its key
        # (config epoch, resource count) moves: the registry only grows
        # between epoch bumps. ResidentOverflow clears the key alone,
        # so the fused-staging hook keeps the last wide set until the
        # next tick re-partitions. partition_rebuilds counts rebuilds.
        self._partition = _NO_PARTITION
        self._partition_key = None
        self.partition_rebuilds = 0
        # Bumped whenever templates / learning windows / parent leases
        # change outside the stores; the resident solver caches its
        # config reads against it.
        self._config_epoch = 0
        self._grpc_server: Optional[grpc.aio.Server] = None
        self._loop: Optional[asyncio.AbstractEventLoop] = None
        self.port: Optional[int] = None

        # RPC admission control (doorman_tpu.admission): micro-batched
        # GetCapacity decisions, AIMD overload shedding by priority
        # band, deadline fast-fail. None serves every request inline —
        # the reference's only defense is client refresh cadence.
        self._admission = (
            admission.bind(self) if admission is not None else None
        )

        # Streaming lease push (doorman_tpu.server.streams): clients
        # hold one WatchCapacity stream instead of polling, and the
        # tick-edge fanout pushes only the rows whose lease moved. Off
        # by default (WatchCapacity answers UNIMPLEMENTED and clients
        # fall back to polling); see doc/streaming.md.
        self._streams = None
        # Fanouts that raised: push_streams logs and drops them so the
        # tick survives; callers that must not ride through read this.
        self.stream_fanout_errors = 0
        if stream_push:
            from doorman_tpu.server.streams import StreamRegistry

            self._streams = StreamRegistry(
                self, max_streams_per_band=max_streams_per_band,
                shards=stream_shards,
            )
        # Frontend serving pool (doorman_tpu.frontend): the multi-
        # process SO_REUSEPORT listener plane. attach_frontend wires an
        # inline or process pool; the control surface registers on the
        # backend gRPC server at start(); the establishment ramp
        # micro-batches forwarded stream establishments.
        self._frontend = None
        self._frontend_control = None
        self._frontend_ramp = None
        # Delta bookkeeping for the fanout: ticks whose changes have no
        # tracked source (python store, overflow fallback, wide/priority
        # solver parts, config epoch moves) force a full subscription
        # check instead of the engine's changed-rid filter.
        self._stream_check_all = True
        self._stream_epoch_seen = -1
        self._rid_map_key = None
        self._rid_map: Dict[int, str] = {}
        # Device-side changed-row -> subscriber matching
        # (server/match.py): the incidence structure, the slot ->
        # subscription map, and the membership key the bindings were
        # last synced against. Native-store batch servers only (the
        # python-store fanout is check_all every tick anyway).
        self._stream_matcher = None
        self._stream_slots: Dict[int, object] = {}
        self._stream_match_key = None
        # Resources currently in learning mode: their scalar decisions
        # move without store deliveries, so they ride the changed set
        # every tick. Rebuilt on membership/epoch moves, pruned as
        # learning windows lapse.
        self._stream_learning_key = None
        self._stream_learning: set = set()

        # Per-tick flight recorder (doorman_tpu.obs.flightrec): one
        # structured record per tick_once, auto-dumped on an unhandled
        # tick exception; /debug/flightrec serves the ring on demand.
        # flightrec_capacity=0 disables.
        if flightrec_capacity > 0:
            from doorman_tpu.obs.flightrec import FlightRecorder

            self.flightrec: Optional[FlightRecorder] = FlightRecorder(
                flightrec_capacity,
                component=f"server:{server_id}",
                clock=clock,
                dump_dir=flightrec_dir,
            )
        else:
            self.flightrec = None
        # Continuous telemetry (obs.history / obs.audit / obs.detect),
        # all off by default. History makes the flight-record stream
        # durable and restart-spanning; the shadow auditor replays
        # every store through the numpy host oracles every
        # `audit_sample` ticks (and on solve_mode transitions) off the
        # hot path; the detector scores each tick record's watched
        # streams with robust z / pinned floors.
        self.history = None
        if history_dir is not None:
            from doorman_tpu.obs.history import HistoryStore

            self.history = HistoryStore(
                history_dir,
                ring=history_capacity,
                component=f"server:{server_id}",
                clock=clock,
            )
        self.shadow_audit = None
        if audit_sample > 0:
            from doorman_tpu.obs.audit import ShadowAuditor

            self.shadow_audit = ShadowAuditor(
                sample=audit_sample,
                inline=audit_inline,
                on_divergence=self._on_audit_divergence,
                clock=clock,
            )
        self.detector = None
        if detect:
            from doorman_tpu.obs.detect import AnomalyDetector

            self.detector = AnomalyDetector()
        self._flight_phase_prev: Dict[str, float] = {}
        self._flight_fed_prev: Dict[str, float] = {}
        # Dispatch accounting baseline (utils.dispatch is process-
        # global and monotone; each tick record carries the delta).
        self._flight_dispatch_prev: Dict[str, int] = {}
        # Last SLO evaluation (evaluate_slos); status() and /debug/slo
        # read it. None until the first evaluation.
        self.last_slo: Optional[dict] = None

        # Metrics hooks; the metrics module replaces these when enabled.
        self.on_request: Callable[[str, float, bool], None] = lambda *a: None
        # Always-on request sampling for /debug/requests.
        from doorman_tpu.obs.requests import RequestLog

        self.request_log = RequestLog(clock=self._clock)
        # JAX profiler capture of the first batch ticks (SURVEY §5: "add
        # JAX profiler traces around the solve"); view with xprof or
        # tensorboard.
        self.profile_dir = profile_dir
        self.profile_ticks = profile_ticks
        self._profiling = False
        self._profile_done = False
        self._profile_from = 0  # applied ticks when the capture started
        if solver_dtype not in ("f32", "f64"):
            raise ValueError(f"unknown solver dtype {solver_dtype!r}")
        # f64 is the oracle-parity default; f32 trades exact parity for
        # TPU-native arithmetic (and enables the fused pallas kernels).
        self.solver_dtype = solver_dtype

    # ------------------------------------------------------------------
    # Lifecycle
    # ------------------------------------------------------------------

    async def start(
        self,
        port: int = 0,
        host: str = "[::]",
        tls_cert: Optional[str] = None,
        tls_key: Optional[str] = None,
    ) -> int:
        """Start serving gRPC; returns the bound port. Passing a cert/key
        pair serves TLS (reference doorman_server.go:171-177)."""
        self._loop = asyncio.get_running_loop()
        server = grpc.aio.server()
        add_capacity_servicer(server, self)
        if self._frontend_control is not None:
            # The frontend pool's control surface (Establish / Drop /
            # Heartbeat) rides the backend gRPC server; handlers must
            # register before the server starts.
            from doorman_tpu.frontend.control import add_frontend_control

            add_frontend_control(server, self._frontend_control)
        if tls_cert or tls_key:
            if not (tls_cert and tls_key):
                raise ValueError("tls_cert and tls_key must both be set")
            with open(tls_key, "rb") as f:
                key = f.read()
            with open(tls_cert, "rb") as f:
                cert = f.read()
            creds = grpc.ssl_server_credentials([(key, cert)])
            self.port = server.add_secure_port(f"{host}:{port}", creds)
        else:
            self.port = server.add_insecure_port(f"{host}:{port}")
        await server.start()
        self._grpc_server = server

        if self.parent_addr:
            # Intermediate servers self-configure from parent grants
            # (server.go:575-586) and keep refreshing them.
            await self.load_config(
                pb.ResourceRepository(resources=[default_resource_template()]),
                {},
            )
            self._tasks.append(asyncio.create_task(self._updater_loop()))

        if self.mode == "batch":
            self._tasks.append(asyncio.create_task(self._tick_loop()))
        elif self._persist is not None:
            # Batch servers flush/snapshot from the tick pipeline; an
            # immediate-mode server needs its own durability beat.
            self._tasks.append(asyncio.create_task(self._persist_loop()))
        if self._streams is not None and self.mode != "batch":
            # Batch servers push at tick edges (tick_once); an
            # immediate-mode server has no tick, so the fanout gets its
            # own beat at the same cadence.
            self._tasks.append(asyncio.create_task(self._stream_loop()))
        return self.port

    def attach_frontend(self, workers: int, *, ring_bytes: int = 1 << 20,
                        inline: bool = True, ramp_window: float = 0.0,
                        stall_margin: float = 3.0,
                        tls_cert: Optional[str] = None,
                        tls_key: Optional[str] = None):
        """Attach the serving-plane pool (doorman_tpu.frontend): N
        listener workers over per-worker push rings, plus the
        establishment ramp. `inline=True` builds the deterministic
        same-process pool (tests, chaos, workload harness — call
        `pump_all()` after push edges); `inline=False` builds the real
        process pool (construct BEFORE start(); its control surface
        registers on the backend gRPC server at start). Returns the
        pool."""
        if self._streams is None:
            raise ValueError(
                "attach_frontend needs stream push enabled (stream_push)"
            )
        from doorman_tpu.admission.ramp import EstablishmentRamp
        from doorman_tpu.frontend.pool import (
            FrontendPool,
            InlineFrontendPool,
        )

        if inline:
            self._frontend = InlineFrontendPool(
                self, workers, ring_bytes=ring_bytes,
                stall_margin=stall_margin,
            )
        else:
            self._frontend = FrontendPool(
                self, workers, ring_bytes=ring_bytes,
                tick_interval=self.tick_interval,
                tls_cert=tls_cert, tls_key=tls_key,
            )
        self._frontend_ramp = EstablishmentRamp(window=ramp_window)
        return self._frontend

    async def stop(self) -> None:
        self._stop_profiler()
        if self._frontend_ramp is not None:
            self._frontend_ramp.close()
            self._frontend_ramp = None
        if self._frontend is not None:
            closer = getattr(self._frontend, "stop", None)
            if closer is not None:
                await closer()
            else:
                self._frontend.close()
            self._frontend = None
        if self._streams is not None:
            self._streams.close()
        for t in self._tasks:
            t.cancel()
        for t in self._tasks:
            try:
                await t
            except (asyncio.CancelledError, Exception):
                pass
        self._tasks.clear()
        if self.shadow_audit is not None:
            self.shadow_audit.close()
        if self.history is not None:
            self.history.close()
        await self.election.stop()
        if self._parent_conn is not None:
            await self._parent_conn.close()
        if self._grpc_server is not None:
            await self._grpc_server.stop(grace=None)
            self._grpc_server = None

    async def wait_until_configured(self) -> None:
        await self.is_configured.wait()

    # ------------------------------------------------------------------
    # Config and election
    # ------------------------------------------------------------------

    async def load_config(
        self,
        repo: pb.ResourceRepository,
        expiry_times: Optional[Dict[str, float]] = None,
    ) -> None:
        """Install a new ResourceRepository (validating it); the first load
        also enters the election (server.go:187-218)."""
        config_mod.validate_repository(repo)
        if repo.groups and self.mode != "batch":
            # Shared upstream caps are enforced only by the batched
            # priority solve; a config that validates and then is not
            # enforced would silently overcommit the grouped resources
            # (an operator trap), so reject it outright. Hot-reload
            # callers catch this and keep the last good config.
            raise config_mod.ConfigError(
                f"config defines {len(repo.groups)} capacity group(s) "
                f"but server mode is {self.mode!r}: group caps are "
                "enforced only by the batch tick — run the server in "
                "batch mode or remove the groups"
            )
        first_time = self.config is None
        self.config = repo
        self._config_epoch += 1
        self._push_groups()
        if first_time:
            self.is_configured.set()
            await self.election.run(
                self.id, self._on_is_master, self._on_current_master
            )
            return
        expiry_times = expiry_times or {}
        for resource_id, res in self.resources.items():
            res.load_config(
                config_mod.find_template(repo, resource_id),
                expiry_times.get(resource_id),
            )

    def _reset_store_engine(self) -> None:
        """A fresh native engine: dropping the resources map must also drop
        the engine-held leases (the engine is get-or-create by id)."""
        if self._native_store:
            from doorman_tpu import native

            engine = native.StoreEngine(clock=self._clock)
            self._store_factory = engine.store
            # The engine itself is exposed for bulk drivers (the vector
            # population interns client handles against it).
            self._store_engine = engine

    async def _on_is_master(self, is_master: bool) -> None:
        """Mastership changes wipe all lease state; a fresh master starts in
        learning mode (server.go:438-455) — unless persistence is
        configured, in which case the wiped state is rebuilt from the
        last snapshot + journal and learning mode is skipped or
        shortened per-resource (doorman_tpu.persist.restore)."""
        was_master = self.is_master
        self.is_master = is_master
        self.mastership_epoch += 1
        # Election transitions land on the trace timeline and in the
        # default registry — a mastership flip explains every gap or
        # learning-mode plateau around it.
        trace_mod.default_tracer().instant(
            "election.transition", cat="election",
            args={"server": self.id, "is_master": is_master},
        )
        metrics_mod.default_registry().counter(
            "doorman_server_mastership_transitions",
            "Mastership transitions observed, by the state entered.",
            labels=("server", "to"),
        ).inc(self.id, "master" if is_master else "standby")
        if is_master:
            log.info("%s: this server is now the master", self.id)
            self.became_master_at = self._clock()
        else:
            log.warning("%s: this server lost mastership", self.id)
            self.became_master_at = 0.0
            if self._streams is not None:
                # Every open capacity stream ends with a terminal
                # mastership redirect — the streaming analog of the
                # unary mastership response. Clients fall back to
                # polling and re-establish against the new master
                # (resuming from their has-baseline).
                self._streams.terminate_all(self._mastership())
            if was_master and self._persist is not None:
                # Flush the terminal step-down marker BEFORE the state
                # wipe: it certifies the journal as complete, which is
                # what lets the next master skip learning outright.
                self._persist.note_step_down()
        self.resources = {}
        self._server_bands = {}
        # Straddle shares die with the lease state: a fresh master (or
        # standby) holds no share until the reconciler grants one.
        self._straddle_shares = {}
        self.fed_stats["straddle_capacity"] = 0.0
        self._reset_store_engine()
        # The engine was replaced: the resident solvers' device tables
        # and any in-flight ticks refer to the old one.
        self._config_epoch += 1
        self._resident = None
        self._resident_pipe.drop()
        self._resident_wide = None
        self._resident_wide_pipe.drop()
        # The partition's lists hold the wiped resources (and through
        # their stores the old engine): drop them with the key.
        self._partition = _NO_PARTITION
        self._partition_key = None
        self._stream_check_all = True
        self._rid_map_key = None
        # The matcher's rid bindings belong to the replaced engine; the
        # terminated subs unbind lazily (their slots die with the map).
        self._stream_matcher = None
        self._stream_slots = {}
        self._stream_match_key = None
        self._stream_learning_key = None
        self._stream_learning = set()
        self.last_restore = None
        if is_master and self._persist is not None and self.config is not None:
            # Warm takeover: rebuild the just-wiped state from the
            # backend. Synchronous on the event loop — nothing serves
            # concurrently with the rebuild, which is the atomicity the
            # restore needs; any corruption degrades to the cold path
            # inside restore().
            self.last_restore = self._persist.restore(self).as_dict()

    async def _on_current_master(self, master: str) -> None:
        if master != self.current_master:
            log.info("%s: current master is now %r", self.id, master)
            self.current_master = master

    def learning_mode_end(self, duration: float) -> float:
        """When a resource with the given learning-mode duration leaves
        learning mode (server.go:172-181)."""
        if duration <= 0:
            return 0.0
        return self.became_master_at + duration

    # ------------------------------------------------------------------
    # Resource registry
    # ------------------------------------------------------------------

    def get_or_create_resource(self, resource_id: str) -> Resource:
        res = self.resources.get(resource_id)
        if res is not None:
            return res
        template = config_mod.find_template(self.config, resource_id)
        algo = template.algorithm
        if algo.HasField("learning_mode_duration"):
            duration = float(algo.learning_mode_duration)
        else:
            duration = float(algo.lease_length)
        res = Resource(
            resource_id,
            template,
            learning_mode_end=self.learning_mode_end(duration),
            clock=self._clock,
            store_factory=self._store_factory,
        )
        self.resources[resource_id] = res
        return res

    # ------------------------------------------------------------------
    # Batch tick loop (the TPU path)
    # ------------------------------------------------------------------

    def _get_solver(self):
        if self._solver is None:
            import jax
            import numpy as np

            if self.solver_dtype == "f64" and not jax.config.jax_enable_x64:
                # The batch solver's f64 parity contract needs x64; the
                # server owns the process, so enabling it here is safe.
                log.info("%s: enabling jax_enable_x64 for the batch solver",
                         self.id)
                jax.config.update("jax_enable_x64", True)
            from doorman_tpu.solver.batch import BatchSolver

            dtype = np.float64 if self.solver_dtype == "f64" else np.float32
            self._solver = BatchSolver(clock=self._clock, dtype=dtype)
            self._push_groups()
        return self._solver

    def _push_groups(self) -> None:
        """Hand the config's capacity groups to the batch solver."""
        if self._solver is not None and self.config is not None:
            self._solver.set_groups(
                {g.name: g.capacity for g in self.config.groups}
            )

    def _resident_solver(self):
        """The device-resident tick solver (lazily created); requires
        the native engine."""
        if self._resident is None:
            import numpy as np

            from doorman_tpu.solver.resident import ResidentDenseSolver

            self._get_solver()  # settles x64 config for f64 mode
            dtype = np.float64 if self.solver_dtype == "f64" else np.float32
            engine = self._store_factory.__self__
            self._resident = ResidentDenseSolver(
                engine, dtype=dtype, clock=self._clock,
                mesh=self._solver_mesh,
                # Grant delivery rides the config's fastest refresh
                # cadence relative to this server's tick cadence.
                rotate_ticks=None, tick_interval=self.tick_interval,
                fused=self._fused_tick,
                scoped=self._scoped_solve,
            )
            if self._fuse_admission and self._admission is not None:
                # Admission-fused staging: the coalescer's windows
                # pre-pack their touched rows (engine.FusedStaging);
                # only meaningful when coalescing is the GetCapacity
                # write path — without admission every write is
                # untracked and the cache would just be invalidated.
                self._resident.attach_staging()
            if self._streams is not None:
                # Streaming lease push: the tick executable compares
                # delivered rows against a resident previous-grants
                # table so the fanout only re-decides subscribers of
                # rows that actually moved (engine delta tracking).
                self._resident.enable_delta_tracking()
            if self.flightrec is not None:
                self._resident.on_anomaly = self._solver_anomaly
        return self._resident

    def _resident_wide_solver(self):
        """The chunked resident solver for lane resources wider than the
        dense bucket cap (lazily created); requires the native engine."""
        if self._resident_wide is None:
            import numpy as np

            from doorman_tpu.solver.resident_wide import WideResidentSolver

            self._get_solver()  # settles x64 config for f64 mode
            dtype = np.float64 if self.solver_dtype == "f64" else np.float32
            engine = self._store_factory.__self__
            self._resident_wide = WideResidentSolver(
                engine, dtype=dtype, clock=self._clock,
                mesh=self._solver_mesh,
                rotate_ticks=None, tick_interval=self.tick_interval,
                fused=self._fused_tick,
                scoped=self._scoped_solve,
            )
            if self.flightrec is not None:
                self._resident_wide.on_anomaly = self._solver_anomaly
        return self._resident_wide

    def _solver_anomaly(self, kind: str, detail: dict) -> None:
        """Tick-engine anomaly hook: an engine-detected invariant at
        risk (e.g. an out-of-range dirty rid aliasing a live row) lands
        as a flight-recorder error instant BEFORE the engine raises, so
        the dump explains the tick that died."""
        fr = self.flightrec
        if fr is None:
            return
        try:
            fr.record(
                t=self._clock(),
                tick=self._ticks_done,
                is_master=self.is_master,
                epoch=self.mastership_epoch,
                error=f"solver_anomaly:{kind}",
                detail=detail,
            )
        except Exception:
            log.exception("%s: anomaly record failed", self.id)

    # -- admission-fused staging hooks ---------------------------------
    # (the tracked-writer registry FUSED_TRACKED_WRITERS lives at module
    # level below the class; doormanlint's fused-writer-discipline rule
    # reads it)

    def _fused_stage(self, resource_ids) -> None:
        """Coalescer hook, called right after a window's grouped store
        writes: pre-pack the touched NARROW lane rows into the resident
        solver's staging cache, moving the pack off the next tick's
        critical path and into the RPC window that caused it. Runs
        wherever the grouped pass runs (loop or executor) — the native
        pack call and the cache are both thread-safe. The drained dirty
        set remains authoritative for WHICH rows upload; this only
        short-circuits packing their VALUES (engine.FusedStaging)."""
        solver = self._resident
        if (
            solver is None
            or solver.staging is None
            or not self.is_master
        ):
            return
        rids = []
        wide_ids = self._partition.wide_ids
        for resource_id in resource_ids:
            res = self.resources.get(resource_id)
            if (
                res is not None
                and resource_id not in wide_ids
                and res._decide_kind != AlgoKind.PRIORITY_BANDS
            ):
                rids.append(res.store._rid)
        if rids:
            solver.stage_rids(rids)

    def _fused_invalidate(self, resource_id: Optional[str] = None) -> None:
        """Untracked-writer hook: any store write outside the
        coalescer's grouped pass (release paths, GetServerCapacity's
        band sub-leases, band sweeps) must drop the touched row's
        staged pack — a stale entry would ship a pre-write value whose
        dirty flag the next drain consumes (engine.FusedStaging's
        freshness contract). resource_id=None drops the whole cache."""
        solver = self._resident
        if solver is None or solver.staging is None:
            return
        if resource_id is None:
            solver.staging.invalidate()
            return
        res = self.resources.get(resource_id)
        if res is not None:
            solver.staging.invalidate(res.store._rid)

    def _lane_partition(self, resources: List[Resource]) -> LanePartition:
        """The resident path covers a native batch server's lane
        (non-PRIORITY_BANDS) resources; a mixed config keeps the
        resident fast path for the lane subset while the PRIORITY_BANDS
        resources (their own dense part, group caps) tick through the
        BatchSolver alongside it. Lane resources wider than the dense
        bucket cap take the chunked wide solver — there is no width
        limit on the resident path. Rebuilt only when the config epoch
        or the resource set moves (ResidentOverflow forces a
        re-partition between rebuilds)."""
        key = (self._config_epoch, len(resources))
        if key != self._partition_key:
            import numpy as np

            from doorman_tpu.solver.batch import DENSE_MAX_K

            # Every lease count in one locked engine call: a len() per
            # store is a call each.
            rids = np.fromiter(
                (r.store._rid for r in resources), np.int32,
                count=len(resources),
            )
            leases = self._store_engine.sums(rids)[:, 3].tolist()
            narrow, wide, prio = [], [], []
            for r, n in zip(resources, leases):
                # _decide_kind, set by Resource.load_config on every
                # template change: reading the template costs
                # microseconds a resource.
                if r._decide_kind == AlgoKind.PRIORITY_BANDS:
                    # A wide PRIORITY_BANDS resource (band aggregation
                    # is exactly the many-client use case) never enters
                    # a resident dense bucket; only lane resources
                    # partition by width.
                    prio.append(r)
                elif n > DENSE_MAX_K:
                    wide.append(r)
                else:
                    narrow.append(r)
            wide_ids = frozenset(r.id for r in wide)
            self._partition = LanePartition(
                narrow, wide, prio, wide_ids,
                wide_ids | frozenset(r.id for r in prio),
            )
            self._partition_key = key
            self.partition_rebuilds += 1
        return self._partition

    def _resident_step(self, solver, resources: List[Resource],
                       config_epoch: int) -> None:
        """One pipelined resident tick (runs in an executor thread; the
        native engine is mutex-guarded against concurrent RPC writes):
        collect the oldest in-flight tick once the pipeline is full,
        dispatch the next. Grants land `tick_pipeline_depth` ticks
        after their solve — bounded by the same freshness argument as
        the delivery rotation (clients refresh far slower than ticks),
        and in exchange tick N's delivery download overlaps the
        staging + solve of ticks N+1..N+depth-1.

        `solver` is resolved by the CALLER on the event loop, together
        with `resources` and `config_epoch`, so the three are mutually
        consistent even when a mastership flip swaps the store engine
        while this runs in the executor: the flip orphans the old
        engine, and a step captured before it keeps writing to that
        orphan (harmless) instead of mixing old rows into the new
        engine. The pipeline stores each handle WITH its solver, and a
        handle from any other solver instance is dropped, not
        collected — its row ids belong to a different engine."""
        self._resident_pipe.step(solver, resources, config_epoch)

    def _resident_wide_step(self, solver, resources: List[Resource],
                            config_epoch: int) -> None:
        """One pipelined wide (chunked) tick; same pipelining and
        flip-safety rules as _resident_step."""
        self._resident_wide_pipe.step(solver, resources, config_epoch)

    @property
    def _ticks_done(self) -> int:
        """Applied batch ticks (the serving condition for store-backed
        grants). max, not sum: a mixed config advances BOTH counters on
        every tick_once (resident lane subset + BatchSolver priority
        part), and summing would double-count — halving e.g. the
        profiler capture window."""
        ticks = self._solver.ticks if self._solver is not None else 0
        if self._resident is not None:
            ticks = max(ticks, self._resident.ticks)
        if self._resident_wide is not None:
            ticks = max(ticks, self._resident_wide.ticks)
        return ticks

    async def tick_once(self) -> None:
        """Run one batched solve over all resources.

        Native stores: every phase runs in an executor thread (the C++
        engine is mutex-guarded, so RPC handlers never wait on more
        than one engine call). Python stores: snapshot packing and
        write-back stay on the event loop (atomic w.r.t. handlers);
        only the device solve leaves it.

        Serialized: two ticks in flight would race the resident
        solver's donated device tables (an XLA donated buffer is
        consumed by its first use — the second tick dies with
        InvalidArgument) and interleave the snapshot/apply phases. The
        server's own loop never overlaps calls, but tick_once is also
        driven directly by tests and operational tooling, and a manual
        tick racing the loop's must queue, not corrupt."""
        async with self._tick_lock:
            tick_start = self._clock()
            tracer = trace_mod.default_tracer()
            with tracer.span(
                "server.tick", cat="tick",
                args={"server": self.id, "resources": len(self.resources)},
            ):
                try:
                    await self._tick_once_locked()
                    # The tick pipeline is the batch server's durability
                    # beat: flush this tick's journal deltas and take the
                    # cadenced snapshot inside the tick span.
                    with tracer.span("tick.persist", cat="tick"):
                        self.persist_step()
                    # Tick-edge stream fanout: push lease deltas to
                    # WatchCapacity subscribers of the rows this tick
                    # moved (the fanout's decides are journal deltas of
                    # the NEXT flush beat).
                    self.push_streams()
                except Exception as exc:
                    # The black box's trigger: an unhandled tick exception
                    # dumps the last N ticks before the error propagates
                    # (to _tick_loop's log, or the chaos runner's
                    # tick_error entry).
                    self._flight_abort(tick_start, exc)
                    raise
                # Shadow audit BEFORE the flight record so an inline
                # auditor's fresh divergence count rides this very tick's
                # record (the executor-backed live default may lag a tick
                # — the counter is cumulative either way).
                with tracer.span("tick.audit", cat="tick"):
                    self._audit_step()
                with tracer.span("tick.flightrec", cat="tick"):
                    self._flight_record_tick(tick_start)
                if self._admission is not None:
                    # Tick lag feeds the overload controller: a solve
                    # falling behind its cadence is overload even while
                    # the RPC path still looks healthy. Measured on the
                    # server clock so chaos replays stay deterministic.
                    with tracer.span("tick.admission", cat="tick"):
                        self._admission.controller.observe_tick_lag(
                            (self._clock() - tick_start)
                            / max(self.tick_interval, 1e-9)
                        )
            self._profile_between_ticks()

    async def _tick_once_locked(self) -> None:
        if not self.resources:
            return
        with trace_mod.default_tracer().span(
            "tick.partition", cat="tick"
        ) as span:
            solver = self._get_solver()
            resources = list(self.resources.values())
            rebuilds = self.partition_rebuilds
            work = self._tick_work(solver, resources)
            if span is not None:
                span.args = {"rebuilt": self.partition_rebuilds - rebuilds}
        if work is not None:
            await self._offload(work)
        else:
            self._stream_check_all = True
            snap = solver.prepare(resources)
            gets = await self._offload(solver.solve, snap)
            solver.apply(resources, snap, gets, return_grants=False)

    async def _offload(self, fn, *args):
        """fn(*args) in an executor thread: `tick.offload` times it on
        the event loop, hop included, and `tick.engine` the call itself
        in the thread."""
        tracer = trace_mod.default_tracer()

        def engine():
            with tracer.span("tick.engine", cat="tick"):
                return fn(*args)

        with tracer.span("tick.offload", cat="tick"):
            # copy_context: executor threads don't inherit contextvars,
            # and the engine's spans must nest under this one.
            ctx = contextvars.copy_context()
            return await asyncio.get_running_loop().run_in_executor(
                None, ctx.run, engine
            )

    def _tick_work(self, solver, resources: List[Resource]):
        """The tick's engine work as one callable for the executor, with
        the lane partition read here, on the event loop; None for a
        python store, whose snapshot pack and write-back stay on the
        loop."""

        def run_tick():
            snap = solver.prepare(resources)
            gets = solver.solve(snap)
            solver.apply(resources, snap, gets, return_grants=False)

        if not self._native_store:
            return None
        part = self._lane_partition(resources)
        if not part.eligible:
            self._stream_check_all = True
            return run_tick
        from doorman_tpu.solver.resident import ResidentOverflow

        narrow_res, wide_res, prio_res = part.narrow, part.wide, part.prio
        # Resolved HERE, on the event loop, so solver/resources/
        # epoch stay mutually consistent under a concurrent
        # mastership flip (see _resident_step).
        resident = self._resident_solver() if narrow_res else None
        wide = self._resident_wide_solver() if wide_res else None
        if not narrow_res:
            self._resident_pipe.drop()
        if not wide_res:
            self._resident_wide_pipe.drop()
        epoch = self._config_epoch

        def resident_or_fallback():
            try:
                if narrow_res:
                    self._resident_step(resident, narrow_res, epoch)
                if wide_res:
                    # Lane resources wider than the dense bucket cap
                    # tick through the chunked solver (their own
                    # device tables; the solves are independent).
                    self._resident_wide_step(wide, wide_res, epoch)
                if prio_res:
                    # PRIORITY_BANDS resources tick through the
                    # BatchSolver's priority part (group caps couple
                    # only these rows, so the two solves are
                    # independent); the lane subset keeps the
                    # resident fast path.
                    snap = solver.prepare(prio_res)
                    gets = solver.solve(snap)
                    solver.apply(
                        prio_res, snap, gets, return_grants=False
                    )
            except ResidentOverflow:
                # A narrow lane resource outgrew the dense bucket
                # mid-tick: force a re-partition (it lands in the
                # wide set next tick) and run this tick through the
                # BatchSolver (correct at any width). BOTH in-flight
                # handles are dropped, not just the narrow one: a
                # pre-overflow wide handle collected after this
                # fallback would overwrite the fresher batch-applied
                # grants with one-tick-stale ones (the chunk-version
                # guard only catches membership changes, not value
                # staleness). Dropping an uncollected handle is
                # documented as benign.
                log.warning(
                    "%s: resident bucket overflow; re-partitioning "
                    "wide resources", self.id,
                )
                # Executor-thread write, but serialized: the loop
                # awaits this callable under _tick_lock, and the
                # only reader (_lane_partition) runs at the next
                # tick's start, after the await completes.
                self._partition_key = None  # doorman: allow[lock-discipline]
                self._resident_pipe.drop()
                self._resident_wide_pipe.drop()
                # The fallback tick applied grants with no delta
                # tracking (and dropped handles lost theirs).
                self._stream_check_all = True  # doorman: allow[lock-discipline] same serialization as _partition_key
                run_tick()

        return resident_or_fallback

    def _profile_between_ticks(self) -> None:
        """--profile-dir's one-shot capture, started and stopped between
        ticks (tick lock held): it starts after the first applied tick,
        so compiles stay out of it, and stops after `profile_ticks`
        more. Capture trouble (another trace already active, no backend)
        is logged and never blocks solving."""
        if not self.profile_dir or self._profile_done:
            return
        done = self._ticks_done
        if not self._profiling:
            if done < 1:
                return
            import jax

            try:
                jax.profiler.start_trace(self.profile_dir)
            except Exception:
                log.exception("%s: profiler capture unavailable", self.id)
                self._profile_done = True
                return
            self._profiling = True
            self._profile_from = done
        elif done >= self._profile_from + self.profile_ticks:
            self._stop_profiler()
            log.info(
                "%s: wrote a JAX profiler trace of %d ticks to %s",
                self.id, done - self._profile_from, self.profile_dir,
            )

    def _stop_profiler(self) -> None:
        """Finish the one-shot profiler capture (also on shutdown, so a
        server stopped mid-window still flushes its trace)."""
        if not self._profiling:
            return
        import jax

        jax.profiler.stop_trace()
        self._profiling = False
        self._profile_done = True

    def set_straddle_share(
        self, resource_id: str, capacity: float, expiry: float
    ) -> None:
        """Federation hook: install this shard's reconciled share of a
        straddling resource's capacity as a parent-style lease — the
        local template's capacity becomes the share and `expiry` rides
        as the parent-lease expiry, so a shard the reconciler stops
        renewing decays to zero capacity on its own (the partition
        blast-radius story, doc/federation.md). Template-only: no store
        row moves here, so the fused-staging pack cache stays valid;
        the config epoch bump routes the new capacity through the
        resident solver's config mirror like any reload."""
        res = self.get_or_create_resource(resource_id)
        tpl = pb.ResourceTemplate()
        tpl.CopyFrom(res.template)
        tpl.capacity = float(capacity)
        res.load_config(tpl, float(expiry))
        self._config_epoch += 1
        self.fed_stats["straddle_updates"] += 1
        self._straddle_shares[resource_id] = float(capacity)
        self.fed_stats["straddle_capacity"] = float(
            sum(self._straddle_shares.values())
        )

    def set_fleet_routing(
        self, epoch: int, routed_away: Dict[str, str]
    ) -> None:
        """Fleet hook: install the epoch's redirect table — every
        resource this shard no longer owns, mapped to the new owner's
        dial address. The table REPLACES the previous epoch's (the
        fleet controller computes it from the full tracked set, so a
        resource that moved back is simply absent). A stale-epoch
        client refreshing a moved resource here gets a mastership
        redirect to the new owner instead of a silently wrong grant;
        its rows on this shard drain by plain lease expiry."""
        epoch = int(epoch)
        if epoch < self._fleet_epoch:
            # An out-of-order install from a slow controller RPC must
            # not roll the table back to an older epoch's map.
            return
        self._fleet_epoch = epoch
        self._fleet_routing = dict(routed_away)

    def persist_step(self) -> None:
        """One durability beat (journal flush + cadenced snapshot +
        compaction) when persistence is configured and this server is
        master. Driven by the batch tick, the immediate-mode timer
        loop, or the chaos runner's stepped schedule. A dead backend
        must never take down serving — failures log and the next beat
        retries."""
        if self._persist is None or not self.is_master:
            return
        try:
            self._persist.step(self)
        except Exception:
            log.exception("%s: persistence step failed", self.id)

    # ------------------------------------------------------------------
    # Streaming lease push (doorman_tpu.server.streams)
    # ------------------------------------------------------------------

    async def _stream_loop(self) -> None:
        """The immediate-mode fanout beat (batch servers push from
        tick_once instead)."""
        while True:
            await asyncio.sleep(self.tick_interval)
            if self.is_master:
                self.push_streams()

    def push_streams(self) -> None:
        """One tick-edge stream fanout: intersect the engine's changed
        rids with the device-resident subscription incidence
        (server/match.py) and hand the registry exactly the matched
        (subscription, row) work — or check_all when no tracked delta
        source covered this tick. Driven by tick_once (batch mode), the
        _stream_loop beat (immediate mode), or a stepped harness (the
        chaos runner). Runs on the event loop; must never take down the
        tick — fanout trouble logs."""
        if self._streams is None or not self.is_master:
            return
        if not len(self._streams):
            # Still drain the delta set so stale rids cannot flood the
            # first subscriber's tick.
            self._stream_changed_rids()
            return
        tracer = trace_mod.default_tracer()
        try:
            with tracer.span(
                "stream.fanout", cat="server",
                args={"server": self.id,
                      "subscribers": len(self._streams),
                      "shards": len(self._streams.shards)},
            ):
                changed_rids, check_all = self._stream_changed_rids()
                if check_all:
                    self._streams.on_tick(None, True)
                else:
                    matched = self._stream_match(changed_rids)
                    self._streams.on_tick(None, False, matched=matched)
        except Exception:
            self.stream_fanout_errors += 1
            log.exception("%s: stream fanout failed", self.id)

    def _stream_changed_rids(self):
        """(changed_rids, check_all) for this fanout: the delta-tracked
        engine's changed rids, plus the rows forced by untracked solver
        parts and the resources in learning mode (their scalar
        decisions move without store deliveries); check_all when
        anything made the filter unsound (config epoch move, fallback
        tick, python store, restore)."""
        check_all = self._stream_check_all or self.mode != "batch"
        self._stream_check_all = False
        if self._config_epoch != self._stream_epoch_seen:
            # Config moves change safe_capacity / algorithms without
            # any store delivery; recheck everything once.
            self._stream_epoch_seen = self._config_epoch
            check_all = True
        solver = self._resident
        if solver is None or not solver.delta_tracking:
            return None, True
        rids = set(solver.take_changed_rids())
        if check_all:
            return None, True
        # Wide and priority rows solve outside the delta-tracked narrow
        # path: their subscribers are checked every tick.
        for resource_id in (
            self._partition.force_ids | self._stream_learning_ids()
        ):
            res = self.resources.get(resource_id)
            if res is not None and hasattr(res.store, "_rid"):
                rids.add(res.store._rid)
        return rids, False

    def _stream_learning_ids(self) -> set:
        """Resource ids currently in learning mode. Rebuilt by one
        O(#resources) scan only when membership or config moved (the
        same cadence _rid_resource_map recomputes); otherwise only the
        current members are re-checked, so a quiet steady-state tick
        pays O(|learning|) — zero once every window lapsed."""
        key = (self._config_epoch, len(self.resources))
        if key != self._stream_learning_key:
            self._stream_learning_key = key
            self._stream_learning = {
                rid for rid, res in self.resources.items()
                if res.in_learning_mode
            }
        elif self._stream_learning:
            self._stream_learning = {
                rid for rid in self._stream_learning
                if (res := self.resources.get(rid)) is not None
                and res.in_learning_mode
            }
        return self._stream_learning

    def _stream_match(self, changed_rids) -> dict:
        """Matched fanout work for one tick edge: subscription ->
        exactly the changed resource ids it watches, via the device
        matcher. Rows per subscription come back in the subscription's
        line order, so push bytes are independent of match order."""
        matcher = self._stream_matcher_sync()
        if not changed_rids or matcher is None or not len(matcher):
            return {}
        pairs = matcher.match(sorted(changed_rids))
        if not len(pairs):
            return {}
        rid_map = self._rid_resource_map()
        hit: Dict[object, set] = {}
        for slot, rid in pairs:
            sub = self._stream_slots.get(int(slot))
            resource_id = rid_map.get(int(rid))
            if sub is not None and resource_id is not None:
                hit.setdefault(sub, set()).add(resource_id)
        return {
            sub: [r for r in sub.lines if r in rows]
            for sub, rows in hit.items()
        }

    def _stream_matcher_sync(self):
        """The subscription matcher, its bindings synced against the
        resource-membership key: a sweep removal or config reload can
        remap engine rids, so the incidence rebuilds from the live
        subscriptions whenever the key moves (steady state: never —
        subscribe/unsubscribe update it incrementally)."""
        if not self._native_store:
            return None
        key = (self._config_epoch, len(self.resources))
        if self._stream_matcher is not None and key == self._stream_match_key:
            return self._stream_matcher
        from doorman_tpu.server.match import SubscriptionMatcher

        matcher = SubscriptionMatcher()
        slots: Dict[int, object] = {}
        for sub in self._streams.iter_subs():
            if sub.terminated:
                continue
            slot = matcher.add(self._stream_sub_rids(sub))
            slots[slot] = sub
            sub.match_slot = slot
        self._stream_matcher = matcher
        self._stream_slots = slots
        self._stream_match_key = (self._config_epoch, len(self.resources))
        return matcher

    def _stream_sub_rids(self, sub) -> list:
        """Engine rids of one subscription's lines (creating any
        resource a sweep removed — its next decide would anyway)."""
        return [
            self.get_or_create_resource(resource_id).store._rid
            for resource_id in sub.lines
        ]

    def _stream_match_add(self, sub) -> None:
        """Establishment hook: bind the new subscription into the live
        incidence structure (point scatters; no rebuild)."""
        matcher = self._stream_matcher
        if matcher is None or not self._native_store:
            return
        slot = matcher.add(self._stream_sub_rids(sub))
        self._stream_slots[slot] = sub
        sub.match_slot = slot
        # Establishment may have created resources; keep the sync key
        # current so the incremental bind is not immediately rebuilt.
        self._stream_match_key = (self._config_epoch, len(self.resources))

    def _stream_match_remove(self, sub) -> None:
        """Stream-close hook: drop the subscription's incidence rows
        (idempotent; a matcher rebuilt since establishment reassigned
        slots, so only a still-current binding is removed)."""
        matcher = self._stream_matcher
        slot = sub.match_slot
        sub.match_slot = None
        if matcher is None or slot is None:
            return
        if self._stream_slots.get(slot) is sub:
            matcher.remove(slot)
            self._stream_slots.pop(slot, None)

    def _rid_resource_map(self) -> Dict[int, str]:
        """Engine rid -> resource id (native stores only), cached like
        _resident_eligible against the config epoch and resource count."""
        key = (self._config_epoch, len(self.resources))
        if key != self._rid_map_key:
            self._rid_map_key = key
            self._rid_map = {
                res.store._rid: rid
                for rid, res in self.resources.items()
                if hasattr(res.store, "_rid")
            }
        return self._rid_map

    # ------------------------------------------------------------------
    # Flight recorder + SLO evaluation
    # ------------------------------------------------------------------

    def _solve_mode(self) -> Optional[str]:
        """The last tick's solve mode across the active resident paths
        ("scoped", "full", or "full:<reason>"; None before any resident
        tick) — shared by the flight record and the audit sampler's
        transition trigger."""
        solvers = [
            s
            for s in (self._resident, self._resident_wide)
            if s is not None and s.ticks
        ]
        if not solvers:
            return None
        forced = [
            s.last_full_reason
            for s in solvers
            if s.last_solve_mode == "full" and s.last_full_reason
        ]
        if forced:
            return f"full:{forced[0]}"
        if any(s.last_solve_mode == "full" for s in solvers):
            return "full"
        return "scoped"

    def _audit_step(self) -> None:
        """Per-tick shadow-audit hook (tick lock held): a cheap
        predicate, a host-side snapshot when a sample is due, and the
        oracle replay on the audit executor (inline for chaos). Never
        raises — the auditor observes the plane, it must not fly it."""
        aud = self.shadow_audit
        if aud is None or not self.resources:
            return
        try:
            aud.maybe_sample(
                self._ticks_done, self._solve_mode(), self.resources
            )
        except Exception:
            log.exception("%s: shadow audit sampling failed", self.id)

    def _on_audit_divergence(self, detail: dict) -> None:
        """A confirmed divergence's blast pattern: the counter, the
        trace instant, a flight-recorder error record, and an
        auto-dump. Runs on the audit executor (or the tick loop when
        inline); everything touched here is thread-safe."""
        metrics_mod.default_registry().counter(
            "doorman_audit_divergence",
            "Confirmed shadow-oracle audit divergences (store of "
            "record vs numpy oracle fixpoint).",
            labels=("server", "resource"),
        ).inc(self.id, str(detail.get("rid", "?")))
        trace_mod.default_tracer().instant(
            "audit.divergence", cat="audit",
            args={
                "server": self.id,
                "resource": detail.get("rid"),
                "digest": detail.get("digest"),
            },
        )
        fr = self.flightrec
        if fr is not None:
            try:
                fr.record(
                    t=self._clock(),
                    tick=detail.get("tick"),
                    is_master=self.is_master,
                    epoch=self.mastership_epoch,
                    error=(
                        f"audit.divergence: {detail.get('rid')} store "
                        f"{detail.get('has')} vs oracle "
                        f"{detail.get('expected')}"
                    ),
                    audit=dict(detail),
                )
                fr.dump("audit_divergence")
            except Exception:
                log.exception(
                    "%s: audit divergence dump failed", self.id
                )

    def _flight_record_tick(self, tick_start: float) -> None:
        """One structured record per applied tick: wall time, per-phase
        lap deltas, admission level + per-band shed tallies, per-shard
        transfer bytes, persist journal seq, mastership epoch, and a
        store digest. O(#resources) — the stores keep running sums."""
        fr = self.flightrec
        if fr is None and self.history is None and self.detector is None:
            return
        from doorman_tpu.obs import phases as phases_mod
        from doorman_tpu.obs.flightrec import store_digest

        now = self._clock()
        totals = self._phase_totals()
        phases = {
            k: round((v - self._flight_phase_prev.get(k, 0.0)) * 1000.0, 3)
            for k, v in totals.items()
            if v - self._flight_phase_prev.get(k, 0.0) > 0
        }
        self._flight_phase_prev = totals
        rec = {
            "t": now,
            "tick": self._ticks_done,
            "wall_ms": round((now - tick_start) * 1000.0, 3),
            "is_master": self.is_master,
            "epoch": self.mastership_epoch,
            "resources": len(self.resources),
            "digest": store_digest(self.resources),
        }
        if self.shard is not None:
            # Federation beat on the black box: which shard this is,
            # how much straddle traffic moved since the last tick
            # (share installs + upstream RPCs), and the capacity the
            # installed shares currently sum to — the overlay counters
            # for "the reconciler is eating the tick" triage.
            rec["shard"] = self.shard
            for key in ("straddle_updates", "upstream_rpcs"):
                delta = self.fed_stats[key] - self._flight_fed_prev.get(
                    key, 0
                )
                if delta:
                    rec[key] = int(delta)
            self._flight_fed_prev = dict(self.fed_stats)
            if self.fed_stats["straddle_capacity"]:
                rec["straddle_capacity"] = round(
                    self.fed_stats["straddle_capacity"], 6
                )
        if phases:
            rec["phases"] = phases
        if self._resident is not None:
            # Fused-window depth of the last resident dispatch: windows
            # folded into the tick and rows served from the window-time
            # pack cache — the new staging pipeline stage is triaged
            # like the others (its lap rides `phases` as "staging").
            lf = self._resident.last_fused
            if lf.get("windows") or lf.get("rows"):
                rec["fused_windows"] = int(lf.get("windows", 0))
                rec["fused_rows"] = int(lf.get("rows", 0))
        # Scoped-solve shape of the last resident dispatch(es): which
        # solve mode ran ("scoped", "full", or "full:<reason>" when an
        # escalation forced the full table), and the scope the compact
        # solve covered. Narrow + wide paths fold: a forced-full on
        # either is the record's mode (escalations must be loud), and
        # the scope tallies sum.
        solvers = [
            s
            for s in (self._resident, self._resident_wide)
            if s is not None and s.ticks
        ]
        if solvers:
            rec["solve_mode"] = self._solve_mode()
            rec["scoped_rows"] = sum(
                int(s.last_scope.get("rows", 0)) for s in solvers
            )
            rec["scoped_resources"] = sum(
                int(s.last_scope.get("resources", 0)) for s in solvers
            )
        # Dispatch accounting: device dispatches (transfers + launches)
        # and device->host syncs this tick asked of the accelerator,
        # counted through the place()/land_parts chokepoints
        # (utils.dispatch) — the fused-tick win as a per-tick number,
        # not a claim. Process-global counters, so concurrent solver
        # paths (narrow + wide) fold into one delta per record.
        dcur = dispatch_mod.snapshot()
        if self._flight_dispatch_prev:
            rec["dispatches"] = (
                dcur["dispatches"]
                - self._flight_dispatch_prev["dispatches"]
            )
            rec["host_syncs"] = (
                dcur["host_syncs"]
                - self._flight_dispatch_prev["host_syncs"]
            )
        self._flight_dispatch_prev = dcur
        depth_used = max(
            len(self._resident_pipe), len(self._resident_wide_pipe)
        )
        if depth_used > 1:
            rec["pipeline_in_flight"] = depth_used
        if self._streams is not None:
            # Stream-push load of this tick: who is subscribed, how many
            # delta rows went out, and the bytes they cost — the triage
            # counters for "the fanout is eating the tick".
            st = self._streams.take_tick_stats()
            rec["subscribers"] = st["subscribers"]
            rec["deltas_pushed"] = st["deltas_pushed"]
            rec["push_bytes"] = st["push_bytes"]
            # Sharded-fanout shape of this tick: how many shards fanned
            # out, the (subscription, row) pairs the device matcher
            # extracted, and the bytes actually serialized (vs pushed —
            # the gap is the shared-row serialization win).
            rec["stream_shards"] = st["stream_shards"]
            rec["matched_pairs"] = st["matched_pairs"]
            rec["serialized_bytes"] = st["serialized_bytes"]
        if self._frontend is not None:
            # Serving-plane pool shape: streams held across listener
            # workers and the frames the tick published to the rings —
            # the triage counters for "a worker fell behind its ring".
            rec["frontend_held"] = self._frontend.held() if hasattr(
                self._frontend, "held"
            ) else sum(
                self._frontend.control.worker_held.values()
            )
            rec["frontend_frames"] = (
                self._frontend.publisher.published_frames
            )
        if self._admission is not None:
            admitted = 0
            shed_by_band: Dict[str, int] = {}
            for (method, band), counts in self._admission.tallies.items():
                if method != "GetCapacity":
                    continue
                admitted += counts["admitted"]
                if counts["shed"]:
                    shed_by_band[str(band)] = counts["shed"]
            rec["admission_level"] = round(
                self._admission.controller.level, 6
            )
            rec["admitted_total"] = admitted
            if shed_by_band:
                rec["shed_by_band"] = shed_by_band
        if self._persist is not None:
            rec["persist_seq"] = self._persist.journal.seq
        shards = phases_mod.last_shard_bytes()
        if shards:
            rec["shard_bytes"] = {
                f"{c}/{d}": list(v) for (c, d), v in sorted(shards.items())
            }
        if self.shadow_audit is not None:
            # Cumulative confirmed divergences: a chrome-overlay track
            # that flatlines at zero on a healthy server.
            rec["audit_divergence"] = self.shadow_audit.divergences
        if self.detector is not None:
            try:
                detections = self.detector.observe(rec)
            except Exception:
                detections = []
                log.exception("%s: anomaly detector failed", self.id)
            rec["anomalies"] = self.detector.anomalies
            if detections:
                rec["anomaly_detections"] = detections
                for det in detections:
                    trace_mod.default_tracer().instant(
                        "detect.anomaly", cat="detect",
                        args={"server": self.id, **det},
                    )
        if fr is not None:
            fr.record(**rec)
        if self.history is not None:
            # History gets its own copy: the recorder mutates its dict
            # (seq stamp) and history stamps hseq/run on this one.
            self.history.append(dict(rec))

    def _flight_abort(self, tick_start: float, exc: BaseException) -> None:
        """Record the failed tick and auto-dump the ring. Must never
        raise: the black box cannot be allowed to mask the exception it
        is documenting."""
        fr = self.flightrec
        if fr is None:
            return
        try:
            now = self._clock()
            fr.record(
                t=now,
                tick=self._ticks_done,
                wall_ms=round((now - tick_start) * 1000.0, 3),
                is_master=self.is_master,
                epoch=self.mastership_epoch,
                error=f"{type(exc).__name__}: {exc}",
            )
            fr.dump("tick_exception")
        except Exception:
            log.exception("%s: flight-recorder dump failed", self.id)

    def evaluate_slos(self, registry=None) -> List[dict]:
        """Evaluate the standing SLO set (obs.slo.server_slos) over the
        flight-recorder window, the request histograms in `registry`
        (default: the process-global registry), the admission tallies,
        and the last restore summary. Caches the result in `last_slo`
        for status() and /debug/slo."""
        from doorman_tpu.obs import slo as slo_mod

        samples: Dict[str, list] = {}
        if self.history is not None:
            # The durable history ring spans process lifetimes (the
            # previous run's records were replayed at open), so the
            # tick-budget window survives a restart.
            ticks = self.history.series("wall_ms")
        elif self.flightrec is not None:
            ticks = [
                r["wall_ms"]
                for r in self.flightrec.snapshot()
                if isinstance(r.get("wall_ms"), (int, float))
            ]
        else:
            ticks = []
        if ticks:
            samples["tick_ms"] = ticks
        scalars: Dict[str, float] = {}
        if self.last_restore is not None and self.last_restore.get(
            "mode"
        ) == "warm":
            scalars["restore_staleness_s"] = float(
                self.last_restore.get("age", 0.0)
            )
        band_tallies: Dict[int, dict] = {}
        if self._admission is not None:
            for (method, band), counts in self._admission.tallies.items():
                if method == "GetCapacity":
                    band_tallies[int(band)] = dict(counts)
        specs = slo_mod.server_slos()
        if self.shadow_audit is not None:
            # The standing audit gate: any confirmed shadow-oracle
            # divergence fails the SLO block until the process is
            # replaced — a live bit-identity violation is not a
            # transient.
            scalars["audit_divergence"] = float(
                self.shadow_audit.divergences
            )
            specs.append(slo_mod.audit_divergence_spec())
        if self.detector is not None:
            scalars["detector_anomalies"] = float(self.detector.anomalies)
            specs.append(slo_mod.detector_anomaly_spec())
        inputs = slo_mod.SloInputs(
            registry=registry or metrics_mod.default_registry(),
            samples=samples,
            scalars=scalars,
            band_tallies=band_tallies,
        )
        verdicts = slo_mod.SloEngine(specs).evaluate(inputs)
        self.last_slo = {
            "at": self._clock(),
            "ok": all(v["status"] != "fail" for v in verdicts),
            "verdicts": verdicts,
        }
        return verdicts

    async def _persist_loop(self) -> None:
        interval = self._persist.flush_interval
        while True:
            await asyncio.sleep(interval)
            self.persist_step()

    async def _tick_loop(self) -> None:
        while True:
            await asyncio.sleep(self.tick_interval)
            if not self.is_master:
                # A flip's drop can race the executor appending one
                # last (stale) entry; no tick runs on a standby, so
                # drop it here or it pins the orphaned engine and its
                # device buffer for the whole standby period.
                self._resident_pipe.drop()
                self._resident_wide_pipe.drop()
                continue
            try:
                await self.tick_once()
            except Exception:
                log.exception("%s: batched tick failed", self.id)

    # ------------------------------------------------------------------
    # RPC handlers
    # ------------------------------------------------------------------

    def _mastership(self) -> pb.Mastership:
        m = pb.Mastership()
        if self.current_master:
            m.master_address = self.current_master
        return m

    async def Discovery(self, request, context):
        out = pb.DiscoveryResponse(is_master=self.is_master)
        out.mastership.CopyFrom(self._mastership())
        return out

    def _rpc_span(self, method: str, context, caller: str):
        """A handler span, parented to the caller's span when the RPC
        carried trace metadata (the gRPC hop of the trace context)."""
        tracer = trace_mod.default_tracer()
        if not tracer.enabled:
            return trace_mod.NOOP_SPAN
        return tracer.span(
            f"server.{method}", cat="server",
            parent=trace_mod.parent_from_grpc_context(context),
            args={"server": self.id, "caller": caller,
                  "is_master": self.is_master},
        )

    async def GetCapacity(self, request, context):
        start = self._clock()
        out = pb.GetCapacityResponse()
        err = False
        adm = self._admission
        adm_observed = False
        with self._rpc_span("GetCapacity", context, request.client_id):
            try:
                if not self.is_master:
                    out.mastership.CopyFrom(self._mastership())
                    return out
                if self._fleet_routing:
                    # Epoch-aware redirect: a reshard moved one of the
                    # requested resources off this shard. Answer with a
                    # mastership redirect to the new owner (clients
                    # batch per shard, so a mixed batch is a stale
                    # router — chasing re-sorts it).
                    moved = next(
                        (req.resource_id for req in request.resource
                         if req.resource_id in self._fleet_routing),
                        None,
                    )
                    if moved is not None:
                        self.fed_stats["fleet_redirects"] += 1
                        addr = self._fleet_routing[moved]
                        if addr:
                            out.mastership.master_address = addr
                        return out
                msg = config_mod.validate_get_capacity_request(request)
                if msg is not None:
                    err = True
                    if adm is not None:
                        adm.observe_rpc(self._clock() - start)
                        adm_observed = True
                    await context.abort(grpc.StatusCode.INVALID_ARGUMENT, msg)
                if adm is not None:
                    shed = adm.check_get_capacity(request, context)
                    if shed is not None:
                        err = True
                        # Latency is observed BEFORE the abort: the
                        # abort's unwind races the client's resumption
                        # on the loop, so a finally-side measurement
                        # can land after the chaos clock's next tick
                        # advance and feed the controller a bogus
                        # tick-length latency sample.
                        adm.observe_rpc(self._clock() - start)
                        adm_observed = True
                        # The retry-after hint rides trailing metadata
                        # (a non-OK status cannot carry a response
                        # message); semantically it is the admission
                        # path's refresh_interval — "come back in N
                        # seconds" (doc/admission.md).
                        context.set_trailing_metadata((
                            (RETRY_AFTER_KEY, f"{shed.retry_after:.3f}"),
                        ))
                        await context.abort(
                            grpc.StatusCode.RESOURCE_EXHAUSTED, shed.reason
                        )
                    return await adm.serve_get_capacity(request)
                for req in request.resource:
                    has = req.has.capacity if req.HasField("has") else 0.0
                    lease, res = self._decide(
                        req.resource_id,
                        Request(request.client_id, has, req.wants, 1,
                                priority=req.priority),
                    )
                    resp = out.response.add()
                    resp.resource_id = req.resource_id
                    resp.gets.expiry_time = int(lease.expiry)
                    resp.gets.refresh_interval = int(lease.refresh_interval)
                    resp.gets.capacity = lease.has
                    resp.safe_capacity = res.safe_capacity()
                return out
            finally:
                dur = self._clock() - start
                if adm is not None and not adm_observed:
                    # Latency feed for the overload controller (shed
                    # requests observed at the abort above).
                    adm.observe_rpc(dur)
                self.on_request("GetCapacity", dur, err)
                self.request_log.record(
                    "GetCapacity", request.client_id,
                    [r.resource_id for r in request.resource],
                    sum(r.wants for r in request.resource),
                    dur, err,
                )

    async def WatchCapacity(self, request, context):
        """Streaming lease push: one subscription request, a stream of
        tick-edge deltas (doc/streaming.md). Establishment walks the
        same gate as a poll — mastership, validation, admission (AIMD
        band shed + per-band stream cap) — then the registry owns the
        stream until it ends with a terminal mastership redirect."""
        start = self._clock()
        err = True
        try:
            with self._rpc_span("WatchCapacity", context,
                                request.client_id):
                if self._streams is None:
                    await context.abort(
                        grpc.StatusCode.UNIMPLEMENTED,
                        "stream push is disabled on this server "
                        "(--stream-push)",
                    )
                if not self.is_master:
                    out = spb.WatchCapacityResponse()
                    out.mastership.CopyFrom(self._mastership())
                    err = False
                    yield out
                    return
                msg = config_mod.validate_get_capacity_request(request)
                if msg is not None:
                    await context.abort(
                        grpc.StatusCode.INVALID_ARGUMENT, msg
                    )
                band = max(
                    (rr.priority for rr in request.resource), default=0
                )

                def _establish():
                    """Gate + subscribe, in arrival order — ridden
                    directly or through the establishment ramp's
                    grid-aligned window (admission/ramp.py)."""
                    shed = None
                    if self._admission is not None:
                        shed = self._admission.check_watch(request)
                    if shed is None:
                        shed = self._streams.check_cap(band)
                    if shed is not None:
                        return shed
                    sub = self._streams.subscribe(request)
                    # Bind the new stream into the device matcher's
                    # incidence structure (a point scatter, not a
                    # rebuild).
                    self._stream_match_add(sub)
                    return sub

                if self._frontend_ramp is not None:
                    result = await self._frontend_ramp.submit(_establish)
                else:
                    result = _establish()
                if isinstance(result, Shed):
                    # Same wire contract as a shed poll: the pacing
                    # hint rides trailing metadata (doc/admission.md).
                    context.set_trailing_metadata((
                        (RETRY_AFTER_KEY, f"{result.retry_after:.3f}"),
                    ))
                    await context.abort(
                        grpc.StatusCode.RESOURCE_EXHAUSTED, result.reason
                    )
                sub = result
                err = False
        finally:
            dur = self._clock() - start
            self.on_request("WatchCapacity", dur, err)
            self.request_log.record(
                "WatchCapacity", request.client_id,
                [r.resource_id for r in request.resource],
                sum(r.wants for r in request.resource),
                dur, err,
            )
        try:
            while True:
                out = await sub.queue.get()
                yield out
                # Data pushes are pre-serialized bytes (the stream
                # serializer passes them through); a message OBJECT is
                # the terminal mastership redirect.
                if not isinstance(out, (bytes, bytearray)):
                    return
        finally:
            self._streams.unsubscribe(sub)
            self._stream_match_remove(sub)

    async def GetServerCapacity(self, request, context):
        start = self._clock()
        out = pb.GetServerCapacityResponse()
        err = False
        with self._rpc_span("GetServerCapacity", context, request.server_id):
            return await self._get_server_capacity(
                request, context, out, start, err
            )

    async def _get_server_capacity(self, request, context, out, start, err):
        try:
            if not self.is_master:
                out.mastership.CopyFrom(self._mastership())
                return out
            msg = config_mod.validate_get_server_capacity_request(request)
            if msg is not None:
                err = True
                await context.abort(grpc.StatusCode.INVALID_ARGUMENT, msg)
            if self._admission is not None:
                # Never shed (one RPC carries a whole downstream
                # subtree's demand — the shed matrix's 'never' row);
                # tallied so the load is visible in the counters.
                self._admission.note_pass_through(
                    "GetServerCapacity",
                    max(
                        (band.priority for r in request.resource
                         for band in r.wants),
                        default=0,
                    ),
                )
            self._sweep_server_bands()
            for req in request.resource:
                # One sub-lease per priority band: the store keeps the
                # downstream server's band composition (reference
                # carries sr.wants as the full band list,
                # simulation/server.py:300-306), so a PRIORITY_BANDS
                # resource discriminates bands across the tree and this
                # server's own upstream aggregation re-emits them.
                bands = list(req.wants) or [
                    pb.PriorityBandAggregate(
                        priority=DEFAULT_PRIORITY, num_clients=1, wants=0.0
                    )
                ]
                wants_total = sum(band.wants for band in bands)
                has_total = req.has.capacity if req.HasField("has") else 0.0
                res = self.get_or_create_resource(req.resource_id)
                key = (req.resource_id, request.server_id)
                prios = {band.priority for band in bands}
                for stale in self._server_bands.get(key, set()) - prios:
                    bkey = _band_key(request.server_id, stale)
                    res.release(bkey)
                    if self._persist is not None:
                        self._persist.record_release(req.resource_id, bkey)
                self._server_bands[key] = prios
                granted, lease = 0.0, None
                for band in bands:
                    # Per-band has: this server granted the band's previous
                    # lease itself, so the stored value is exact; the
                    # wants-proportional split of the aggregate wire `has`
                    # only seeds bands we have no record of (the wire
                    # carries one aggregate has per resource).
                    bkey = _band_key(request.server_id, band.priority)
                    prev = res.store.get(bkey)
                    # Missing bands return ZERO_LEASE (expiry 0), so one
                    # expiry check covers both absent and lapsed.
                    if prev.expiry >= self._clock():
                        has_band = prev.has
                    elif wants_total > 0:
                        has_band = has_total * (band.wants / wants_total)
                    else:
                        has_band = has_total / len(bands)
                    lease, res = self._decide(
                        req.resource_id,
                        Request(
                            bkey, has_band, band.wants,
                            max(band.num_clients, 1),
                            priority=band.priority,
                        ),
                    )
                    granted += lease.has
                # Untracked writes: the band sub-lease decides above
                # bypass the coalescer's stage (see _fused_invalidate).
                self._fused_invalidate(req.resource_id)
                resp = out.response.add()
                resp.resource_id = req.resource_id
                resp.gets.expiry_time = int(lease.expiry)
                resp.gets.refresh_interval = int(lease.refresh_interval)
                resp.gets.capacity = granted
                resp.algorithm.CopyFrom(res.template.algorithm)
                resp.safe_capacity = (
                    res.template.safe_capacity
                    if res.template.HasField("safe_capacity")
                    else 0.0
                )
            return out
        finally:
            self.on_request("GetServerCapacity", self._clock() - start, err)
            self.request_log.record(
                "GetServerCapacity", request.server_id,
                [r.resource_id for r in request.resource],
                sum(
                    band.wants for r in request.resource
                    for band in r.wants
                ),
                self._clock() - start, err,
            )

    async def ReleaseCapacity(self, request, context):
        start = self._clock()
        out = pb.ReleaseCapacityResponse()
        err = False
        with self._rpc_span("ReleaseCapacity", context, request.client_id):
            return await self._release_capacity(
                request, context, out, start, err
            )

    async def _release_capacity(self, request, context, out, start, err):
        try:
            if not self.is_master:
                out.mastership.CopyFrom(self._mastership())
                return out
            msg = config_mod.validate_release_capacity_request(request)
            if msg is not None:
                err = True
                await context.abort(grpc.StatusCode.INVALID_ARGUMENT, msg)
            if self._admission is not None:
                # Never shed: releases shrink load; refusing one pins
                # a dead client's capacity and worsens the overload.
                self._admission.note_pass_through("ReleaseCapacity")
            for resource_id in request.resource_id:
                res = self.resources.get(resource_id)
                if res is None:
                    continue
                res.release(request.client_id)
                if self._persist is not None:
                    self._persist.record_release(
                        resource_id, request.client_id
                    )
                # A downstream *server* holds per-band sub-leases; release
                # them too and forget its band composition.
                key = (resource_id, request.client_id)
                for prio in self._server_bands.pop(key, set()):
                    bkey = _band_key(request.client_id, prio)
                    res.release(bkey)
                    if self._persist is not None:
                        self._persist.record_release(resource_id, bkey)
                # Untracked write: a staged pack of this row predates
                # the release (see _fused_invalidate).
                self._fused_invalidate(resource_id)
            return out
        finally:
            self.on_request("ReleaseCapacity", self._clock() - start, err)
            self.request_log.record(
                "ReleaseCapacity", request.client_id,
                list(request.resource_id), 0.0,
                self._clock() - start, err,
            )

    def _sweep_server_bands(self) -> None:
        """Drop (resource, server) band-composition entries whose sub-leases
        have all expired out of the store — downstream servers that vanished
        without a ReleaseCapacity would otherwise leak an entry forever.
        Time-gated: the underlying expiry state only changes as leases
        lapse, so scanning more than once per interval buys nothing."""
        now = self._clock()
        if now - self._last_band_sweep < BAND_SWEEP_INTERVAL:
            return
        self._last_band_sweep = now
        stale = []
        swept = set()
        for (resource_id, server_id), prios in self._server_bands.items():
            res = self.resources.get(resource_id)
            if res is not None and resource_id not in swept:
                # has_client counts expired leases as live; sweep them out
                # first so vanished servers actually disappear even in
                # immediate mode (where no batch tick cleans stores).
                res.store.clean()
                # Untracked removal (see _fused_invalidate).
                self._fused_invalidate(resource_id)
                swept.add(resource_id)
            if res is None or not any(
                res.store.has_client(_band_key(server_id, p)) for p in prios
            ):
                stale.append((resource_id, server_id))
        for key in stale:
            del self._server_bands[key]

    def _decide(self, resource_id: str, request: Request):
        """Produce a lease for one resource request. Immediate mode (and
        unknown clients, and learning mode) run the scalar per-request
        algorithm; batch mode serves the last tick's solved grant and only
        records the new demand."""
        res = self.get_or_create_resource(resource_id)
        lease = None
        if (
            self.mode == "batch"
            and not res.in_learning_mode
            and self._ticks_done > 0
        ):
            rg = res._refresh_grant
            if rg is not None:
                # Native store: one locked C call records the demand
                # and serves the last solved grant (dm_refresh_grant);
                # None means the client is new — fall to decide below.
                lease = rg(
                    request.client, res._lease_length,
                    res._refresh_interval, request.wants,
                    request.subclients, request.priority,
                )
            elif res.store.has_client(request.client):
                lease = res.store.assign(
                    request.client,
                    res._lease_length,
                    res._refresh_interval,
                    res.store.get(request.client).has,
                    request.wants,
                    request.subclients,
                    priority=request.priority,
                )
        if lease is None:
            lease = res.decide(request)
        if self._persist is not None:
            # Every served lease is a journal delta; replay over the
            # last snapshot reconstructs this exact store row.
            self._persist.record_assign(resource_id, request.client, lease)
        return lease, res

    def decide_bulk(
        self,
        resource_id: str,
        cids,
        has,
        wants,
        priorities,
        *,
        old_has,
        old_wants,
        new_mask,
        cid_handles=None,
        expected_count=None,
    ):
        """Bulk refresh entry for array-backed drivers (the vector
        workload population): decide one resource's batch of
        single-resource requests in arrival order and return
        ``(grants, expiry, refresh_interval, safe, fast_rows)`` arrays.
        Tries the vectorized grouped pass first
        (`coalesce.decide_grouped_arrays`, grant-exact with the
        per-request path) and falls back to the sequential
        `decide_grouped` — identical responses either way, only
        ``fast_rows`` says how much of the batch went through arrays.
        Caller preconditions: this server is master, no stored lease is
        expired, and the mirror arrays describe the store's rows
        exactly. Must run on the event loop (same discipline as the
        coalescer's inline window)."""
        from doorman_tpu.admission import coalesce as coalesce_mod
        import numpy as np

        try:
            out = coalesce_mod.decide_grouped_arrays(
                self, resource_id, cids, has, wants, priorities,
                old_has=old_has, old_wants=old_wants, new_mask=new_mask,
                cid_handles=cid_handles, expected_count=expected_count,
            )
            if out is None:
                name_of = (
                    self._store_engine.client_name
                    if cids is None else None
                )
                work = []
                for i in range(len(wants)):
                    name = (
                        cids[i] if cids is not None
                        else name_of(int(cid_handles[i]))
                    )
                    work.append((resource_id, Request(
                        name, float(has[i]), float(wants[i]), 1,
                        priority=int(priorities[i]),
                    )))
                decided = coalesce_mod.decide_grouped(self, work)
                n = len(work)
                grants = np.empty(n, np.float64)
                expiry = np.empty(n, np.float64)
                refresh = np.empty(n, np.float64)
                safe = np.empty(n, np.float64)
                for i, (lease, _res, s) in enumerate(decided):
                    grants[i] = lease.has
                    expiry[i] = lease.expiry
                    refresh[i] = lease.refresh_interval
                    safe[i] = s
                out = (grants, expiry, refresh, safe, 0)
        except BaseException:
            # Same contract as the coalescer's window: a partially
            # applied batch can't prove staging freshness — drop the
            # fused cache and re-raise.
            self._fused_invalidate()
            raise
        self._fused_stage({resource_id})
        return out

    # ------------------------------------------------------------------
    # Intermediate-server updater (refresh capacity from parent)
    # ------------------------------------------------------------------

    def _build_server_capacity_request(self) -> pb.GetServerCapacityRequest:
        """Aggregate every local resource into per-band aggregates.

        Clients and downstream servers' bands group by wire priority
        (simulation/server_state_wrapper.py:305-334 — the Go server's
        single-band pack at server.go:227-261 is its own documented TODO),
        so band structure survives every upstream hop. The current parent
        lease rides along as `has` so the parent's algorithms see this
        server as a returning client."""
        out = pb.GetServerCapacityRequest(server_id=self.id)
        for resource_id, res in self.resources.items():
            if res.store.sum_wants > 0:
                rr = out.resource.add()
                rr.resource_id = resource_id
                if res.parent_expiry is not None and res.capacity > 0:
                    rr.has.capacity = res.capacity
                    rr.has.expiry_time = int(res.parent_expiry)
                # One aggregation call per resource (the native store
                # does this in C — a 1M-lease store must not be walked
                # per-lease on the event loop).
                for priority, wants, num_clients in (
                    res.store.band_aggregates()
                ):
                    if wants <= 0:
                        continue
                    band = rr.wants.add()
                    band.priority = priority
                    band.num_clients = max(int(num_clients), 1)
                    band.wants = wants
        if not out.resource:
            # Probe request so the parent link stays warm (server.go:66-79).
            rr = out.resource.add()
            rr.resource_id = "*"
            band = rr.wants.add()
            band.priority = DEFAULT_PRIORITY
            band.num_clients = 1
            band.wants = 0.0
        return out

    async def _perform_parent_requests(self, retry_number: int):
        """One GetServerCapacity exchange with the parent: send aggregated
        demand, re-template local resources from the grants
        (server.go:227-323). Returns (next_interval, next_retry_number)."""
        if self._parent_conn is None:
            from doorman_tpu.client.connection import Connection

            self._parent_conn = Connection(
                self.parent_addr,
                minimum_refresh_interval=self.minimum_refresh_interval,
                tls=self.parent_tls,
                tls_ca=self.parent_tls_ca,
            )
        request = self._build_server_capacity_request()
        try:
            # The metadata is computed inside the lambda, at call time,
            # so each attempt carries the parent_refresh span context
            # over the GetServerCapacity hop.
            with trace_mod.default_tracer().span(
                "server.parent_refresh", cat="server",
                args={"server": self.id, "parent": self.parent_addr},
            ):
                out = await self._parent_conn.execute(
                    lambda stub: stub.GetServerCapacity(
                        request, metadata=trace_mod.grpc_metadata()
                    )
                )
        except Exception:
            log.exception("%s: GetServerCapacity to parent failed", self.id)
            return (
                backoff(MIN_BACKOFF, MAX_BACKOFF, retry_number),
                retry_number + 1,
            )

        interval = VERY_LONG_TIME
        templates: List[pb.ResourceTemplate] = []
        expiry_times: Dict[str, float] = {}
        for pr in out.response:
            if pr.resource_id not in self.resources:
                if pr.resource_id != "*":
                    log.error(
                        "%s: response for unknown resource %r",
                        self.id, pr.resource_id,
                    )
                continue
            expiry_times[pr.resource_id] = float(pr.gets.expiry_time)
            tpl = pb.ResourceTemplate(
                identifier_glob=pr.resource_id,
                capacity=pr.gets.capacity,
                safe_capacity=pr.safe_capacity,
            )
            tpl.algorithm.CopyFrom(pr.algorithm)
            templates.append(tpl)
            interval = min(interval, float(pr.gets.refresh_interval))
        templates.append(default_resource_template())
        try:
            await self.load_config(
                pb.ResourceRepository(resources=templates), expiry_times
            )
        except config_mod.ConfigError:
            log.exception("%s: loading parent-derived config failed", self.id)
            return (
                backoff(MIN_BACKOFF, MAX_BACKOFF, retry_number),
                retry_number + 1,
            )
        if interval < self.minimum_refresh_interval or interval == VERY_LONG_TIME:
            interval = self.minimum_refresh_interval
        return interval, 0

    async def _updater_loop(self) -> None:
        interval, retry = DEFAULT_INTERVAL, 0
        while True:
            await asyncio.sleep(interval)
            interval, retry = await self._perform_parent_requests(retry)

    # ------------------------------------------------------------------
    # Status views
    # ------------------------------------------------------------------

    def status(self) -> dict:
        return {
            "id": self.id,
            "is_master": self.is_master,
            "election": str(self.election),
            "current_master": self.current_master,
            "mode": self.mode,
            # The platform actually solving (only read once a tick has
            # completed: jax.default_backend() would otherwise TRIGGER
            # backend init from the status page's thread).
            "backend": self._backend_platform(),
            # Axis sizes of the resident solvers' device mesh (None:
            # single-device resident ticks).
            "mesh": (
                {str(k): int(v) for k, v in self._solver_mesh.shape.items()}
                if self._solver_mesh is not None
                else None
            ),
            "ticks": self._ticks_done,
            # Tick-pipeline shape: configured depth and what is in
            # flight right now (resident + wide pipelines).
            "tick_pipeline": {
                "depth": self._tick_pipeline_depth,
                "in_flight": (
                    len(self._resident_pipe)
                    + len(self._resident_wide_pipe)
                ),
            },
            # Fused-tick mode and the process-cumulative dispatch
            # accounting (device dispatches / host syncs through the
            # counted chokepoints; per-tick deltas ride the flight
            # recorder as `dispatches`/`host_syncs`).
            "fused_tick": self._fused_tick,
            # Scoped-solve state per resident path (None: path not
            # active yet): last solve mode + forced-full reason, the
            # last compact scope, the host frontier size, and the
            # cumulative scoped/full tick split — the "solve_mode
            # stuck at full" triage block (doc/operations.md).
            "scoped_solve": self._scoped_solve,
            "solve_scope": {
                "narrow": (
                    self._resident.scope_status()
                    if self._resident is not None
                    else None
                ),
                "wide": (
                    self._resident_wide.scope_status()
                    if self._resident_wide is not None
                    else None
                ),
            },
            "dispatch": dispatch_mod.snapshot(),
            # Admission-fused staging counters (None: fusion off or the
            # resident path not active yet); see doc/bench.md.
            "fused_staging": (
                self._resident.staging.status()
                if self._resident is not None
                and self._resident.staging is not None
                else None
            ),
            # Ticks the resident solver served without device work (the
            # idle fast path); a busy server shows 0 here.
            "idle_ticks": (
                self._resident.idle_ticks
                if self._resident is not None
                else 0
            ),
            "tick_phase_total_ms": {  # cumulative since start
                k: round(v * 1000.0, 3)
                for k, v in self._phase_totals().items()
            },
            "last_tick_ms": round(self._last_tick_seconds() * 1000.0, 3),
            "persist": (
                self._persist.status()
                if self._persist is not None
                else None
            ),
            "admission": (
                self._admission.status()
                if self._admission is not None
                else None
            ),
            # Streaming lease push (None: --stream-push off).
            "streams": (
                self._streams.status()
                if self._streams is not None
                else None
            ),
            # Serving-plane pool (None: single-process front-end).
            "frontend": (
                {
                    **self._frontend.status(),
                    "ramp": (
                        self._frontend_ramp.status()
                        if self._frontend_ramp is not None
                        else None
                    ),
                    "control": (
                        self._frontend_control.status()
                        if self._frontend_control is not None
                        else None
                    ),
                }
                if self._frontend is not None
                else None
            ),
            # Federation identity + traffic (None: unsharded server
            # with no federated activity).
            "federation": (
                {
                    "shard": self.shard,
                    "straddle_shares": dict(self._straddle_shares),
                    **{k: v for k, v in self.fed_stats.items()},
                }
                if self.shard is not None
                or any(self.fed_stats.values())
                else None
            ),
            "last_restore": self.last_restore,
            "flightrec": (
                self.flightrec.status()
                if self.flightrec is not None
                else None
            ),
            # Continuous telemetry (None: feature off): durable
            # history, shadow-oracle audit, anomaly detector.
            "history": (
                self.history.status()
                if self.history is not None
                else None
            ),
            "shadow_audit": (
                self.shadow_audit.status()
                if self.shadow_audit is not None
                else None
            ),
            "detector": (
                self.detector.status()
                if self.detector is not None
                else None
            ),
            "slo": self.last_slo,
            "resources": {
                rid: res.status() for rid, res in self.resources.items()
            },
            "config": (
                config_mod.repository_to_yaml(self.config)
                if self.config is not None
                else ""
            ),
        }

    def _phase_totals(self) -> Dict[str, float]:
        """Cumulative per-phase seconds across every active solver path;
        wide/batch keys are prefixed so a mixed config reads unambiguously
        (the same breakdown /metrics carries as per-phase histograms)."""
        out: Dict[str, float] = {}
        if self._resident is not None:
            out.update(self._resident.phase_s)
        if self._resident_wide is not None:
            for k, v in self._resident_wide.phase_s.items():
                out[f"wide.{k}"] = v
        if self._solver is not None:
            for k, v in self._solver.phase_s.items():
                out[f"batch.{k}"] = v
        if self._stream_matcher is not None:
            # The stream fanout's match/staging laps (server/match.py).
            for k, v in self._stream_matcher.phase_s.items():
                if v:
                    out[f"stream.{k}"] = v
        return out

    def _last_tick_seconds(self) -> float:
        return max(
            (
                s.last_tick_seconds
                for s in (self._solver, self._resident, self._resident_wide)
                if s is not None
            ),
            default=0.0,
        )

    def _backend_platform(self) -> str:
        if self._ticks_done <= 0:
            return ""
        try:
            import jax

            return jax.default_backend()
        except Exception:
            # Distinct from the pre-first-tick "" sentinel: ticks ran,
            # so "(no tick yet)" would hide a real backend error.
            return "(error)"

    def resource_lease_status(self, resource_id: str):
        res = self.resources.get(resource_id)
        if res is None:
            return None
        return res.store.lease_status()


# ----------------------------------------------------------------------
# Fused-staging tracked-writer registry (machine-checked)
# ----------------------------------------------------------------------
# The FusedStaging freshness contract (solver/engine.py): a window-time
# pack cache entry is valid only while no store write touched its row
# after staging. doormanlint's fused-writer-discipline rule requires
# every store-row writer in this file and admission/coalesce.py to
# either call _fused_invalidate (release paths, band sub-leases, band
# sweeps do) or appear here with the audit note saying who owns its
# staging obligation. Adding a writer to this list is a CONTRACT CLAIM
# — include the argument, like the entries below.
FUSED_TRACKED_WRITERS = frozenset({
    # The coalescer's grouped pass is THE tracked writer: it re-stages
    # everything it wrote via _fused_stage at window close and drops the
    # whole cache on a partially-applied window. (It calls both hooks
    # inline, so it self-certifies; listed for documentation.)
    "Coalescer._decide_batch",
    # The shared grouped-decide core (admission/coalesce.decide_grouped)
    # only dispatches to _decide; its CALLERS own the contract exactly
    # like _decide's own call sites below: Coalescer._decide_batch
    # re-stages via _fused_stage after the window's writes, and the
    # stream fanout's per-shard pass (StreamShard.fanout_build) runs
    # only steady-state refresh decides — identical wants rewritten,
    # packed bytes unchanged, the StreamRegistry._decide argument.
    "decide_grouped",
    # _decide writes one row per call; its four call sites own the
    # contract: Coalescer._decide_batch re-stages after the window's
    # writes, _get_server_capacity invalidates after the band loop,
    # GetCapacity's direct loop only runs with admission off (below),
    # and the stream registry (server/streams.py) invalidates on its
    # establishment decide — the only one of its decides that changes
    # packed bytes (steady refreshes rewrite identical wants; see
    # StreamRegistry._decide).
    "CapacityServer._decide",
    # The direct per-request loop runs only when admission is None
    # (coalescing otherwise owns every GetCapacity decide), and fused
    # staging is attached iff fuse_admission AND admission coalescing
    # are active (_resident_solver) — so on this path the staging cache
    # provably does not exist.
    "CapacityServer.GetCapacity",
    # Mastership transitions swap the store engine and null the
    # resident solvers before persist.restore writes the fresh engine:
    # the staged cache dies with the old solver (engine handles are
    # meaningless across the swap), and a new cache cannot exist until
    # a new solver is built after this method returns.
    "CapacityServer._on_is_master",
    # The array decide pass (admission/coalesce.decide_grouped_arrays)
    # commits its grants in one bulk_assign; its sole caller,
    # CapacityServer.decide_bulk, owns the contract exactly like
    # Coalescer._decide_batch does for decide_grouped — it calls
    # _fused_stage after the batch's writes and _fused_invalidate on a
    # partially-applied batch (the exception path).
    "decide_grouped_arrays",
})
