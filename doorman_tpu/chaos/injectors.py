"""Fault injectors: shims at the seams the stack already crosses.

Four seams, matching the production failure surface:

  * the election's lease-KV (ChaosLeaseKV wrapping any LeaseKV) — the
    in-process equivalent of etcd round-trips failing;
  * the etcd v3 gateway client itself (ChaosEtcdGateway, a drop-in
    EtcdGateway speaking the REAL HTTP dialect against tests/fake_etcd
    or a live cluster): delayed/dropped round-trips and watch stalls;
  * gRPC hops between client<->server and intermediate<->root
    (ChaosGrpcProxy): latency, dropped RPCs, spurious NOT_MASTER;
  * the solver/backend boundary (SolverInjector): ResidentOverflow,
    slow device steps, a dead backend raising mid-tick.

All injectors consult one FaultState switchboard the runner drives from
the plan's event schedule; none of them mutates doorman code — they
wrap instances, so production paths run unmodified when no fault is
active.
"""

from __future__ import annotations

import asyncio
import random
import socket
import time
from typing import Dict, List, Optional, Tuple

from doorman_tpu.proto import doorman_pb2 as pb
from doorman_tpu.proto.grpc_api import CapacityServicer, add_capacity_servicer
from doorman_tpu.server.election import LeaseKV
from doorman_tpu.server.etcd import EtcdGateway
from doorman_tpu.chaos.plan import FaultEvent


class FaultInjected(ConnectionError):
    """An injected transport-style failure (distinguishable from a
    definite protocol outcome like 'lease gone')."""


class FaultState:
    """The live fault switchboard.

    The runner starts plan events here and advances ticks; injectors
    query with take(). A fault stays active until its duration expires;
    params["calls"] makes it count-limited instead ("drop the next N
    calls"), consumed by take(). The seeded RNG is the ONLY randomness
    a chaos run may use — injectors that jitter must draw from it, so a
    plan's seed fully determines the run."""

    def __init__(self, seed: int = 0):
        self.rng = random.Random(seed)
        self.tick = 0
        # (kind, target) -> {"until": tick, "params": dict}
        self._active: Dict[Tuple[str, str], Dict] = {}

    def begin_tick(self, tick: int) -> None:
        self.tick = tick
        gone = [
            key
            for key, entry in self._active.items()
            if entry["until"] <= tick
        ]
        for key in gone:
            del self._active[key]

    def start(self, ev: FaultEvent) -> None:
        self._active[(ev.kind, ev.target)] = {
            "until": ev.at_tick + max(ev.duration_ticks, 1),
            "params": dict(ev.params),
        }

    def active(self, kind: str, target: str) -> Optional[Dict]:
        """Params of the matching active fault (exact target wins over
        the "*" wildcard), or None. Does not consume call budgets."""
        for key in ((kind, target), (kind, "*")):
            entry = self._active.get(key)
            if entry is not None:
                return entry["params"]
        return None

    def take(self, kind: str, target: str) -> Optional[Dict]:
        """Like active(), but consumes one unit of a params["calls"]
        budget (deactivating the fault at zero)."""
        for key in ((kind, target), (kind, "*")):
            entry = self._active.get(key)
            if entry is None:
                continue
            params = entry["params"]
            calls = params.get("calls")
            if calls is not None:
                if calls <= 0:
                    del self._active[key]
                    continue
                params["calls"] = calls - 1
                if params["calls"] <= 0:
                    del self._active[key]
            return params
        return None


# ----------------------------------------------------------------------
# Election lease-KV seam
# ----------------------------------------------------------------------


class ChaosLeaseKV(LeaseKV):
    """Wraps any LeaseKV; kv_drop raises a transport-style failure,
    kv_delay adds real latency. `target` is the owning server's logical
    name, so a plan can brown out ONE candidate's view of etcd."""

    def __init__(self, inner: LeaseKV, state: FaultState, target: str):
        self.inner = inner
        self._state = state
        self.target = target

    async def _gate(self) -> None:
        p = self._state.take("kv_delay", self.target)
        if p is not None:
            await asyncio.sleep(float(p.get("seconds", 0.01)))
        p = self._state.take("kv_drop", self.target)
        if p is not None:
            raise FaultInjected(
                f"chaos: kv round-trip dropped ({self.target})"
            )

    async def acquire(self, key, value, ttl) -> bool:
        await self._gate()
        return await self.inner.acquire(key, value, ttl)

    async def refresh(self, key, value, ttl) -> bool:
        await self._gate()
        return await self.inner.refresh(key, value, ttl)

    async def get(self, key):
        await self._gate()
        return await self.inner.get(key)

    async def wait_for_change(self, key, timeout) -> None:
        await self.inner.wait_for_change(key, timeout)


# ----------------------------------------------------------------------
# etcd gateway seam (the real HTTP dialect)
# ----------------------------------------------------------------------


class ChaosEtcdGateway(EtcdGateway):
    """Drop-in EtcdGateway whose round-trips consult the switchboard.

    Runs against tests/fake_etcd.FakeEtcd (or live etcd) so the REAL
    EtcdKV election stack — renewal retries included — is what gets
    stressed; etcd_drop with params={"calls": 1} is exactly "one etcd
    hiccup". Blocking by design: the gateway always runs in executor
    threads."""

    def __init__(self, endpoints: List[str], state: FaultState,
                 target: str = "etcd"):
        super().__init__(endpoints)
        self._state = state
        self.target = target

    def _post(self, path: str, payload: dict, timeout: float = 30.0) -> dict:
        p = self._state.take("etcd_delay", self.target)
        if p is not None:
            time.sleep(float(p.get("seconds", 0.01)))
        # Peek before consuming: params["path_prefix"] scopes the drop
        # to one endpoint family (e.g. "/v3/lease/keepalive" targets
        # renewals without starving the election's watcher reads), and
        # a non-matching round-trip must not burn the calls budget.
        p = self._state.active("etcd_drop", self.target)
        if p is not None and path.startswith(p.get("path_prefix", "")):
            self._state.take("etcd_drop", self.target)
            raise FaultInjected(
                f"chaos: etcd round-trip dropped ({self.target}, {path})"
            )
        return super()._post(path, payload, timeout)

    def wait_for_change(self, key: str, timeout: float = 60.0) -> bool:
        p = self._state.take("etcd_watch_stall", self.target)
        if p is not None:
            # The watch neither delivers nor errors — it just hangs
            # until the caller's timeout (a stalled gateway stream).
            time.sleep(min(timeout, float(p.get("seconds", timeout))))
            return False
        return super().wait_for_change(key, timeout)


# ----------------------------------------------------------------------
# gRPC seam
# ----------------------------------------------------------------------


def _not_master_response(method: str, master: str):
    cls = {
        "Discovery": pb.DiscoveryResponse,
        "GetCapacity": pb.GetCapacityResponse,
        "GetServerCapacity": pb.GetServerCapacityResponse,
        "ReleaseCapacity": pb.ReleaseCapacityResponse,
    }[method]
    out = cls()
    if master:
        out.mastership.master_address = master
    else:
        out.mastership.SetInParent()
    return out


class ChaosGrpcProxy(CapacityServicer):
    """A loopback gRPC hop in front of a CapacityServer.

    Clients (and downstream servers) dial the proxy; each RPC consults
    the switchboard for the proxy's `link` target, then delegates to
    the backend servicer by direct method call (same loop, same grpc
    context — aborts and metadata behave exactly as if the client hit
    the server). Faults: grpc_drop (UNAVAILABLE), grpc_delay,
    grpc_not_master (a spurious mastership redirect)."""

    def __init__(self, state: FaultState, link: str):
        self._state = state
        self.link = link
        self.backend: Optional[CapacityServicer] = None  # set by runner
        self._server = None
        self.port: Optional[int] = None

    @property
    def address(self) -> str:
        return f"127.0.0.1:{self.port}"

    async def start(self) -> int:
        import grpc

        server = grpc.aio.server()
        add_capacity_servicer(server, self)
        self.port = server.add_insecure_port("127.0.0.1:0")
        await server.start()
        self._server = server
        return self.port

    async def stop(self) -> None:
        if self._server is not None:
            await self._server.stop(grace=None)
            self._server = None

    async def _intercept(self, method: str, request, context):
        import grpc

        p = self._state.take("grpc_delay", self.link)
        if p is not None:
            await asyncio.sleep(float(p.get("seconds", 0.01)))
        p = self._state.take("grpc_drop", self.link)
        if p is not None:
            await context.abort(
                grpc.StatusCode.UNAVAILABLE,
                f"chaos: rpc dropped ({self.link})",
            )
        p = self._state.take("grpc_not_master", self.link)
        if p is not None:
            return _not_master_response(method, p.get("master", ""))
        return await getattr(self.backend, method)(request, context)

    async def Discovery(self, request, context):
        return await self._intercept("Discovery", request, context)

    async def GetCapacity(self, request, context):
        return await self._intercept("GetCapacity", request, context)

    async def GetServerCapacity(self, request, context):
        return await self._intercept("GetServerCapacity", request, context)

    async def ReleaseCapacity(self, request, context):
        return await self._intercept("ReleaseCapacity", request, context)

    async def WatchCapacity(self, request, context):
        """The server-streaming leg of the proxy: establishment walks
        the same fault seams as a unary RPC (grpc_not_master yields a
        terminal redirect — exactly what a flipped master streams),
        then every backend push is forwarded message for message."""
        import grpc

        p = self._state.take("grpc_delay", self.link)
        if p is not None:
            await asyncio.sleep(float(p.get("seconds", 0.01)))
        p = self._state.take("grpc_drop", self.link)
        if p is not None:
            await context.abort(
                grpc.StatusCode.UNAVAILABLE,
                f"chaos: rpc dropped ({self.link})",
            )
        p = self._state.take("grpc_not_master", self.link)
        if p is not None:
            from doorman_tpu.proto import doorman_stream_pb2 as spb

            out = spb.WatchCapacityResponse()
            if p.get("master"):
                out.mastership.master_address = p["master"]
            else:
                out.mastership.SetInParent()
            yield out
            return
        async for msg in self.backend.WatchCapacity(request, context):
            yield msg


# ----------------------------------------------------------------------
# Solver / backend seam
# ----------------------------------------------------------------------


class SolverInjector:
    """Wraps a CapacityServer's solver entry points (instance-level, no
    doorman code modified): solver_error makes the device solve raise
    (device lost), solver_slow stretches it, resident_overflow raises
    ResidentOverflow from the resident step — exercising the server's
    fallback-to-BatchSolver path and the handle-clearing fix — and
    grant_corrupt scales one row of the solve's output (a silent wrong
    answer, not a crash: the fault only the shadow-oracle audit can
    see)."""

    def __init__(self, state: FaultState, target: str):
        self._state = state
        self.target = target

    def _gate(self) -> None:
        # Runs in the tick's executor thread: blocking sleep is correct.
        p = self._state.take("solver_slow", self.target)
        if p is not None:
            time.sleep(float(p.get("seconds", 0.01)))
        p = self._state.take("solver_error", self.target)
        if p is not None:
            raise RuntimeError(
                f"chaos: device backend unreachable ({self.target})"
            )

    def _corrupt(self, gets):
        """While grant_corrupt is active, scale gets[row] by `factor`
        (default 0.75 — shrinking keeps capacity conservation and
        has <= wants intact, so the corruption passes every structural
        invariant and only the bit-identity audit can catch it)."""
        p = self._state.active("grant_corrupt", self.target)
        if p is None:
            return gets
        import numpy as np

        out = np.asarray(gets).copy()
        row = int(p.get("row", 0))
        if 0 <= row < out.shape[0]:
            out[row] = out[row] * float(p.get("factor", 0.75))
        return out

    def install(self, server) -> None:
        injector = self
        orig_get_solver = server._get_solver

        def get_solver():
            solver = orig_get_solver()
            if not getattr(solver, "_chaos_wrapped", False):
                orig_solve = solver.solve

                def solve(snap):
                    injector._gate()
                    return injector._corrupt(orig_solve(snap))

                solver.solve = solve
                solver._chaos_wrapped = True
            return solver

        server._get_solver = get_solver

        def wrap_step(orig_step):
            def step(solver, resources, config_epoch):
                p = injector._state.take(
                    "resident_overflow", injector.target
                )
                if p is not None:
                    from doorman_tpu.solver.resident import ResidentOverflow

                    raise ResidentOverflow(
                        f"chaos: injected overflow ({injector.target})"
                    )
                injector._gate()
                return orig_step(solver, resources, config_epoch)

            return step

        server._resident_step = wrap_step(server._resident_step)
        server._resident_wide_step = wrap_step(server._resident_wide_step)


# ----------------------------------------------------------------------
# Host seams: stale ports, backend probes
# ----------------------------------------------------------------------


class PortInjector:
    """Holds loopback ports bound, simulating the stale server an
    interrupted drive leaks (the ensure_ports_free failure mode)."""

    def __init__(self):
        self._sockets: List[socket.socket] = []

    def bind(self) -> int:
        s = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
        s.bind(("127.0.0.1", 0))
        s.listen(1)
        self._sockets.append(s)
        return s.getsockname()[1]

    def release_all(self) -> None:
        for s in self._sockets:
            s.close()
        self._sockets.clear()
