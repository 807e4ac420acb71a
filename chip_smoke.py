#!/usr/bin/env python3
"""Chip smoke: the batch server's device-resident tick on a TPU.

One process holds the chip for the whole run. It builds CapacityServers
the way the server CLI does (batch mode, native store, loopback gRPC),
loads 1,000,000 leases over 10,000 resources (100 clients each, every
algorithm lane by glob) and drives the tick with `tick_once`:

  A  f32, fused and scoped tick (the defaults): the pallas kernel is in
     the compiled tick, grants match the numpy oracle, and real
     GetCapacity / ReleaseCapacity RPCs are answered;
  B  f32 with stream push (delta tracking): a WatchCapacity watcher sees
     its grant move through tick-edge pushes, the first one from the
     full tick that runs `fused_tick_pallas`;
  C  f64, the CLI default: the same oracle check at the f64 bound.

`--chips 4` runs only the mesh path instead: the same load ticked by a
server whose resident tables are sharded over all four chips and by a
single-device server, in this process, with bit-identical stores.

Tick times printed here are smoke timings, not benchmark results. Any
failed check or exception exits non-zero before the last line, which is
`{"ok": true, "device": {...}}`. There is no CPU mode.
"""

from __future__ import annotations

import argparse
import asyncio
import json
import statistics
import sys
import time

import numpy as np

NUM_RES = 10_000
PER_RES = 100
A_TICKS = 8
C_TICKS = 4
ORACLE_SAMPLE = 60  # resources per lane checked against the oracle
RPC_TIMEOUT_S = 30.0

# (glob prefix, wire kind, template capacity). STATIC's capacity is per
# client; the shared lanes get ~60% of the ~5000 their clients want.
LANES = (
    ("none", "NO_ALGORITHM", 1000),
    ("static", "STATIC", 40),
    ("prop", "PROPORTIONAL_SHARE", 3000),
    ("fair", "FAIR_SHARE", 3000),
)


class SmokeError(AssertionError):
    pass


def check(cond: bool, msg: str) -> None:
    if not cond:
        raise SmokeError(msg)


def config_yaml() -> str:
    out = ["resources:"]
    for i, (prefix, kind, cap) in enumerate(LANES):
        glob = "*" if i == len(LANES) - 1 else f"{prefix}/*"
        out += [
            f'- identifier_glob: "{glob}"',
            f"  capacity: {cap}",
            f"  algorithm: {{kind: {kind}, lease_length: 600,",
            "              refresh_interval: 16, learning_mode_duration: 0}",
        ]
    return "\n".join(out) + "\n"


def resource_ids(num_res: int) -> list:
    """num_res ids, the lanes interleaved: lane = index % len(LANES)."""
    return [
        f"{LANES[i % len(LANES)][0]}/{i // len(LANES)}"
        for i in range(num_res)
    ]


def lane_of(resource_id: str):
    prefix = resource_id.split("/", 1)[0]
    return next(lane for lane in LANES if lane[0] == prefix)


async def make_server(name: str, *, dtype: str, stream_push=False,
                      mesh=None):
    """A batch server as `doorman-server --mode batch --native-store`
    builds it, listening on loopback. Its own tick loop is cancelled:
    this script drives every tick, so no tick error is swallowed."""
    from doorman_tpu import native
    from doorman_tpu.server.config import parse_yaml_config
    from doorman_tpu.server.election import TrivialElection
    from doorman_tpu.server.server import CapacityServer

    check(native.native_available(), "native store engine unavailable")
    server = CapacityServer(
        name, TrivialElection(), mode="batch", tick_interval=1.0,
        minimum_refresh_interval=0.0, native_store=True,
        solver_dtype=dtype, stream_push=stream_push, mesh=mesh,
    )
    check(server._native_store, "server fell back to the Python store")
    port = await server.start(0, host="127.0.0.1")
    await server.load_config(parse_yaml_config(config_yaml()))
    await asyncio.sleep(0)  # election callbacks land
    check(server.is_master, f"{name} did not become master")
    server.current_master = f"127.0.0.1:{port}"
    for task in server._tasks:
        task.cancel()
    server._tasks.clear()
    return server, f"127.0.0.1:{port}"


def load(server, seed: int, num_res: int, per_res: int):
    """num_res * per_res leases, has 0, integer wants in [1, 100) and
    subclients in [1, 5), all made from `seed`. Returns {resource id:
    (wants, subclients)} in store order."""
    rng = np.random.default_rng(seed)
    ids = resource_ids(num_res)
    wants = rng.integers(1, 100, (num_res, per_res)).astype(np.float64)
    subs = rng.integers(1, 5, (num_res, per_res)).astype(np.int32)
    zeros = np.zeros(per_res)
    for r, rid in enumerate(ids):
        res = server.get_or_create_resource(rid)
        res.store.bulk_assign(
            [f"c{r * per_res + j}" for j in range(per_res)],
            600.0, 16.0, zeros, wants[r], subs[r],
        )
    return {rid: (wants[r], subs[r]) for r, rid in enumerate(ids)}


def grants(server, resource_id: str) -> dict:
    return {c: lease.has for c, lease in
            server.resources[resource_id].store.items()}


def oracle_error(server, loaded: dict, sample: list) -> dict:
    """{lane: max over `sample` of |store grant - oracle| / row scale},
    where the oracle sees the inputs of the first solve: the loaded
    wants and subclients with every `has` at 0."""
    from doorman_tpu.algorithms.kinds import AlgoKind
    from doorman_tpu.algorithms.tick import oracle_row

    worst = {lane[1]: 0.0 for lane in LANES}
    for rid in sample:
        _, kind, cap = lane_of(rid)
        wants, subs = loaded[rid]
        got = grants(server, rid)
        check(len(got) == len(wants), f"{rid}: {len(got)} leases")
        have = np.asarray([got[c] for c in sorted(got, key=lambda c:
                                                  int(c[1:]))])
        expected = oracle_row(
            int(AlgoKind[kind]), float(cap), float(cap), wants,
            np.zeros_like(wants), subs.astype(np.float64),
        )
        scale = max(float(cap), float(wants.max()))
        err = float(np.abs(have - expected).max()) / scale
        worst[kind] = max(worst[kind], err)
    return worst


def check_oracle(phase: str, server, loaded: dict, bound: float) -> None:
    sample = oracle_sample(loaded)
    errs = oracle_error(server, loaded, sample)
    print(f"[{phase}] oracle over {len(sample)} resources, max rel error "
          f"per lane {errs} (bound {bound:g})", flush=True)
    check(max(errs.values()) <= bound, f"oracle error over {bound:g}")


def oracle_sample(loaded: dict) -> list:
    ids = list(loaded)
    out = []
    for k in range(len(LANES)):
        lane = ids[k::len(LANES)]
        step = max(len(lane) // ORACLE_SAMPLE, 1)
        out += lane[::step][:ORACLE_SAMPLE]
    return out


def check_pallas(solver, kernel: str) -> None:
    check(kernel in solver.traced_kernels,
          f"tick did not compile {kernel} (traced: "
          f"{sorted(solver.traced_kernels)}): it took the XLA solve")


def check_on_tpu(solver, count: int) -> None:
    tables = (solver._wants, solver._has, solver._sub, solver._act)
    for t in tables:
        devs = {d.platform for d in t.devices()}
        check(devs == {"tpu"}, f"resident table on {devs}")
        check(len(t.devices()) == count,
              f"resident table on {len(t.devices())} devices")


async def timed_ticks(server, n: int) -> list:
    out = []
    for _ in range(n):
        t0 = time.perf_counter()
        await server.tick_once()
        out.append(time.perf_counter() - t0)
    return out


def report_ticks(phase: str, ticks: list) -> None:
    ms = [t * 1e3 for t in ticks]
    print(f"[{phase}] smoke timing: first tick (compile) {ms[0]} ms, "
          f"median of the next {len(ms) - 1} {statistics.median(ms[1:])} "
          f"ms (all ms: {ms})", flush=True)


def capacity_request(client: str, resource_id: str, wants: float):
    from doorman_tpu.proto import doorman_pb2 as pb

    req = pb.GetCapacityRequest(client_id=client)
    rr = req.resource.add()
    rr.resource_id = resource_id
    rr.wants = wants
    rr.priority = 1
    return req


async def phase_a(seed: int) -> None:
    import grpc

    from doorman_tpu.proto import doorman_pb2 as pb
    from doorman_tpu.proto.grpc_api import CapacityStub
    from doorman_tpu.algorithms.tick import F32_PARITY_REL_BOUND

    server, addr = await make_server("smoke-a", dtype="f32")
    try:
        t0 = time.perf_counter()
        loaded = load(server, seed, NUM_RES, PER_RES)
        print(f"[A] loaded {NUM_RES * PER_RES} leases over {NUM_RES} "
              f"resources in {time.perf_counter() - t0} s", flush=True)
        ticks = await timed_ticks(server, 2)
        solver = server._resident
        check(solver is not None, "the tick did not take the resident path")
        # Depth-1 pipeline: tick 2 applied tick 1's full solve.
        check_oracle("A", server, loaded, F32_PARITY_REL_BOUND)
        before = dict(solver.phase_s)
        ticks += await timed_ticks(server, A_TICKS - 2)
        report_ticks("A", ticks)
        n = A_TICKS - 2
        print("[A] engine phases, ms per tick over the last "
              f"{n} ticks (host clock): " + ", ".join(
                  f"{k} {(v - before[k]) * 1e3 / n}"
                  for k, v in solver.phase_s.items() if v > before[k]
              ) + f"; last scope {solver.last_scope}", flush=True)
        check_on_tpu(solver, 1)
        check_pallas(solver, "solve_dense_pallas")
        print(f"[A] pallas kernels in the tick: "
              f"{sorted(solver.traced_kernels)}; solve modes "
              f"{solver.solve_modes}", flush=True)

        targets = ["prop/3", "fair/3", "static/3", "none/3"]
        async with grpc.aio.insecure_channel(addr) as ch:
            stub = CapacityStub(ch)
            for i, rid in enumerate(targets):
                await stub.GetCapacity(
                    capacity_request(f"smoke{i}", rid, 25.0),
                    timeout=RPC_TIMEOUT_S,
                )
            await timed_ticks(server, 4)
            for i, rid in enumerate(targets):
                out = await stub.GetCapacity(
                    capacity_request(f"smoke{i}", rid, 25.0),
                    timeout=RPC_TIMEOUT_S,
                )
                got = out.response[0].gets.capacity
                check(got > 0, f"smoke{i} on {rid} granted {got}")
                _, kind, cap = lane_of(rid)
                held = grants(server, rid).values()
                total = sum(held)
                if kind in ("PROPORTIONAL_SHARE", "FAIR_SHARE"):
                    check(total <= cap * (1 + F32_PARITY_REL_BOUND),
                          f"{rid}: granted {total} > capacity {cap}")
                if kind == "STATIC":  # capacity is per client
                    check(max(held) <= cap, f"{rid}: a grant over {cap}")
                print(f"[A] GetCapacity smoke{i} {rid}: {got} "
                      f"(resource total {total}, capacity {cap})",
                      flush=True)
            for i, rid in enumerate(targets):
                rel = pb.ReleaseCapacityRequest(
                    client_id=f"smoke{i}", resource_id=[rid]
                )
                await stub.ReleaseCapacity(rel, timeout=RPC_TIMEOUT_S)
                check(not server.resources[rid].store.has_client(
                    f"smoke{i}"), f"smoke{i} still holds {rid}")
        await timed_ticks(server, 1)
        print("[A] ok", flush=True)
    finally:
        await server.stop()


class StreamReader:
    """Reads a server stream, keeping a pending read across timeouts
    (cancelling a read would cancel the whole RPC)."""

    def __init__(self, call):
        self.call = call
        self._pending = None

    async def read(self, timeout: float):
        if self._pending is None:
            self._pending = asyncio.ensure_future(self.call.read())
        done, _ = await asyncio.wait({self._pending}, timeout=timeout)
        if not done:
            return None
        task, self._pending = self._pending, None
        return task.result()

    def cancel(self):
        if self._pending is not None:
            self._pending.cancel()
        self.call.cancel()


async def watched_grants(reader, rid: str, timeout: float) -> list:
    """Every grant for `rid` pushed until the stream goes quiet."""
    import grpc

    out = []
    while True:
        msg = await reader.read(timeout)
        if msg is None:
            return out
        check(msg is not grpc.aio.EOF, "watch stream ended")
        out += [r.gets.capacity for r in msg.response
                if r.resource_id == rid]


async def phase_b(seed: int) -> None:
    import grpc

    from doorman_tpu.proto import doorman_stream_pb2 as spb
    from doorman_tpu.proto.grpc_api import CapacityStub

    server, addr = await make_server("smoke-b", dtype="f32",
                                     stream_push=True)
    rid = "prop/7"
    try:
        load(server, seed, NUM_RES, PER_RES)
        async with grpc.aio.insecure_channel(addr) as ch:
            stub = CapacityStub(ch)
            req = spb.WatchCapacityRequest(client_id="watcher")
            rr = req.resource.add()
            rr.resource_id = rid
            rr.wants = 30.0
            rr.priority = 1
            reader = StreamReader(stub.WatchCapacity(req))
            try:
                snap = await reader.read(RPC_TIMEOUT_S)
                check(snap is not None and snap.snapshot,
                      "no opening snapshot on the watch stream")
                # Tick 1 is a full solve (fused_tick_pallas with the
                # delta compare); tick 2 applies it and pushes.
                ticks = await timed_ticks(server, 2)
                solver = server._resident
                check_on_tpu(solver, 1)
                check_pallas(solver, "fused_tick_pallas")
                first = await watched_grants(reader, rid, 2.0)
                check(first and first[-1] > 0,
                      f"no grant pushed after the full tick: {first}")
                print(f"[B] pushed after the fused_tick_pallas tick: "
                      f"{first}", flush=True)
                # Another client's wants move the proportion.
                await stub.GetCapacity(
                    capacity_request("other", rid, 2000.0),
                    timeout=RPC_TIMEOUT_S,
                )
                moved = []
                for _ in range(4):
                    ticks += await timed_ticks(server, 1)
                    moved += await watched_grants(reader, rid, 1.0)
                check(moved and moved[-1] < first[-1],
                      f"watcher grant did not move down: {first[-1]} "
                      f"-> {moved}")
                check(server.stream_fanout_errors == 0,
                      f"{server.stream_fanout_errors} stream fanouts "
                      "failed")
                print(f"[B] pushed after the wants change: {moved}; "
                      f"pallas kernels {sorted(solver.traced_kernels)}",
                      flush=True)
                report_ticks("B", ticks)
            finally:
                reader.cancel()
        print("[B] ok", flush=True)
    finally:
        await server.stop()


async def phase_c(seed: int) -> None:
    from doorman_tpu.algorithms.tick import F64_TPU_REL_BOUND

    server, _ = await make_server("smoke-c", dtype="f64")
    try:
        loaded = load(server, seed, NUM_RES, PER_RES)
        ticks = await timed_ticks(server, 2)
        solver = server._resident
        check(solver is not None, "the tick did not take the resident path")
        check(solver._dtype == np.float64, f"solver dtype {solver._dtype}")
        check_on_tpu(solver, 1)
        check_oracle("C", server, loaded, F64_TPU_REL_BOUND)
        ticks += await timed_ticks(server, C_TICKS - 2)
        report_ticks("C", ticks)
        print("[C] ok", flush=True)
    finally:
        await server.stop()


async def four_chips(seed: int, devices: list) -> None:
    """The mesh-resident tick over every chip against the single-device
    tick on devices[0]: bit-identical stores, shards on every chip."""
    from doorman_tpu.parallel.mesh import make_mesh_from_spec

    mesh = make_mesh_from_spec("auto")
    check(mesh.devices.size == len(devices), f"mesh {dict(mesh.shape)}")
    meshed, _ = await make_server("smoke-mesh", dtype="f32", mesh=mesh)
    single, _ = await make_server("smoke-one", dtype="f32")
    try:
        load(meshed, seed, NUM_RES, PER_RES)
        load(single, seed, NUM_RES, PER_RES)
        t_mesh, t_one = [], []
        for tick in range(A_TICKS):
            t_mesh += await timed_ticks(meshed, 1)
            t_one += await timed_ticks(single, 1)
            if tick == 0:
                # Deliver every row on every tick: the two layouts
                # rotate delivery differently, and rows still moving
                # would land in their stores at different ticks.
                meshed._resident.rotate_ticks = 1
                single._resident.rotate_ticks = 1
        report_ticks("mesh x4", t_mesh)
        report_ticks("single", t_one)
        solver = meshed._resident
        check_pallas(solver, "solve_dense_pallas")
        ids = {d.id for d in devices}
        for name in ("_wants", "_has", "_sub", "_act"):
            table = getattr(solver, name)
            on = {s.device.id for s in table.addressable_shards}
            check(on == ids, f"{name} shards on {on}, want {ids}")
        check_on_tpu(single._resident, 1)
        check({d.id for d in single._resident._wants.devices()}
              == {devices[0].id}, "single-device tables off devices[0]")
        for d in devices:
            stats = d.memory_stats() or {}
            print(f"[mesh] device {d.id}: bytes_in_use "
                  f"{stats.get('bytes_in_use')}", flush=True)
        diverged = 0
        for rid in meshed.resources:
            a = grants(meshed, rid)
            b = grants(single, rid)
            check(a.keys() == b.keys(), f"{rid}: lease sets differ")
            diverged += sum(
                np.float64(a[c]).tobytes() != np.float64(b[c]).tobytes()
                for c in a
            )
        check(diverged == 0, f"{diverged} grants differ mesh vs single")
        print(f"[mesh] {len(meshed.resources)} resources: stores "
              f"bit-identical, tables sharded over {sorted(ids)}",
              flush=True)
    finally:
        await meshed.stop()
        await single.stop()


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    p.add_argument("--chips", type=int, choices=(1, 4), default=1)
    p.add_argument("--seed", type=int, default=0)
    args = p.parse_args(argv)

    import jax

    devices = jax.devices()
    print(f"jax {jax.__version__}; device_kind "
          f"{devices[0].device_kind!r}; platform {devices[0].platform}; "
          f"{len(devices)} device(s)", flush=True)
    if devices[0].platform != "tpu":
        print(f"chip_smoke: no TPU (platform {devices[0].platform}); "
              "this script runs only on the chip", file=sys.stderr)
        return 1
    if len(devices) != args.chips:
        print(f"chip_smoke: --chips {args.chips} but {len(devices)} "
              "device(s) visible", file=sys.stderr)
        return 1

    from doorman_tpu.utils.compile_cache import place_compile_cache

    print(f"compile cache: {place_compile_cache()}", flush=True)
    t0 = time.perf_counter()
    if args.chips == 4:
        asyncio.run(four_chips(args.seed, devices))
    else:
        asyncio.run(phase_a(args.seed))
        asyncio.run(phase_b(args.seed))
        asyncio.run(phase_c(args.seed))  # last: f64 turns x64 on
    print(f"smoke wall {time.perf_counter() - t0} s", flush=True)
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
    }}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
