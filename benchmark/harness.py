"""One run of one benchmark cell: build, load, warm, measure, check.

Everything a cell needs is found by name: `BENCHMARK.json` names the
cell's configuration (a file under `configs/`), its traffic mix (a data
file under `traffic/`, read by `traffic_gen`) and its metrics (one
reader each under `metrics/`). Adding a cell, a configuration, a mix or
a metric adds files and entries and edits none.

A run prints progress on standard error, every line tagged with the
device, and one JSON line last on standard output.
"""

from __future__ import annotations

import asyncio
import importlib.util
import json
import math
import shutil
import sys
import tempfile
import time
from pathlib import Path
from types import SimpleNamespace

import numpy as np

import reference
import roofline

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent
SLOW_TICK_S = 1.0  # a tick longer than this is logged with its phases


class BenchError(RuntimeError):
    """The run cannot produce a result (no chip, bad cell, a failure)."""


class Log:
    def __init__(self, device: str = "no device yet"):
        self.device = device

    def __call__(self, msg: str) -> None:
        print(f"[{self.device}] {msg}", file=sys.stderr, flush=True)


# -- the cell, found by name ------------------------------------------------


def load_cell(name: str, root: Path = ROOT) -> dict:
    bench = json.loads((root / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise BenchError(f"no workload {name!r} in BENCHMARK.json")
    cell = cells[name]
    cfg = {c["name"]: c for c in bench["configs"]}[cell["config"]]
    e2e = [m for m in bench["end_to_end"]
           if name in m.get("workloads", [name])]
    moved = {m["name"] for m in e2e}
    per_layer = [m for m in bench["per_layer"]
                 if (name in m["workloads"] if "workloads" in m
                     else m["moves"] in moved)]
    return {"cell": cell,
            "config": json.loads((root / cfg["file"]).read_text()),
            "traffic": json.loads(
                (HERE / "traffic" / f"{cell['traffic']}.json").read_text()),
            "end_to_end": e2e, "per_layer": per_layer}


def reader(metric: str):
    path = HERE / "metrics" / f"{metric}.py"
    spec = importlib.util.spec_from_file_location(
        f"bench_metric_{metric.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


# -- the deployment -----------------------------------------------------------


def resource_ids(config: dict) -> list:
    """Resource ids with the lanes interleaved: lane = index % lanes."""
    lanes = config["lanes"]
    return [f"{lanes[i % len(lanes)]['prefix']}/{i // len(lanes)}"
            for i in range(config["resources"])]


def lane_of(config: dict, resource_id: str) -> dict:
    prefix = resource_id.split("/", 1)[0]
    return next(ln for ln in config["lanes"] if ln["prefix"] == prefix)


def config_yaml(config: dict) -> str:
    out = ["resources:"]
    lanes = config["lanes"]
    for i, ln in enumerate(lanes):
        glob = "*" if i == len(lanes) - 1 else f"{ln['prefix']}/*"
        out += [
            f'- identifier_glob: "{glob}"',
            f"  capacity: {ln['capacity']}",
            f"  algorithm: {{kind: {ln['kind']}, "
            f"lease_length: {config['lease_length_s']},",
            f"              refresh_interval: {config['refresh_interval_s']}, "
            f"learning_mode_duration: {config['learning_mode_duration_s']}}}",
        ]
    return "\n".join(out) + "\n"


async def build_server(config: dict):
    """The server as `doorman-server --mode batch --native-store` builds
    it, on loopback, with its tick loop cancelled: the benchmark drives
    every tick through tick_once."""
    from doorman_tpu import native
    from doorman_tpu.server.config import parse_yaml_config
    from doorman_tpu.server.election import TrivialElection
    from doorman_tpu.server.server import CapacityServer

    s = config["server"]
    if s["native_store"] and not native.native_available():
        raise BenchError("native store engine unavailable")
    server = CapacityServer(
        "bench", TrivialElection(), mode=s["mode"],
        tick_interval=s["tick_interval_s"],
        minimum_refresh_interval=s["minimum_refresh_interval_s"],
        native_store=s["native_store"], solver_dtype=s["solver_dtype"],
        stream_push=s["stream_push"], fused_tick=s["fused_tick"],
        scoped_solve=s["scoped_solve"],
        tick_pipeline_depth=s["tick_pipeline_depth"],
    )
    port = await server.start(0, host="127.0.0.1")
    await server.load_config(parse_yaml_config(config_yaml(config)))
    await asyncio.sleep(0)
    if not server.is_master:
        raise BenchError("the server did not become master")
    addr = f"127.0.0.1:{port}"
    server.current_master = addr
    for task in server._tasks:
        task.cancel()
    server._tasks.clear()
    return server, addr


def load(server, config: dict, seed: int):
    """All leases in one StoreEngine.bulk_assign call: has 0, integer
    wants and subclients drawn from the seed (chip_smoke.py's loader)."""
    from traffic_gen import Leases

    n_res, per = config["resources"], config["clients_per_resource"]
    rng = np.random.default_rng(np.random.SeedSequence([seed, 0]))
    lo, hi = config["wants"]
    wants = rng.integers(lo, hi, n_res * per).astype(np.float64)
    slo, shi = config["subclients"]
    subs = rng.integers(slo, shi, n_res * per).astype(np.int32)
    stores = [server.get_or_create_resource(rid).store
              for rid in resource_ids(config)]
    engine = stores[0]._engine
    rids = np.repeat(np.asarray([s._rid for s in stores], np.int32), per)
    cids = np.fromiter((engine.client_handle(f"c{i}")
                        for i in range(n_res * per)), np.int64,
                       count=n_res * per)
    n = engine.bulk_assign(
        rids, cids, np.full(n_res * per, time.time() + config[
            "lease_length_s"]),
        np.full(n_res * per, float(config["refresh_interval_s"])),
        np.zeros(n_res * per), wants, subs,
    )
    if n != n_res * per:
        raise BenchError(f"bulk_assign loaded {n} of {n_res * per} leases")
    return engine, stores, Leases(rids, cids, wants, subs, per)


class CompileCounter:
    """Programs traced and compiled, with the time of each, through
    jax.monitoring (a retrace means a shape the warm-up missed)."""

    EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
              "/jax/core/compile/backend_compile_duration")

    def __init__(self):
        self.events = []

    def install(self):
        import jax

        def listener(event, duration, **_):
            if event in self.EVENTS:
                self.events.append((time.perf_counter(), event, duration))

        jax.monitoring.register_event_duration_secs_listener(listener)

    def count(self, t0: float, t1: float, event: str) -> int:
        return sum(1 for t, e, _ in self.events if t0 <= t < t1 and e == event)

    def seconds(self, t0: float, t1: float) -> dict:
        """Seconds spent per event kind in [t0, t1)."""
        out = {}
        for t, e, d in self.events:
            if t0 <= t < t1:
                key = e.rsplit("/", 1)[-1]
                out[key] = out.get(key, 0.0) + d
        return out


def engines(server) -> list:
    """[(component, engine)] for the resident tick engines the server
    runs now: the dense one, and the chunked one for lane resources wider
    than the dense bucket cap. The server creates each on first need."""
    return [(e.component, e)
            for e in (server._resident, server._resident_wide)
            if e is not None]


def lap_totals(server) -> dict:
    """{component: its cumulative phase laps}, taken before a tick."""
    return {name: dict(e.phase_s) for name, e in engines(server)}


def traced_kernels(server) -> list:
    """The kernels the engines traced, sorted; empty with none."""
    return sorted(set().union(*(e.traced_kernels
                                for _, e in engines(server))))


def tick_record(server, before: dict, start: float, wall: float) -> dict:
    """One tick: `engines` holds each engine's lap deltas against
    `before` (an engine created inside the tick counts from zero) and its
    `last_scope`; `phases` sums the deltas per phase name over the
    engines. `scope_rows` is the dense engine's `last_scope["rows"]` (0
    with none): resources in its scope, the unit `scope_rows.tick` and
    the dense kernel's roofline read. The chunked engine counts chunk
    rows of its slot width; a reader of it takes
    `engines["resident_wide"]["scope"]`."""
    rec = {"start": start, "wall_s": wall, "phases": {}, "scope_rows": 0,
           "engines": {}}
    for name, e in engines(server):
        was = before.get(name, {})
        laps = {k: v - was.get(k, 0.0) for k, v in e.phase_s.items()}
        rec["engines"][name] = {"phases": laps, "scope": dict(e.last_scope)}
        for k, v in laps.items():
            rec["phases"][k] = rec["phases"].get(k, 0.0) + v
        if name == "resident":
            rec["scope_rows"] = int(e.last_scope.get("rows", 0))
    return rec


class Profiler:
    """jax.profiler over the whole window: the trace starts in set-up
    (its start-up cost stays out of the window), the `bench.traced`
    annotation spans the window, and the trace stops after it."""

    def __init__(self, log):
        self.dir = None
        self.ann = None
        self.log = log

    def start(self):
        import jax

        self.dir = tempfile.mkdtemp(prefix="bench-trace-")
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0  # host annotations stay, calls do not
        jax.profiler.start_trace(self.dir, profiler_options=opts)

    def open_window(self):
        import jax

        self.ann = jax.profiler.TraceAnnotation("bench.traced")
        self.ann.__enter__()

    def stop(self):
        import jax

        if self.ann is not None:
            self.ann.__exit__(None, None, None)
            self.ann = None
        jax.profiler.stop_trace()

    def read(self, kernel_names):
        import tracing

        if self.dir is None:
            return None
        try:
            rows = tracing.extract(self.dir)
            return tracing.reduce(rows, kernel_names)
        finally:
            shutil.rmtree(self.dir, ignore_errors=True)


# -- the tick cells -------------------------------------------------------


class GcWatch:
    """Garbage-collection pauses of the process (gc.callbacks), so a
    host stall can be told apart from the program's own work."""

    def __init__(self):
        self.pauses = []  # (start perf_counter, seconds, generation)
        self._t = None

    def install(self):
        import gc

        def cb(phase, info):
            if phase == "start":
                self._t = time.perf_counter()
            elif self._t is not None:
                t = self._t
                self.pauses.append((t, time.perf_counter() - t,
                                    info["generation"]))
                self._t = None

        gc.callbacks.append(cb)
        return self

    def within(self, t0: float, t1: float):
        """(seconds, count, count of generation-2) of pauses in [t0, t1)."""
        got = [(d, g) for t, d, g in self.pauses if t0 <= t < t1]
        return (sum(d for d, _ in got), len(got),
                sum(1 for _, g in got if g == 2))


def program_keys(server) -> set:
    """Shape keys of the tick programs the resident engines hold (a new
    one is a shape met for the first time in this process), without
    the lane set, each led by its engine's component."""
    return {(name,) + tuple(x for x in k if not isinstance(x, frozenset))
            for name, e in engines(server) for k in e._tick_fns}


async def drive_ticks(server, leases, engine, spec, seed, seconds, trace,
                      r, counter, log):
    """Ticks back to back through tick_once; before each, the open-loop
    writer writes every refresh slot that came due. The warm-up paces the
    writer off the clock over a range of batch sizes around the tick
    period, until the shapes stop compiling; then the window; then the
    settle ticks the check waits for."""
    from traffic_gen import OpenLoopWriter

    config, traffic = spec["config"], spec["traffic"]
    ticks = []
    gcw = GcWatch().install()
    writer = OpenLoopWriter(
        engine, leases, traffic, seed, config["lease_length_s"],
        config["refresh_interval_s"], config["wants"], len(config["lanes"]))

    async def tick(record: bool, write=None):
        import jax

        t_w = time.perf_counter()
        if write is not None:
            with jax.profiler.TraceAnnotation("bench.write"):
                batch = write()
        else:
            batch = 0
        before = lap_totals(server)
        n_events = len(counter.events)
        keys = program_keys(server)
        t = time.perf_counter()
        with jax.profiler.TraceAnnotation("bench.tick"):
            await server.tick_once()
        wall = time.perf_counter() - t
        rec = tick_record(server, before, t, wall)
        rec["traced"] = len(counter.events) > n_events
        rec["batch"] = batch
        rec["write_s"] = t - t_w
        new_keys = program_keys(server) - keys
        rec["new_programs"] = len(new_keys)
        if record:
            ticks.append(rec)
        if wall > SLOW_TICK_S or (record and rec["traced"]):
            gc_s, gc_n, gc2 = gcw.within(t, t + wall)
            log(f"slow tick {wall} s after a batch of {batch}: phases "
                + ", ".join(f"{k} {v}" for k, v in rec["phases"].items()
                            if v > 1e-3)
                + f"; scope {rec['scope_rows']}; new programs "
                f"{sorted(new_keys, key=repr)}; compile events "
                f"{counter.seconds(t, t + wall)}; gc {gc_s} s in {gc_n} "
                f"({gc2} of generation 2)")
        return rec

    t = time.perf_counter()
    await tick(False)
    r.split["first_tick_s"] = time.perf_counter() - t
    # Warm-up, paced: each tick writes pace x the recent period of
    # schedule, over a range of batch sizes around the tick period.
    paces = traffic["warm_paces"]
    period = []  # recent quiet tick periods (write + tick) at pace 1
    t = time.perf_counter()
    quiet = 0
    warm_ticks = 0
    while (time.perf_counter() - t < traffic["warm_min_s"]
           or (quiet < traffic["warm_quiet_ticks"]
               and time.perf_counter() - t < traffic["warm_max_s"])):
        est = float(np.median(period[-16:])) if period else 0.25
        pace = paces[warm_ticks % len(paces)]
        rec = await tick(False, lambda: writer.write_span(pace * est))
        if rec["traced"]:
            quiet = 0
        else:
            quiet += 1
            if pace == 1.0:
                period.append(rec["wall_s"] + rec["write_s"])
        warm_ticks += 1
    r.split["warm_paced_s"] = time.perf_counter() - t
    # Warm-up, on the clock: the traffic as the window sees it, the
    # profiler on in a traced run; a tick that compiles puts the
    # schedule back to one period behind, so set-up cannot spiral.
    est = float(np.median(period[-16:])) if period else 0.25
    prof = Profiler(log) if trace else None
    t = time.perf_counter()
    if prof is not None:
        prof.start()
    writer.anchor(time.perf_counter(), est)
    quiet = 0
    while (time.perf_counter() - t < traffic["warm_open_s"]
           or (quiet < traffic["warm_quiet_ticks"]
               and time.perf_counter() - t < traffic["warm_max_s"])):
        rec = await tick(False, writer.write_due)
        warm_ticks += 1
        if rec["traced"]:
            quiet = 0
            writer.anchor(time.perf_counter(), est)
        else:
            quiet += 1
    r.split["warm_open_s"] = time.perf_counter() - t
    r.split["warm_ticks"] = warm_ticks
    r.split["warm_programs"] = len(program_keys(server))
    writer.late.clear()
    sent0, refused0, write0 = writer.sent, writer.refused, writer.write_s
    if prof is not None:
        prof.open_window()
    t0 = time.perf_counter()
    r.window_start = t0
    r.setup_s = t0 - r.t_process
    while time.perf_counter() - t0 < seconds:
        rec = await tick(True, writer.write_due)
    t1 = time.perf_counter()
    if prof is not None:
        prof.stop()
        r.trace_ticks = ticks
    r.window_s = t1 - t0
    r.window_end = t1
    r.ticks = ticks
    r.compiles = counter.count(t0, t1, CompileCounter.EVENTS[0])
    r.attempted = writer.sent - sent0
    r.failed = writer.refused - refused0
    third = max(len(ticks) // 3, 1)
    r.writer = {"refreshes": r.attempted, "refused": r.failed,
                "write_s": writer.write_s - write0, "ticks": len(ticks),
                "late": writer.late,
                "batch_first": float(np.mean([t["batch"]
                                              for t in ticks[:third]])),
                "batch_last": float(np.mean([t["batch"]
                                             for t in ticks[-third:]])),
                "gc": gcw.within(t0, t1),
                "new_programs": sum(t["new_programs"] for t in ticks)}
    r.memory_peak = memory_peak(r.devices)
    if trace:
        r.trace = prof.read(traced_kernels(server))
    for _ in range(config["settle_ticks"]):
        await server.tick_once()
    r.settle_s = time.perf_counter() - t1


def memory_peak(devices) -> int:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in devices]
    return int(max(peaks))


# -- the check ------------------------------------------------------------


def dump_store(server, engine, config: dict, ids=None):
    """{resource id: (client handles, wants, has, subclients)} straight
    from the store of record, one C call."""
    ids = ids or resource_ids(config)
    stores = [server.resources[rid].store for rid in ids]
    ridx, cid, wants, has, sub, _ = engine.pack(stores)
    bounds = np.searchsorted(ridx, np.arange(len(ids) + 1))
    return {rid: (cid[a:b], wants[a:b], has[a:b], sub[a:b])
            for rid, a, b in zip(ids, bounds[:-1], bounds[1:])}


def check_store(dump: dict, config: dict, leases, prev=None) -> dict:
    """grant_gap: over every resource, the widest gap between a stored
    grant and the reference's on the benchmark's own record of wants and
    subclients, as a share of the row's scale. PROPORTIONAL_SHARE takes
    `prev` (by default the stored grants themselves) as the grants the
    solve saw. A lease missing, unknown, or holding wants the benchmark
    did not write reads 1."""
    inv = np.full(int(leases.cids.max()) + 1, -1, np.int64)
    inv[leases.cids] = np.arange(len(leases.cids))
    per = config["clients_per_resource"]
    worst = 0.0
    bad_rows = 0
    for rid, (cid, w_store, has, sub_store) in dump.items():
        lane = lane_of(config, rid)
        e = np.where(cid < len(inv), inv[np.minimum(cid, len(inv) - 1)], -1)
        ok = (len(e) == per and bool((e >= 0).all())
              and np.array_equal(leases.wants[e], w_store)
              and np.array_equal(leases.subclients[e].astype(np.float64),
                                 sub_store))
        if not ok:
            bad_rows += 1
            worst = 1.0
            continue
        seen = has if prev is None else prev[rid][2]
        g = reference.row_gap(lane["kind"], lane["capacity"], w_store, seen,
                              sub_store, has)
        worst = max(worst, g)
    return {"grant_gap": worst, "bad_rows": bad_rows}


def control_dump(dump: dict, config: dict) -> dict:
    """The control in the program's place: every row's grants replaced by
    the reference's computed in bfloat16 (the precision below the
    configuration's f32) on the same inputs."""
    low = reference.bf16()
    out = {}
    for rid, (cid, w, has, sub) in dump.items():
        lane = lane_of(config, rid)
        g = reference.lane_grants(lane["kind"], lane["capacity"], w, has,
                                  sub, low).astype(np.float64)
        out[rid] = (cid, w, g, sub)
    return out


# -- the run ------------------------------------------------------------------


def device_info(devices) -> dict:
    d = devices[0]
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(devices)}


def find_devices(chips: int, require_chip: bool):
    import jax

    devices = jax.devices()
    if require_chip:
        if devices[0].platform != "tpu":
            raise BenchError(
                f"no TPU: JAX found {devices[0].platform}; the benchmark "
                "runs only on the chip")
        if len(devices) < chips:
            raise BenchError(f"the cell asks for {chips} chips, JAX found "
                             f"{len(devices)}")
    return devices[:chips]


async def run_cell(spec: dict, seed: int, seconds: float, trace: bool, *,
                   t_process: float, require_chip: bool = True,
                   control: bool = False, log=None) -> dict:
    """One run; returns the result line's object. With `control`, it
    also holds `_control`: the line the same check gives with the control
    in the program's place (`control_dump`)."""
    seed = int(seed) % (1 << 64)  # any whole number; SeedSequence wants >= 0
    log = log or Log()
    devices = find_devices(spec["cell"]["chips"], require_chip)
    dev = device_info(devices)
    log.device = f"{dev['platform']} {dev['kind']} x{dev['count']}"
    config, traffic = spec["config"], spec["traffic"]
    r = SimpleNamespace(
        t_process=t_process, devices=devices, split={}, ticks=[],
        trace=None, trace_ticks=[],
        compiles=None, writer=None,
        attempted=0, failed=0, config=config, traffic=traffic,
        peaks=roofline.peaks(dev["kind"]) if require_chip else None,
    )
    counter = CompileCounter()
    counter.install()
    server, _ = await build_server(config)
    try:
        t = time.perf_counter()
        engine, _, leases = load(server, config, seed)
        r.split["load_s"] = time.perf_counter() - t
        await drive_ticks(server, leases, engine, spec, seed, seconds,
                          trace, r, counter, log)
        log(f"window {r.window_s} s: {len(r.ticks)} ticks; settle "
            f"{r.settle_s} s")
        t = time.perf_counter()
        dump = dump_store(server, engine, config)
        checks = check_store(dump, config, leases)
        r.check_s = time.perf_counter() - t
        log(f"check {r.check_s} s over {config['resources']} resources")
    finally:
        await server.stop()
    out = finish(spec, r, checks, trace, dev, log)
    if control:
        log("control: the reference in bfloat16 in the program's place")
        ctl = check_store(control_dump(dump, config), config, leases,
                          prev=dump)
        out["_control"] = finish(spec, r, ctl, trace, dev, log)
    return out


def _pct(values, q):
    """Nearest-rank percentile (q in [0, 100]) of all values."""
    v = np.sort(np.asarray(values, np.float64))
    if not len(v):
        return float("nan")
    return float(v[min(len(v) - 1, max(0, math.ceil(q / 100 * len(v)) - 1))])


def finish(spec, r, checks, trace, dev, log) -> dict:
    config = spec["config"]
    log(f"set-up {r.setup_s} s: " + ", ".join(
        f"{k} {v}" + (" s" if k.endswith("_s") else "")
        for k, v in r.split.items()))
    w = r.writer
    late = np.asarray(w["late"]) * 1e3
    gc_s, gc_n, gc2 = w["gc"]
    log(f"writer: {w['refreshes']} refreshes over {w['ticks']} ticks "
        f"({w['refreshes'] / r.window_s} /s), refused {w['refused']}, "
        f"{w['write_s']} s writing; lateness over {len(late)} slots p50 "
        f"{_pct(late, 50)} ms, p99 {_pct(late, 99)} ms, max "
        f"{float(np.max(late, initial=0))} ms; batch per tick first third "
        f"{w['batch_first']}, last third {w['batch_last']}; gc {gc_s} s in "
        f"{gc_n} pauses ({gc2} of generation 2); programs new in the "
        f"window {w['new_programs']}")
    metrics = {}
    wanted = spec["per_layer"] if trace else spec["end_to_end"]
    for m in wanted:
        value = reader(m["name"])(r)
        if value is None:
            log(f"metric {m['name']}: nothing to read")
            continue
        metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
    limits = config["limits"]
    compared = {k: {"value": checks[k], "limit": limits[k]}
                for k in limits if k in checks}
    correct = all(c["value"] <= c["limit"] for c in compared.values())
    device = dict(dev, memory_peak_bytes=r.memory_peak)
    out = {"correct": correct, "attempted": int(r.attempted),
           "failed": int(r.failed), "metrics": metrics, "device": device}
    if trace and r.trace is not None:
        device["busy_s"] = r.trace["busy_s"]
        device["window_s"] = r.trace["window_s"]
        out["breakdown"] = {"device_ops": r.trace["ops"][:10],
                            "idle_gaps": r.trace["gaps"][:10]}
    out["checks"] = compared
    for k, c in compared.items():
        log(f"check {k} {c['value']!r} limit {c['limit']!r}")
    return out
