"""Flight recorder: ring semantics, dumps (JSON + Chrome overlay),
server integration (per-tick records, tick-exception auto-dump,
/debug/slo and /debug/flightrec endpoints), and determinism — a forced
chaos invariant violation dumps the last N ticks byte-stably across
two runs of the same seeded plan."""

import asyncio
import json
import os
import urllib.request

import pytest

import tests.conftest  # noqa: F401

from doorman_tpu import native
from doorman_tpu.chaos.plan import FaultEvent, FaultPlan
from doorman_tpu.chaos.runner import ChaosRunner
from doorman_tpu.obs.debug import DebugServer
from doorman_tpu.obs.flightrec import FlightRecorder, store_digest
from doorman_tpu.obs.trace import default_tracer
from doorman_tpu.server.config import parse_yaml_config
from doorman_tpu.server.election import TrivialElection
from doorman_tpu.server.server import CapacityServer

CONFIG = """
resources:
- identifier_glob: "*"
  capacity: 100
  algorithm: {kind: PROPORTIONAL_SHARE, lease_length: 60,
              refresh_interval: 1, learning_mode_duration: 0}
"""


def fetch(port, path):
    with urllib.request.urlopen(
        f"http://127.0.0.1:{port}{path}", timeout=5
    ) as resp:
        return resp.status, resp.read().decode()


# ----------------------------------------------------------------------
# Ring semantics
# ----------------------------------------------------------------------


def test_ring_bounds_and_sequence():
    fr = FlightRecorder(4, component="t", dump_dir="")
    assert fr.occupancy == 0 and fr.head_seq == 0
    for i in range(10):
        fr.record(t=float(i), tick=i)
    assert fr.head_seq == 10
    assert fr.occupancy == 4
    assert [r["seq"] for r in fr.snapshot()] == [7, 8, 9, 10]
    st = fr.status()
    assert st["head_seq"] == 10 and st["capacity"] == 4
    assert st["last_dump"] is None


def test_view_is_side_effect_free_and_dump_writes_files(tmp_path):
    fr = FlightRecorder(
        8, component="t", clock=lambda: 123.0, dump_dir=str(tmp_path)
    )
    for i in range(3):
        fr.record(t=float(i), tick=i, wall_ms=2.0,
                  phases={"solve": 1.5, "apply": 0.5})
    view = fr.view("peek")
    assert len(view["records"]) == 3
    assert fr.last_dump is None and not list(tmp_path.iterdir())

    dump = fr.dump("tick_exception")
    assert dump["reason"] == "tick_exception"
    assert [r["tick"] for r in dump["records"]] == [0, 1, 2]
    assert fr.last_dump["reason"] == "tick_exception"
    names = sorted(p.name for p in tmp_path.iterdir())
    assert len(names) == 2
    assert names[0].endswith(".json") and names[1].endswith(".trace.json")
    # Both artifacts parse; the overlay carries the tick events.
    on_disk = json.loads((tmp_path / names[0]).read_text())
    assert on_disk["records"] == dump["records"]
    overlay = json.loads((tmp_path / names[1]).read_text())
    ticks = [e for e in overlay["traceEvents"]
             if e.get("name") == "tick" and e.get("ph") == "X"]
    assert len(ticks) == 3


def test_chrome_overlay_counters_and_instants():
    fr = FlightRecorder(8, component="t", dump_dir="")
    fr.record(t=0.0, tick=0, wall_ms=3.0, phases={"solve": 3.0},
              admission_level=0.5, shed_by_band={"0": 7})
    fr.record(t=1.0, tick=1, error="RuntimeError: boom")
    overlay = json.loads(fr.chrome_overlay())
    names = [e["name"] for e in overlay["traceEvents"]]
    assert "admission_level" in names and "shed_by_band" in names
    assert "solve" in names and "error" in names


def test_store_digest_tracks_grant_mass():
    class Store:
        def __init__(self, has):
            self.sum_has, self.sum_wants = has, 10.0

        def __len__(self):
            return 1

    class Res:
        def __init__(self, has):
            self.capacity, self.store = 100.0, Store(has)

    a = store_digest({"r0": Res(5.0)})
    assert a == store_digest({"r0": Res(5.0)})  # stable
    assert a != store_digest({"r0": Res(6.0)})  # moves with grants
    assert len(a) == 16


class AggStore:
    """A store double holding only the three aggregates the digest reads."""

    def __init__(self, has, wants, leases):
        self.sum_has, self.sum_wants, self.leases = has, wants, leases

    def __len__(self):
        return self.leases


class AggRes:
    def __init__(self, capacity, has, wants, leases):
        self.capacity = capacity
        self.store = AggStore(has, wants, leases)


def _agg_state():
    return {
        "r0": AggRes(100.0, 5.0, 10.0, 2),
        "r1": AggRes(40.0, 1.5, 3.25, 1),
    }


def _lease_resources(store_factory, clock):
    """Three resources holding the same leases whatever the store."""
    from doorman_tpu.core.resource import Resource
    from doorman_tpu.proto import doorman_pb2 as pb

    out = {}
    for r in range(3):
        t = pb.ResourceTemplate()
        t.identifier_glob = f"res{r}"
        t.capacity = 100.0 + 7.5 * r
        t.algorithm.kind = pb.Algorithm.PROPORTIONAL_SHARE
        t.algorithm.lease_length = 60
        t.algorithm.refresh_interval = 5
        res = Resource(f"res{r}", t, clock=clock, store_factory=store_factory)
        for c in range(r + 2):
            res.store.assign(f"c{c}", 60.0, 5.0, 0.1 * (c + r), 1.3 * c,
                             c + 1)
        res.store.release("c0")
        out[res.id] = res
    return out


needs_native = pytest.mark.skipif(
    not native.native_available(), reason="native store build unavailable"
)


def _digest_spans(tracer):
    return [e.args for e in tracer.snapshot() if e.name == "flightrec.digest"]


@pytest.fixture
def tracer():
    """The process-global tracer enabled and cleared; left disabled and
    empty for the other tests."""
    tr = default_tracer()
    tr.clear()
    tr.enable()
    yield tr
    tr.disable()
    tr.clear()


@needs_native
def test_store_digest_equal_for_native_and_python_stores():
    clock = lambda: 1000.0  # noqa: E731
    engine = native.StoreEngine(clock=clock)
    cc = _lease_resources(engine.store, clock)
    py = _lease_resources(None, clock)
    assert type(cc["res0"].store).__name__ == "NativeLeaseStore"
    assert type(py["res0"].store).__name__ == "LeaseStore"
    assert store_digest(cc) == store_digest(py)


@needs_native
def test_store_digest_is_stable_across_calls():
    clock = lambda: 1000.0  # noqa: E731
    engine = native.StoreEngine(clock=clock)
    cc = _lease_resources(engine.store, clock)
    first = store_digest(cc)
    assert all(store_digest(cc) == first for _ in range(3))
    assert store_digest(_agg_state()) == store_digest(_agg_state())


@pytest.mark.parametrize("change", [
    "capacity", "sum_has", "sum_wants", "leases", "add", "remove",
])
def test_store_digest_moves_with_each_aggregate(change):
    state = _agg_state()
    before = store_digest(state)
    if change == "capacity":
        state["r1"].capacity += 1e-3
    elif change == "add":
        state["r2"] = AggRes(0.0, 0.0, 0.0, 0)
    elif change == "remove":
        del state["r1"]
    else:
        store = state["r1"].store
        setattr(store, change, getattr(store, change) + 1)
    assert store_digest(state) != before


def test_store_digest_rounds_to_six_decimals():
    state = _agg_state()
    before = store_digest(state)
    state["r0"].store.sum_has += 1e-9
    state["r1"].capacity -= 1e-9
    assert store_digest(state) == before


@needs_native
def test_store_digest_native_path_is_one_engine_call(monkeypatch, tracer):
    clock = lambda: 1000.0  # noqa: E731
    engine = native.StoreEngine(clock=clock)
    cc = _lease_resources(engine.store, clock)
    py = _lease_resources(None, clock)
    expected = store_digest(py)
    assert _digest_spans(tracer) == [
        {"path": "per_store", "resources": 3}
    ]

    def per_store_read(self):
        raise AssertionError("per-store aggregate read on the bulk path")

    monkeypatch.setattr(native.NativeLeaseStore, "_sums", per_store_read)
    assert store_digest(cc) == expected
    assert _digest_spans(tracer)[-1] == {"path": "bulk", "resources": 3}
    # A store from another engine makes the set mixed: per-store reads.
    cc["res9"] = _lease_resources(native.StoreEngine(clock=clock).store,
                                  clock)["res0"]
    with pytest.raises(AssertionError, match="per-store"):
        store_digest(cc)


# ----------------------------------------------------------------------
# Server integration
# ----------------------------------------------------------------------


def test_server_records_ticks_and_serves_debug_pages(tmp_path):
    async def body():
        server = CapacityServer(
            "fr-server", TrivialElection(), mode="batch",
            tick_interval=3600.0,  # ticks driven manually below
            minimum_refresh_interval=0.0,
            flightrec_capacity=16, flightrec_dir=str(tmp_path),
        )
        await server.start(0, host="127.0.0.1")
        await server.load_config(parse_yaml_config(CONFIG))
        await asyncio.sleep(0)
        from doorman_tpu.algorithms import Request

        server._decide("r0", Request("c1", 0.0, 30.0, 1))
        await server.tick_once()
        await server.tick_once()

        recs = server.flightrec.snapshot()
        assert len(recs) == 2
        assert recs[-1]["tick"] == 2
        assert recs[-1]["wall_ms"] >= 0.0
        assert recs[-1]["resources"] == 1
        assert len(recs[-1]["digest"]) == 16
        assert recs[-1]["epoch"] >= 1  # TrivialElection's one flip

        st = server.status()
        assert st["flightrec"]["head_seq"] == 2
        assert st["flightrec"]["occupancy"] == 2
        assert st["slo"] is None  # not evaluated yet

        verdicts = {v["slo"]: v for v in server.evaluate_slos()}
        assert verdicts["tick_budget_p50_ms"]["status"] in (
            "pass", "fail"  # measured either way — never no_data
        )
        assert verdicts["top_band_goodput"]["status"] == "no_data"
        assert server.status()["slo"]["verdicts"]

        debug = DebugServer(host="127.0.0.1")
        debug.add_server(server, asyncio.get_running_loop())
        dport = debug.start()
        loop = asyncio.get_running_loop()

        status, page = await loop.run_in_executor(
            None, fetch, dport, "/debug"
        )
        assert "/debug/slo" in page and "/debug/flightrec" in page

        status, body_ = await loop.run_in_executor(
            None, fetch, dport, "/debug/slo?format=json"
        )
        assert status == 200
        slo_json = json.loads(body_)["fr-server"]
        assert {v["slo"] for v in slo_json["verdicts"]} >= {
            "tick_budget_p50_ms", "get_capacity_p99_ms"
        }

        status, body_ = await loop.run_in_executor(
            None, fetch, dport, "/debug/flightrec?format=json"
        )
        assert status == 200
        dump = json.loads(body_)["fr-server"]
        assert [r["tick"] for r in dump["records"]] == [1, 2]

        status, body_ = await loop.run_in_executor(
            None, fetch, dport, "/debug/flightrec?format=chrome"
        )
        assert status == 200
        assert json.loads(body_)["traceEvents"]

        for path in ("/debug/slo", "/debug/flightrec", "/debug/status"):
            status, page = await loop.run_in_executor(
                None, fetch, dport, path
            )
            assert status == 200, path
        # The status overview carries the satellite surfaces.
        _, page = await loop.run_in_executor(
            None, fetch, dport, "/debug/status"
        )
        assert "flight recorder: head seq" in page
        assert "last SLO verdict" in page

        debug.stop()
        await server.stop()

    asyncio.run(body())


def test_tick_exception_auto_dumps(tmp_path):
    async def body():
        server = CapacityServer(
            "fr-crash", TrivialElection(), mode="batch",
            tick_interval=3600.0, minimum_refresh_interval=0.0,
            flightrec_capacity=8, flightrec_dir=str(tmp_path),
        )
        await server.start(0, host="127.0.0.1")
        await server.load_config(parse_yaml_config(CONFIG))
        await asyncio.sleep(0)
        from doorman_tpu.algorithms import Request

        server._decide("r0", Request("c1", 0.0, 30.0, 1))
        await server.tick_once()  # one healthy record

        async def boom():
            raise RuntimeError("device lost")

        server._tick_once_locked = boom
        try:
            await server.tick_once()
            raise AssertionError("tick_once must re-raise")
        except RuntimeError:
            pass

        assert server.flightrec.last_dump["reason"] == "tick_exception"
        recs = server.flightrec.snapshot()
        assert "RuntimeError: device lost" in recs[-1]["error"]
        dumped = [
            p for p in os.listdir(tmp_path)
            if "tick_exception" in p and p.endswith(".json")
            and not p.endswith(".trace.json")
        ]
        assert len(dumped) == 1
        on_disk = json.loads((tmp_path / dumped[0]).read_text())
        # The dump replays the healthy tick AND the failing one.
        assert len(on_disk["records"]) == 2
        await server.stop()

    asyncio.run(body())


def test_flightrec_disabled_is_clean():
    async def body():
        server = CapacityServer(
            "fr-off", TrivialElection(), mode="batch",
            tick_interval=3600.0, minimum_refresh_interval=0.0,
            flightrec_capacity=0,
        )
        await server.start(0, host="127.0.0.1")
        await server.load_config(parse_yaml_config(CONFIG))
        await asyncio.sleep(0)
        from doorman_tpu.algorithms import Request

        server._decide("r0", Request("c1", 0.0, 30.0, 1))
        await server.tick_once()
        assert server.flightrec is None
        assert server.status()["flightrec"] is None
        # SLO evaluation still works; the tick stream is just absent.
        verdicts = {v["slo"]: v for v in server.evaluate_slos()}
        assert verdicts["tick_budget_p50_ms"]["status"] == "no_data"
        await server.stop()

    asyncio.run(body())


# ----------------------------------------------------------------------
# Chaos determinism: the black box is a replay artifact
# ----------------------------------------------------------------------


def _noheal_plan():
    """A fault window that outlives the run: no reconvergence is
    possible, so the end-of-run reconvergence violation fires — the
    deterministic way to force an invariant violation."""
    return FaultPlan(
        name="forced_noheal", seed=9,
        setup={"servers": 1, "clients": 2, "wants": [10.0, 20.0],
               "capacity": 50, "mode": "immediate", "lease_length": 60,
               "refresh_interval": 1, "learning_mode_duration": 0,
               "election_ttl": 3.0},
        events=[FaultEvent(at_tick=4, kind="kv_drop", target="s0",
                           duration_ticks=40)],
        warmup_ticks=4, total_ticks=12, reconverge_ticks=2,
    )


def test_forced_violation_dumps_byte_stably(monkeypatch):
    # The dump must not depend on the environment's dump directory.
    monkeypatch.delenv("DOORMAN_FLIGHTREC_DIR", raising=False)
    v1 = asyncio.run(ChaosRunner(_noheal_plan()).run())
    v2 = asyncio.run(ChaosRunner(_noheal_plan()).run())
    assert not v1["ok"]
    dump = v1["flightrec_dump"]
    assert dump is not None
    assert dump["reason"] == "invariant:reconvergence"
    # The dump replays every tick of the run plus the end-of-run entry.
    plan = _noheal_plan()
    assert [r["tick"] for r in dump["records"]] == list(
        range(plan.total_ticks + 1)
    )
    assert dump["records"][-1]["violations"][0][1] == "reconvergence"
    # Per-tick records carry the black-box fields.
    rec = dump["records"][0]
    assert rec["masters"] == ["s0"]
    assert "digests" in rec and "s0" in rec["digests"]
    # Byte-stable across two runs of the same seeded plan.
    assert json.dumps(dump, sort_keys=True) == json.dumps(
        v2["flightrec_dump"], sort_keys=True
    )
    # The SLO block reports the blown budget as a hard fail.
    slo_v = {x["slo"]: x for x in v1["slo"]["verdicts"]}
    assert slo_v["forced_noheal:reconverge_ticks"]["status"] == "fail"
    assert not v1["slo"]["ok"]


def test_clean_run_has_no_dump_and_passing_slo(monkeypatch):
    monkeypatch.delenv("DOORMAN_FLIGHTREC_DIR", raising=False)
    from doorman_tpu.chaos.plans import get_plan

    v = asyncio.run(ChaosRunner(get_plan("master_flap")).run())
    assert v["ok"]
    assert v["flightrec_dump"] is None
    slo_v = {x["slo"]: x for x in v["slo"]["verdicts"]}
    assert slo_v["master_flap:reconverge_ticks"]["status"] == "pass"
    assert v["slo"]["ok"]
