"""The harness reads every resident tick engine the server runs.

A lane resource with more than `DENSE_MAX_K` clients ticks through the
chunked engine (`solver/resident_wide.py`), the others through the
dense one. Each tick record carries every engine's lap deltas and scope
under `engines`, and `phases` sums the laps, so the lap readers and the
span readers that subtract laps read the same whichever engine ran.
"""

import asyncio
import time
from types import SimpleNamespace

import pytest

import harness
import spans
from test_faults import small_spec, unchanged

SEED = 2**31 + 29


def wide_spec():
    """4 resources x 5,000 clients, every one over the dense bucket cap,
    so the chunked engine alone ticks them; the shared lanes overloaded
    (capacity half of the ~250,000 wanted), changed wants on resources
    drawn uniformly."""
    spec = small_spec("hot_set")
    c, t = spec["config"], spec["traffic"]
    c["resources"], c["clients_per_resource"] = 4, 5000
    for lane in c["lanes"]:
        if lane["kind"] in ("PROPORTIONAL_SHARE", "FAIR_SHARE"):
            lane["capacity"] = 125000
    t["pick"] = "uniform"
    return spec


def run(spec, trace, monkeypatch):
    """One run through run_cell; returns (result line, the run's record
    `r`, the mean `tick.engine` span in ms, read while the ring holds the
    window)."""
    seen = {}
    finish = harness.finish

    def keep(spec, r, checks, trace, dev, log):
        seen["r"] = r
        seen["engine_ms"] = spans.mean_ms(
            r, lambda t, s, c: s.get("tick.engine", 0.0))
        return finish(spec, r, checks, trace, dev, log)

    monkeypatch.setattr(harness, "finish", keep)
    out = asyncio.run(harness.run_cell(
        spec, SEED, 2.0, trace, t_process=time.perf_counter(),
        require_chip=False))
    return out, seen["r"], seen["engine_ms"]


@pytest.mark.parametrize("trace", [False, True], ids=["untraced", "traced"])
def test_wide_cell_reads_the_chunked_engine(trace, monkeypatch):
    from doorman_tpu.solver.batch import DENSE_MAX_K

    spec = wide_spec()
    assert spec["config"]["clients_per_resource"] > DENSE_MAX_K
    out, r, engine_ms = run(spec, trace, monkeypatch)
    assert out["correct"] is True, out["checks"]
    assert r.ticks
    for t in r.ticks:
        assert set(t["engines"]) == {"resident_wide"}
        assert t["phases"]
        assert t["phases"] == t["engines"]["resident_wide"]["phases"]
        assert t["scope_rows"] == 0  # dense rows: none here
    assert any(t["engines"]["resident_wide"]["scope"]["rows"] > 0
               for t in r.ticks)
    assert harness.reader("engine_host_ms.tick")(r) > 0
    if trace:
        got = out["metrics"]
        assert got["engine_host_ms.tick"]["value"] > 0
        assert engine_ms is not None
        assert got["engine_unlapped_ms.tick"]["value"] < engine_ms


def test_dense_cell_reads_the_dense_engine(monkeypatch):
    out, r, _ = run(small_spec("hot_set"), False, monkeypatch)
    assert out["correct"] is True, out["checks"]
    assert r.ticks
    for t in r.ticks:
        assert set(t["engines"]) == {"resident"}
        assert t["phases"] == t["engines"]["resident"]["phases"]
        assert t["scope_rows"] == t["engines"]["resident"]["scope"]["rows"]


def test_wide_fault_reads_incorrect(monkeypatch):
    """The chunked engine's write-back returns the state unchanged."""
    from doorman_tpu.solver.resident_wide import WideResidentSolver

    monkeypatch.setattr(WideResidentSolver, "_apply_grants", unchanged)
    out, _, _ = run(wide_spec(), False, monkeypatch)
    assert out["correct"] is False, out["checks"]


# -- the record on hand-built engines -------------------------------------


def engine(component, laps, rows, keys, kernels=()):
    return SimpleNamespace(
        component=component, phase_s=dict(laps),
        last_scope={"rows": rows, "resources": rows},
        _tick_fns={k: None for k in keys}, traced_kernels=set(kernels))


def test_dense_only_record_is_the_dense_engines():
    """With the dense engine alone, the record, the program keys and the
    kernels are what reading that engine alone gives."""
    dense = engine("resident", {"fused": 1.0, "apply": 2.0}, 7,
                   [("fused", 64, frozenset({1})), ("scoped", 8)],
                   ["solve_dense_pallas"])
    server = SimpleNamespace(_resident=dense, _resident_wide=None)
    before = harness.lap_totals(server)
    dense.phase_s.update(fused=1.5, apply=2.25)
    rec = harness.tick_record(server, before, 0.0, 0.1)
    assert rec["phases"] == {"fused": 0.5, "apply": 0.25}
    assert rec["scope_rows"] == 7
    assert harness.program_keys(server) == {
        ("resident", "fused", 64), ("resident", "scoped", 8)}
    assert [name for name, _ in harness.engines(server)] == ["resident"]
    assert harness.traced_kernels(server) == ["solve_dense_pallas"]


def test_both_engines_record_sums_laps_and_tags_keys():
    dense = engine("resident", {"fused": 1.0, "apply": 2.0}, 7, [(8, 16)],
                   ["solve_dense_pallas"])
    server = SimpleNamespace(_resident=dense, _resident_wide=None)
    before = harness.lap_totals(server)
    # The chunked engine appears inside the tick: its laps count from 0.
    wide = engine("resident_wide", {"fused": 0.25, "staging": 0.5}, 3,
                  [(8, 16)], ["solve_dense_pallas", "water_fill"])
    server._resident_wide = wide
    dense.phase_s.update(fused=1.5)
    rec = harness.tick_record(server, before, 0.0, 0.1)
    assert rec["phases"] == {"fused": 0.75, "apply": 0.0, "staging": 0.5}
    assert rec["engines"]["resident_wide"]["phases"] == wide.phase_s
    assert rec["engines"]["resident_wide"]["scope"]["rows"] == 3
    assert rec["scope_rows"] == 7
    assert harness.program_keys(server) == {("resident", 8, 16),
                                            ("resident_wide", 8, 16)}
    assert harness.traced_kernels(server) == ["solve_dense_pallas",
                                              "water_fill"]
    server._resident = server._resident_wide = None
    assert harness.traced_kernels(server) == []
