"""The wide deployment's cell, `wide_1m.uniform`, shrunk for the CPU.

Every resource is wider than the dense engine's bucket cap, so the
chunked engine (`solver/resident_wide.py`) ticks them all. The run goes
through `run_cell` as on the chip, with the ceiling on the engine's
scatter blocks (`WIDE_SCATTER_MAX`) set to the test's size, so the
warm-up's bursts take the whole-table path; the readers of the engine's
scope counters read the window's ticks.
"""

import asyncio
import time

import pytest

import harness
from test_faults import unchanged

SEED = 2**31 + 41
CELL = "wide_1m.uniform"
WHOLE_SEQS = set()  # dispatch sequence numbers of whole-table ticks
READERS = ("wide_scope_rows.tick", "wide_staged_slots.tick",
           "wide_padded_slots.tick", "wide_tick_roofline")
SPANS = ("tick_partition_ms.tick", "tick_offload_ms.tick",
         "tick_flightrec_ms.tick", "tick_gc_ms.tick",
         "engine_unlapped_ms.tick", "tick_unattributed_ms.tick")


def uniform_spec():
    """4 resources x 5,000 clients (as test_engines.wide_spec), the
    shared lanes at 60% of the ~250,000 wanted, the cell's own traffic
    at its clients' cadence (20,000 every 16 s) and a short warm-up."""
    spec = harness.load_cell(CELL)
    c, t = spec["config"], spec["traffic"]
    c["resources"], c["clients_per_resource"] = 4, 5000
    for lane in c["lanes"]:
        if lane["kind"] in ("PROPORTIONAL_SHARE", "FAIR_SHARE"):
            lane["capacity"] = 150000
    # Quiet for a whole cycle of paces: the largest burst comes after
    # the last compile.
    t.update(rate_per_s=20000 / 16, warm_min_s=1, warm_open_s=1,
             warm_quiet_ticks=len(t["warm_paces"]) + 1, warm_max_s=60)
    return spec


def run(spec, trace, monkeypatch, ceiling):
    """One run through run_cell with the scatter ceiling at `ceiling`;
    returns (result line, the run's record, whole-table stagings)."""
    from doorman_tpu.solver import resident_wide

    monkeypatch.setattr(resident_wide, "WIDE_SCATTER_MAX", ceiling)
    seen = {"whole": 0}
    WHOLE_SEQS.clear()
    stage = resident_wide.WideResidentSolver._stage_whole

    def count(self, *blocks):
        seen["whole"] += 1
        WHOLE_SEQS.add(self._seq)  # the sequence number of this tick
        return stage(self, *blocks)

    monkeypatch.setattr(resident_wide.WideResidentSolver, "_stage_whole",
                        count)
    finish = harness.finish

    def keep(spec, r, checks, trace, dev, log):
        seen["r"] = r
        return finish(spec, r, checks, trace, dev, log)

    monkeypatch.setattr(harness, "finish", keep)
    out = asyncio.run(harness.run_cell(
        spec, SEED, 2.0, trace, t_process=time.perf_counter(),
        require_chip=False))
    return out, seen["r"], seen["whole"]


def test_cell_is_declared_with_its_readers():
    spec = harness.load_cell(CELL)
    assert spec["config"]["clients_per_resource"] == 100000
    assert spec["config"]["resources"] == 10
    names = {m["name"] for m in spec["per_layer"]}
    assert set(READERS) <= names
    assert set(SPANS) <= names
    assert "scope_rows.tick" not in names
    assert "solve_dense_pallas_roofline" not in names
    assert {m["name"] for m in spec["end_to_end"]} == {
        "tick_ms", "tick_p90_ms", "setup_s"}


@pytest.mark.parametrize("trace", [False, True], ids=["untraced", "traced"])
def test_wide_cell_correct_and_counters_read(trace, monkeypatch):
    from doorman_tpu.solver.batch import DENSE_MAX_K

    spec = uniform_spec()
    assert spec["config"]["clients_per_resource"] > DENSE_MAX_K
    out, r, whole = run(spec, trace, monkeypatch, 256)
    assert out["correct"] is True, out["checks"]
    assert whole > 0  # the warm-up's bursts staged the whole table
    rows = harness.reader("wide_scope_rows.tick")(r)
    staged = harness.reader("wide_staged_slots.tick")(r)
    padded = harness.reader("wide_padded_slots.tick")(r)
    assert rows > 0 and staged > 0
    assert padded >= staged
    roof = harness.reader("wide_tick_roofline")(r)
    assert roof is None or roof >= 0
    if trace:
        got = out["metrics"]
        for name in READERS[:3]:
            assert got[name]["value"] > 0, name
        # The tracer's ring holds every window tick's spans.
        for name in SPANS:
            assert got[name]["value"] >= 0, name
        assert got["tick_offload_ms.tick"]["value"] > 0
        assert "scope_rows.tick" not in got


def test_every_tick_whole_reads_correct(monkeypatch):
    """A ceiling under zero stages the whole table on every tick, the
    settle ticks included: the check reads those ticks alone."""
    out, _, whole = run(uniform_spec(), False, monkeypatch, -1)
    assert whole > 0
    assert out["correct"] is True, out["checks"]


def test_whole_table_fault_reads_incorrect(monkeypatch):
    """The write-back of whole-table ticks is left out (as
    test_faults.unchanged does for every tick), and every tick is one;
    `run` records each whole-table tick's sequence number."""
    from doorman_tpu.solver.resident_wide import WideResidentSolver

    apply = WideResidentSolver._apply_grants

    def skip_whole(self, handle, gets):
        if handle.seq in WHOLE_SEQS:
            return unchanged(self, handle, gets)
        return apply(self, handle, gets)

    monkeypatch.setattr(WideResidentSolver, "_apply_grants", skip_whole)
    out, _, whole = run(uniform_spec(), False, monkeypatch, -1)
    assert whole > 0
    assert out["correct"] is False, out["checks"]


def test_bfloat16_control_reads_incorrect():
    """The reference in bfloat16 in the program's place fails the cell's
    limit, while the program passes it."""
    out = asyncio.run(harness.run_cell(
        uniform_spec(), SEED, 2.0, False, t_process=time.perf_counter(),
        require_chip=False, control=True))
    assert out["correct"] is True, out["checks"]
    assert out["_control"]["correct"] is False, out["_control"]["checks"]
