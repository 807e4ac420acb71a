"""The trace reduction: busy union, idle gaps named by the innermost host
span, kernel time by name, ops counted once when nested; on hand-made
rows and on one tick recorded on the chip (`data/trace_rows.json.gz`).
The program's spans change the gaps' names and nothing else."""

import gzip
import json
from pathlib import Path

import pytest

import roofline
import tracing

DATA = Path(__file__).resolve().parent / "data" / "trace_rows.json.gz"
DEV = "/device:TPU:0"


def rows():
    return [
        ["/host:CPU", "python", "bench.traced", 0.0, 1000.0],
        ["/host:CPU", "python", "bench.tick", 0.0, 400.0],
        ["/host:CPU", "python", "bench.tick", 500.0, 400.0],
        # a loop with one op nested in it, a kernel, and an op that
        # sticks out of the window
        [DEV, "XLA Ops", "%while.1 = (s32[]) while(...)", 100.0, 100.0],
        [DEV, "XLA Ops", "%fusion.1 = f32[1] fusion(...)", 120.0, 50.0],
        [DEV, "XLA Ops", "%solve_dense_pallas.1 = custom-call()", 200.0,
         50.0],
        [DEV, "XLA Ops", "%fusion.2 = f32[1] fusion(...)", 950.0, 100.0],
        [DEV, "XLA Modules", "jit_tick", 100.0, 900.0],
    ]


def test_reduce_hand_made():
    out = tracing.reduce(rows(), ["solve_dense_pallas"])
    assert out["window_s"] == pytest.approx(1000e-9)
    # union: [100, 250] and [950, 1000] inside the window
    assert out["busy_s"] == pytest.approx(200e-9)
    assert out["kernel_s"] == pytest.approx(50e-9)
    ops = dict(out["ops"])
    assert ops == pytest.approx({"while.1": 100e-9,
                                 "solve_dense_pallas": 50e-9,
                                 "fusion.2": 50e-9})
    # gaps: [250, 950] overlaps the second tick most; [0, 100] the first
    assert out["gaps"][0] == ["bench.tick", pytest.approx(700e-9)]
    assert out["gaps"][1] == ["bench.tick", pytest.approx(100e-9)]


def program_spans():
    """The program's annotations in the two ticks of rows(): the first
    tick's engine work on the executor's thread, the second's flight
    record; and a host event that is no annotation of either."""
    return [
        ["/host:CPU", "python", "server.tick", 5.0, 385.0],
        ["/host:CPU", "python", "tick.offload", 10.0, 290.0],
        ["/host:CPU", "executor", "tick.engine", 20.0, 260.0],
        ["/host:CPU", "python", "server.tick", 510.0, 380.0],
        ["/host:CPU", "python", "tick.partition", 520.0, 100.0],
        ["/host:CPU", "python", "tick.flightrec", 650.0, 240.0],
        ["/host:CPU", "python", "flightrec.digest", 660.0, 200.0],
        ["/host:CPU", "python", "PjitFunction(tick)", 0.0, 1000.0],
    ]


def test_reduce_names_gaps_by_the_innermost_program_span():
    old = tracing.reduce(rows(), ["solve_dense_pallas"])
    new = tracing.reduce(rows() + program_spans(), ["solve_dense_pallas"])
    assert {k: v for k, v in new.items() if k != "gaps"} == \
        {k: v for k, v in old.items() if k != "gaps"}
    assert [g[1] for g in new["gaps"]] == [g[1] for g in old["gaps"]]
    # [250, 950]: the second bench.tick > server.tick > tick.flightrec
    # (240 against the partition's 100) > flightrec.digest; [0, 100]:
    # the first tick's chain down to the engine on the other thread.
    assert [g[0] for g in new["gaps"]] == ["flightrec.digest",
                                           "tick.engine"]


def test_reduce_nothing_to_read():
    assert tracing.reduce([r for r in rows() if r[0] != DEV]) is None
    assert tracing.reduce(
        [r for r in rows() if r[2] != "bench.traced"]) is None


def naive_busy(rs, lo, hi):
    """Busy nanoseconds by walking sorted event edges one by one."""
    edges = sorted({lo, hi} | {min(max(x, lo), hi) for r in rs
                                for x in (r[3], r[3] + r[4])})
    busy = 0.0
    for a, b in zip(edges, edges[1:]):
        mid = (a + b) / 2
        if any(r[3] <= mid < r[3] + r[4] for r in rs):
            busy += b - a
    return busy


def test_recorded_trace():
    rec = json.loads(gzip.open(DATA, "rt").read())
    out = tracing.reduce(rec["rows"], rec["kernels"])
    for key in ("window_s", "busy_s", "kernel_s"):
        assert out[key] == pytest.approx(rec["expected"][key])
    assert out["gaps"] == rec["expected"]["gaps"]
    win = [r for r in rec["rows"] if r[2] == "bench.traced"][0]
    ops = [r for r in rec["rows"] if r[0].startswith("/device:")
           and r[1] == tracing.OPS_LINE]
    assert 0 < out["busy_s"] <= out["window_s"]
    assert out["busy_s"] * 1e9 == pytest.approx(
        naive_busy(ops, win[3], win[3] + win[4]), rel=1e-9)
    assert out["kernel_s"] > 0
    # every op's device time is inside the busy time
    assert sum(v for _, v in out["ops"]) <= out["busy_s"] * (1 + 1e-9)
    least, bound = roofline.least_time(rec["leases_solved"],
                                       roofline.peaks("TPU v5 lite"))
    assert bound == "memory"
    assert 0 < least / out["kernel_s"] <= 1.0


def test_recorded_trace_with_program_spans():
    """Program spans laid into the recorded ticks (a server.tick inside
    each bench.tick, a partition then a flight record inside that): every
    number is the recording's, and the gaps, as long as before, are
    named by the spans."""
    rec = json.loads(gzip.open(DATA, "rt").read())
    extra = []
    for r in rec["rows"]:
        if r[2] == "bench.tick":
            t0, d = r[3] + 0.01 * r[4], 0.98 * r[4]
            extra += [["/host:CPU", "python", "server.tick", t0, d],
                      ["/host:CPU", "python", "tick.partition", t0, 0.3 * d],
                      ["/host:CPU", "python", "tick.flightrec",
                       t0 + 0.4 * d, 0.6 * d]]
    out = tracing.reduce(rec["rows"] + extra, rec["kernels"])
    want = rec["expected"]
    for key in ("window_s", "busy_s", "kernel_s", "devices"):
        assert out[key] == want[key]
    assert out["ops"] == want["ops"]
    assert [g[1] for g in out["gaps"]] == [g[1] for g in want["gaps"]]
    names = {g[0] for g in out["gaps"]}
    assert names <= {"bench.tick", "server.tick", "tick.partition",
                     "tick.flightrec"}
    assert names & {"tick.partition", "tick.flightrec"}


def test_extract_keeps_the_program_annotations(tmp_path):
    """A CPU capture: the program's spans and the benchmark's annotations
    come out as rows, another annotation does not."""
    import jax

    jax.profiler.start_trace(str(tmp_path))
    try:
        with jax.profiler.TraceAnnotation("bench.traced"):
            with jax.profiler.TraceAnnotation("server.tick"):
                with jax.profiler.TraceAnnotation("tick.flightrec"):
                    jax.numpy.ones(4).block_until_ready()
            with jax.profiler.TraceAnnotation("elsewhere.work"):
                pass
    finally:
        jax.profiler.stop_trace()
    names = {r[2] for r in tracing.extract(str(tmp_path))
             if not r[0].startswith(tracing.DEVICE_PREFIX)}
    assert {"bench.traced", "server.tick", "tick.flightrec"} <= names
    assert "elsewhere.work" not in names
