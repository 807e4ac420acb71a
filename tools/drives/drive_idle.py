"""Idle fast path at 1M on the chip: after two quiet rotations, ticks
must cost no device work (microseconds, idle_ticks climbing)."""
import asyncio
import time

import numpy as np

from _common import pin_platform_in_process, NUM_RES, PER_RES

async def main():
    from doorman_tpu import native
    from doorman_tpu.core.resource import Resource
    from doorman_tpu.proto import doorman_pb2 as pb
    from doorman_tpu.solver.resident import ResidentDenseSolver

    engine = native.StoreEngine()
    rng = np.random.default_rng(1)
    resources = []
    rids = np.empty(NUM_RES * PER_RES, np.int32)
    for r in range(NUM_RES):
        tpl = pb.ResourceTemplate(
            identifier_glob=f"res{r}", capacity=50000.0,
            algorithm=pb.Algorithm(
                kind=pb.Algorithm.PROPORTIONAL_SHARE,
                lease_length=600, refresh_interval=16),
        )
        res = Resource(f"res{r}", tpl, store_factory=engine.store)
        resources.append(res)
        rids[r*PER_RES:(r+1)*PER_RES] = res.store._rid
    cids = np.array([engine.client_handle(f"c{i}")
                     for i in range(NUM_RES*PER_RES)], np.int64)
    n = NUM_RES * PER_RES
    engine.bulk_assign(rids, cids, np.full(n, time.time()+600.0),
                       np.full(n, 16.0), np.zeros(n),
                       rng.integers(1,100,n).astype(np.float64),
                       np.ones(n, np.int32))
    solver = ResidentDenseSolver(engine, dtype=np.float32,
                                 rotate_ticks=4, tick_interval=1.0)
    # 2 rotations + margin of quiet ticks, then the idle path engages.
    for t in range(14):
        t0 = time.perf_counter()
        solver.step(resources)
        ms = (time.perf_counter() - t0) * 1000
        print(f"tick {t:2d}: {ms:8.1f} ms idle={solver.idle_ticks}",
              flush=True)
    assert solver.idle_ticks >= 2, solver.idle_ticks
    # Idle ticks must be ~free.
    t0 = time.perf_counter()
    solver.step(resources)
    idle_ms = (time.perf_counter() - t0) * 1000
    print(f"idle tick: {idle_ms:.3f} ms")
    assert idle_ms < 5.0, idle_ms
    # Any write resumes real ticks.
    engine.bulk_refresh(rids[:100], cids[:100],
                        np.full(100, time.time()+600.0),
                        np.full(100, 16.0), np.full(100, 55.0))
    before = solver.idle_ticks
    solver.step(resources)
    assert solver.idle_ticks == before, "write did not resume real ticks"
    print("IDLE 1M OK")

pin_platform_in_process()
asyncio.run(main())
