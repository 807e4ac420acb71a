"""Three-level tree live — the reference's flagship topology
(/root/reference/simulation/scenario_five.py, doc/design.md hierarchy):
root <- region <- leaf, all batch+native over real gRPC, with PRIORITY
BANDS flowing through both hops.

12 leaf clients (4 at priority 9 wanting 40 each, 8 at priority 1
wanting 40 each; total 480 > root capacity 400) must converge to the
banded allocation — high band fully served (~40 each), low band sharing
the remainder (~30 each) — and HOLD it. Asserts:

  * convergence within the refresh-decay-predicted bound (each hop adds
    at most ~one refresh interval + tick of lag; the bound below is a
    generous multiple of that sum, so a tree that only converges by
    accident-of-timeout fails);
  * capacity conservation at EVERY hop, from each server's own
    /debug/vars: leaf outgrants <= leaf's lease from region <= region's
    lease from root <= root capacity;
  * band structure survives two aggregation hops (high clients ~40,
    low clients share what remains).
"""

import asyncio
import json
import os
import sys
import time
import urllib.request

from _common import ensure_ports_free, platform_args, spawn, stop, tail, write_config


ROOT_CAP = 400.0
N_HI, N_LO, WANTS = 4, 8, 40.0

cfg = write_config(f"""
resources:
  - identifier_glob: "shared"
    capacity: {ROOT_CAP}
    algorithm:
      kind: PRIORITY_BANDS
      lease_length: 30
      refresh_interval: 2
      learning_mode_duration: 0
  - identifier_glob: "*"
    capacity: 50
    algorithm:
      kind: PROPORTIONAL_SHARE
      lease_length: 30
      refresh_interval: 2
      learning_mode_duration: 0
""")

ROOT, REGION, LEAF = 15720, 15721, 15722
DBG_ROOT, DBG_REGION, DBG_LEAF = 15770, 15771, 15772
ensure_ports_free(ROOT, REGION, LEAF, DBG_ROOT, DBG_REGION, DBG_LEAF)

# Refresh-decay convergence bound: propagation lag is at most ~one
# refresh + one tick per hop each way, so steady state must arrive
# within a few multiples of sum(refresh_i + tick_i) over the 3 levels
# (2s root refresh + 1s minimum at each lower hop + 3 x 0.4s ticks ~=
# 5.2s; x10 margin for process startup and election).
CONVERGE_BOUND_S = 60.0


def server(port, dbg, parent=None, config=None):
    args = [sys.executable, "-m", "doorman_tpu.cmd.server",
            "--port", str(port), "--debug-port", str(dbg),
            "--mode", "batch", "--native-store", "--tick-interval", "0.4",
            "--server-id", f"127.0.0.1:{port}"]
    if parent:
        args += ["--parent", f"127.0.0.1:{parent}",
                 "--minimum-refresh-interval", "1.0"]
    if config:
        args += ["--config", f"file:{config}"]
    return spawn(args + platform_args(), name=f"tree3-{port}")


root = server(ROOT, DBG_ROOT, config=cfg)
region = server(REGION, DBG_REGION, parent=ROOT)
leaf = server(LEAF, DBG_LEAF, parent=REGION)


def shared_vars(dbg_port):
    with urllib.request.urlopen(
        f"http://127.0.0.1:{dbg_port}/debug/vars", timeout=5
    ) as r:
        doc = json.load(r)
    for st in doc["servers"]:
        res = st["resources"].get("shared")
        if res is not None:
            return res
    return None


async def main():
    from doorman_tpu.client import Client

    await asyncio.sleep(10)  # servers up, parent exchanges flowing
    for proc, name in ((root, "root"), (region, "region"), (leaf, "leaf")):
        assert proc.poll() is None, f"{name} died:\n{tail(proc)}"

    clients, hi, lo = [], [], []
    t_start = time.time()
    try:
        for i in range(N_HI):
            c = await Client.connect(
                f"127.0.0.1:{LEAF}", client_id=f"hi{i}",
                minimum_refresh_interval=1.0,
            )
            clients.append(c)
            hi.append(await c.resource("shared", wants=WANTS, priority=9))
        for i in range(N_LO):
            c = await Client.connect(
                f"127.0.0.1:{LEAF}", client_id=f"lo{i}",
                minimum_refresh_interval=1.0,
            )
            clients.append(c)
            lo.append(await c.resource("shared", wants=WANTS, priority=1))

        # Expected banded allocation: high fully served, low shares the
        # remainder of the root capacity.
        lo_share = (ROOT_CAP - N_HI * WANTS) / N_LO  # 30 each
        deadline = time.time() + CONVERGE_BOUND_S
        stable, converged_at = 0, None
        while time.time() < deadline:
            await asyncio.sleep(2)
            for proc, name in ((root, "root"), (region, "region"),
                               (leaf, "leaf")):
                assert proc.poll() is None, f"{name} died:\n{tail(proc)}"
            hi_tot = sum(r.current_capacity() for r in hi)
            lo_tot = sum(r.current_capacity() for r in lo)
            ok = (
                abs(hi_tot - N_HI * WANTS) <= 0.05 * N_HI * WANTS
                and abs(lo_tot - N_LO * lo_share) <= 0.10 * N_LO * lo_share
            )
            stable = stable + 1 if ok else 0
            if stable >= 2:
                converged_at = time.time() - t_start
                break
        assert converged_at is not None, (
            f"no banded convergence within {CONVERGE_BOUND_S}s: "
            f"hi={[r.current_capacity() for r in hi]} "
            f"lo={[r.current_capacity() for r in lo]}"
        )
        print(f"converged in {converged_at:.1f}s "
              f"(bound {CONVERGE_BOUND_S}s): hi={hi_tot:.1f}/"
              f"{N_HI * WANTS:.0f} lo={lo_tot:.1f}/{N_LO * lo_share:.0f}")

        # Conservation at every hop, from each server's own debug vars.
        v_leaf = shared_vars(DBG_LEAF)
        v_region = shared_vars(DBG_REGION)
        v_root = shared_vars(DBG_ROOT)
        assert v_leaf and v_region and v_root, "missing /debug/vars"
        eps = 1e-6
        # Leaf outgrants fit the leaf's lease from the region (its
        # template capacity IS that lease), and so on up the tree.
        assert v_leaf["sum_has"] <= v_leaf["capacity"] + eps, v_leaf
        assert v_leaf["capacity"] <= v_region["capacity"] + eps, (
            v_leaf, v_region,
        )
        assert v_region["sum_has"] <= v_region["capacity"] + eps, v_region
        assert v_region["capacity"] <= ROOT_CAP + eps, v_region
        assert v_root["sum_has"] <= ROOT_CAP + eps, v_root
        print(
            "conservation per hop: "
            f"leaf {v_leaf['sum_has']:.1f}<={v_leaf['capacity']:.1f}, "
            f"region {v_region['sum_has']:.1f}<={v_region['capacity']:.1f}"
            f"<={ROOT_CAP:.0f}, root {v_root['sum_has']:.1f}"
        )

        # Per-client band shape (not just totals): every high client at
        # ~full wants, every low client well below.
        for r in hi:
            assert r.current_capacity() >= 0.9 * WANTS, r.current_capacity()
        for r in lo:
            assert r.current_capacity() <= lo_share * 1.2 + eps, (
                r.current_capacity()
            )
        print("TREE3 OK: bands held through two hops")
    finally:
        for c in clients:
            try:
                await asyncio.wait_for(c.close(), 10)
            except Exception:
                pass


try:
    asyncio.run(main())
finally:
    stop(leaf)
    stop(region)
    stop(root)
    os.unlink(cfg)
