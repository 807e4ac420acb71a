"""E2E on the real TPU: PRIORITY_BANDS resource with a capacity group,
batch+native server; high-priority clients must be served before
low-priority ones when demand exceeds capacity."""
import os
import sys
import time

from _common import platform_args, spawn, stop, tail, write_config


cfg = write_config("""
groups:
- name: upstream
  capacity: 100
resources:
- identifier_glob: banded
  capacity: 100
  capacity_group: upstream
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

port = 15610
proc = spawn(
    [sys.executable, "-m", "doorman_tpu.cmd.server",
     "--port", str(port), "--debug-port", "-1",
     "--mode", "batch", "--native-store", "--tick-interval", "0.4",
     "--config", f"file:{cfg}",
     "--server-id", f"127.0.0.1:{port}"] + platform_args(),
    name="priority-server",
)

import asyncio

async def main():
    from doorman_tpu.client import Client

    clients = []
    res = []
    try:
        # 3 high-priority (band 2) wanting 30 each; 3 low (band 0)
        # wanting 30 each: total demand 180 > cap 100. High band is
        # served fully (90), low band splits the remaining 10.
        for i in range(3):
            c = await Client.connect(f"127.0.0.1:{port}",
                                     client_id=f"hi{i}",
                                     minimum_refresh_interval=1.0)
            clients.append(c)
            res.append(("hi", await c.resource("banded", 30.0, priority=2)))
        for i in range(3):
            c = await Client.connect(f"127.0.0.1:{port}",
                                     client_id=f"lo{i}",
                                     minimum_refresh_interval=1.0)
            clients.append(c)
            res.append(("lo", await c.resource("banded", 30.0, priority=0)))
        deadline = time.time() + 90
        while time.time() < deadline:
            await asyncio.sleep(2)
            assert proc.poll() is None, tail(proc)
            hi = [r.current_capacity() for k, r in res if k == "hi"]
            lo = [r.current_capacity() for k, r in res if k == "lo"]
            total = sum(hi) + sum(lo)
            if all(h > 29.0 for h in hi) and total <= 101.0 and sum(lo) < 15.0:
                print(f"hi={hi} lo={[round(x,1) for x in lo]} total={total:.1f}")
                print("PRIORITY E2E OK: high band served first, group cap held")
                return
        raise AssertionError(
            f"did not converge: hi={hi} lo={lo} total={total}"
        )
    finally:
        for c in clients:
            try:
                await asyncio.wait_for(c.close(), 10)
            except Exception:
                pass

try:
    asyncio.run(main())
finally:
    stop(proc)
    os.unlink(cfg)
