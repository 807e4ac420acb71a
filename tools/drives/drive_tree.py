"""Two-level tree live: a root and an intermediate server (both
batch+native on the device backend); 20 clients of the intermediate
must converge to grants that sum to at most the intermediate's own
lease from the root, and the root must see the intermediate's
aggregated demand as band sub-leases."""

import asyncio
import os
import sys
import time
import urllib.request

from _common import ensure_ports_free, platform_args, spawn, stop, tail, write_config


cfg = write_config("""
resources:
  - identifier_glob: "shared"
    capacity: 400
    algorithm:
      kind: FAIR_SHARE
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

ROOT, INTER, ROOT_DEBUG = 15710, 15711, 15760
ensure_ports_free(ROOT, INTER, ROOT_DEBUG)
root = spawn(
    [sys.executable, "-m", "doorman_tpu.cmd.server",
     "--port", str(ROOT), "--debug-port", str(ROOT_DEBUG),
     "--mode", "batch", "--native-store", "--tick-interval", "0.4",
     "--config", f"file:{cfg}",
     "--server-id", f"127.0.0.1:{ROOT}"] + platform_args(),
    name="tree-root",
)
inter = spawn(
    [sys.executable, "-m", "doorman_tpu.cmd.server",
     "--port", str(INTER), "--debug-port", "-1",
     "--mode", "batch", "--native-store", "--tick-interval", "0.4",
     "--parent", f"127.0.0.1:{ROOT}",
     "--minimum-refresh-interval", "1.0",
     "--server-id", f"127.0.0.1:{INTER}"] + platform_args(),
    name="tree-inter",
)


async def main():
    from doorman_tpu.client import Client

    await asyncio.sleep(8)  # both servers up, first parent exchange
    assert root.poll() is None, tail(root)
    assert inter.poll() is None, tail(inter)

    clients, resources = [], []
    try:
        for i in range(20):
            c = await Client.connect(
                f"127.0.0.1:{INTER}", client_id=f"leaf{i}",
                minimum_refresh_interval=1.0,
            )
            clients.append(c)
            resources.append(await c.resource("shared", wants=40.0))

        # Converge: demand 800 > root cap 400; the intermediate's total
        # outgrant must reach (essentially) its full parent lease and
        # HOLD there — two consecutive stable samples, so neither a
        # tree stuck below the lease nor a momentary pass-through of a
        # later oversubscription satisfies the check.
        deadline = time.time() + 90
        total, stable = 0.0, 0
        while time.time() < deadline:
            await asyncio.sleep(2)
            assert inter.poll() is None, tail(inter)
            assert root.poll() is None, tail(root)
            total = sum(r.current_capacity() for r in resources)
            if 396.0 <= total <= 404.0:
                stable += 1
                if stable >= 2:
                    break
            else:
                stable = 0
        print(f"intermediate outgrants total: {total:.1f} (root cap 400)")
        assert stable >= 2, f"did not hold at the parent lease: {total}"

        # The root must carry the intermediate's demand as sub-leases.
        with urllib.request.urlopen(
            f"http://127.0.0.1:{ROOT_DEBUG}/debug/resources?resource=shared",
            timeout=5,
        ) as r:
            page = r.read().decode()
        assert f"127.0.0.1:{INTER}" in page, "no sub-lease at the root"
        print("TREE OK: tree converged within the parent lease")
    finally:
        for c in clients:
            try:
                await asyncio.wait_for(c.close(), 10)
            except Exception:
                pass


try:
    asyncio.run(main())
finally:
    stop(inter)
    stop(root)
    os.unlink(cfg)
