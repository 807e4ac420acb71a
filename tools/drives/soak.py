"""10-minute soak: batch+native server CLI on the device backend, etcd
election with a forced lock expiry every 75s, 30 clients refreshing
continuously via the real client library. Every flip must be observed
END TO END — the lock vanishes, the server re-acquires it, and a FRESH
post-flip grant reaches a client — and the server RSS must stay flat.
"""

import asyncio
import os
import sys
import time

from _common import ensure_ports_free, platform_args, spawn, stop, tail, write_config

from tests.fake_etcd import FakeEtcd

DURATION = 600.0
FLIP_EVERY = 75.0


fake = FakeEtcd()
fake.start()
cfg = write_config("""
resources:
  - identifier_glob: "*"
    capacity: 300
    algorithm:
      kind: PROPORTIONAL_SHARE
      lease_length: 20
      refresh_interval: 2
      learning_mode_duration: 0
""")

port = 15400
ensure_ports_free(port, 15450)  # serving + debug ports
server = spawn(
    [sys.executable, "-m", "doorman_tpu.cmd.server",
     "--port", str(port), "--debug-port", "15450",
     "--mode", "batch", "--native-store", "--tick-interval", "0.5",
     "--config", f"file:{cfg}",
     "--etcd-endpoints", fake.address,
     "--master-election-lock", "/lock", "--master-delay", "5.0",
     "--server-id", f"127.0.0.1:{port}"] + platform_args(),
    name="soak-server",
)


def rss_mb():
    with open(f"/proc/{server.pid}/status") as f:
        for line in f:
            if line.startswith("VmRSS"):
                return int(line.split()[1]) / 1024.0
    return 0.0


async def main():
    from doorman_tpu.client import Client

    deadline = time.time() + 60
    while time.time() < deadline and fake.value("/lock") is None:
        assert server.poll() is None, tail(server)
        await asyncio.sleep(0.5)
    assert fake.value("/lock"), "never became master"
    await asyncio.sleep(3)

    clients, resources = [], []
    for i in range(30):
        c = await Client.connect(
            f"127.0.0.1:{port}", client_id=f"soak{i}",
            minimum_refresh_interval=1.0,
        )
        clients.append(c)
        resources.append(await c.resource("res0", wants=20.0))

    async def wait_for(pred, timeout, what):
        end = time.time() + timeout
        while time.time() < end:
            if pred():
                return
            assert server.poll() is None, tail(server)
            await asyncio.sleep(0.3)
        raise AssertionError(f"timeout waiting for {what}")

    flips = 0
    rss_samples = []
    start = time.time()
    next_flip = start + FLIP_EVERY
    try:
        while time.time() - start < DURATION:
            await asyncio.sleep(5)
            assert server.poll() is None, tail(server)
            rss_samples.append(rss_mb())
            if time.time() >= next_flip:
                flips += 1
                next_flip = time.time() + FLIP_EVERY
                fake.expire_key_lease("/lock")
                # End-to-end recovery, not a stale-lease tautology:
                # the lock must be re-acquired, and a FRESH grant (for
                # changed wants, so the capacity queue gets a new
                # value) must reach a client afterwards.
                await wait_for(
                    lambda: fake.value("/lock") is not None,
                    40, f"re-acquire after flip {flips}",
                )
                probe = resources[flips % len(resources)]
                q = probe.capacity()
                while not q.empty():
                    q.get_nowait()
                await probe.ask(20.0 + flips)  # forces a refresh
                fresh = await asyncio.wait_for(q.get(), 40)
                assert fresh > 0, f"flip {flips}: fresh grant {fresh}"
        granted = sum(r.current_capacity() for r in resources)
        print(f"flips={flips} granted_total={granted:.1f} "
              f"rss_first={rss_samples[2]:.0f}MB "
              f"rss_last={rss_samples[-1]:.0f}MB")
        assert flips >= 6
        # RSS growth bounded: < 15% over the soak after warmup.
        assert rss_samples[-1] < rss_samples[2] * 1.15 + 50, rss_samples
        print("SOAK OK")
    finally:
        for c in clients:
            try:
                await asyncio.wait_for(c.close(), 10)
            except Exception:
                pass


try:
    asyncio.run(main())
finally:
    stop(server)
    fake.stop()
    os.unlink(cfg)
