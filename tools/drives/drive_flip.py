"""E2E drive: batch+native server CLI under etcd election; force a
mastership flip (expire the lock lease) while a client holds a lease
and confirm the server steps down, re-wins, and serves fresh grants."""

import os
import subprocess
import sys
import time

from _common import ensure_ports_free, platform_args, REPO, spawn, stop, tail, write_config

from tests.fake_etcd import FakeEtcd


fake = FakeEtcd()
fake.start()
cfg = write_config("""
resources:
  - identifier_glob: "*"
    capacity: 100
    algorithm:
      kind: PROPORTIONAL_SHARE
      lease_length: 30
      refresh_interval: 2
      learning_mode_duration: 0
""")

port = 15322
ensure_ports_free(port)
proc = spawn(
    [sys.executable, "-m", "doorman_tpu.cmd.server",
     "--port", str(port), "--debug-port", "-1",
     "--mode", "batch", "--native-store", "--tick-interval", "0.3",
     "--config", f"file:{cfg}",
     "--etcd-endpoints", fake.address,
     "--master-election-lock", "/doorman/master",
     "--master-delay", "3.0",
     "--server-id", f"127.0.0.1:{port}"] + platform_args(),
    name="flip-server",
)


def one_shot(cid, wants):
    return subprocess.run(
        [sys.executable, "-m", "doorman_tpu.cmd.client",
         "--server", f"127.0.0.1:{port}", "--client-id", cid,
         "--timeout", "45", "res0", str(wants)],
        cwd=REPO, capture_output=True, text=True, timeout=120,
    )


try:
    deadline = time.time() + 40
    while time.time() < deadline and fake.value("/doorman/master") is None:
        assert proc.poll() is None, tail(proc)
        time.sleep(0.3)
    assert fake.value("/doorman/master"), "server never won mastership"
    time.sleep(3.0)  # a few ticks (CPU mode compiles here)

    out = one_shot("pre", 10)
    assert out.returncode == 0 and "got 10" in out.stdout, (
        out.stdout + out.stderr
    )
    print("pre-flip grant OK:", out.stdout.strip())

    # Force the flip: the lock's lease lapses as if renewal stopped.
    fake.expire_key_lease("/doorman/master")
    # The server must step down (refresh fails) and then re-win.
    deadline = time.time() + 30
    rewon = saw_empty = False
    while time.time() < deadline:
        v = fake.value("/doorman/master")
        if v is None:
            saw_empty = True
        elif saw_empty and v:
            rewon = True
            break
        time.sleep(0.2)
    assert rewon, "server did not re-acquire mastership after the flip"
    time.sleep(3.0)  # ticks on the fresh engine

    out = one_shot("post", 7)
    assert out.returncode == 0 and "got 7" in out.stdout, (
        out.stdout + out.stderr
    )
    print("post-flip grant OK:", out.stdout.strip())
    print("E2E OK: flip mid-operation, server re-won, fresh grants served")
finally:
    stop(proc)
    fake.stop()
    os.unlink(cfg)
