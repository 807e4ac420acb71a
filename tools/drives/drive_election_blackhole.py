"""E2E drive: a server with a KVElection over EtcdKV must win
mastership and serve capacity when the FIRST etcd endpoint is
partitioned (blackhole: accepts TCP, never answers) and the second is
healthy — the deadline-budgeted endpoint failover."""

import os
import socket
import subprocess
import sys
import time

from _common import ensure_ports_free, platform_args, REPO, spawn, stop, tail, write_config

from tests.fake_etcd import FakeEtcd


blackhole = socket.socket()
blackhole.bind(("127.0.0.1", 0))
blackhole.listen(1)
bh_addr = f"127.0.0.1:{blackhole.getsockname()[1]}"

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

port = 15311
ensure_ports_free(port)
proc = spawn(
    [sys.executable, "-m", "doorman_tpu.cmd.server",
     "--port", str(port), "--debug-port", "-1",
     "--config", f"file:{cfg}",
     "--etcd-endpoints", f"{bh_addr},{fake.address}",
     "--master-election-lock", "/doorman/master",
     "--master-delay", "6.0",
     "--server-id", f"127.0.0.1:{port}"] + platform_args(),
    name="blackhole-server",
)
try:
    # Give it time to campaign past the blackhole endpoint.
    deadline = time.time() + 40
    lock_value = None
    while time.time() < deadline:
        lock_value = fake.value("/doorman/master")
        if lock_value:
            break
        assert proc.poll() is None, tail(proc)
        time.sleep(0.5)
    print("lock holder:", lock_value)
    assert lock_value == f"127.0.0.1:{port}", lock_value

    out = subprocess.run(
        [sys.executable, "-m", "doorman_tpu.cmd.client",
         "--server", f"127.0.0.1:{port}", "--timeout", "45",
         "res0", "10"],
        cwd=REPO, capture_output=True, text=True, timeout=120,
    )
    print("client stdout:", out.stdout.strip())
    print("client rc:", out.returncode)
    assert out.returncode == 0, out.stderr
    assert "10" in out.stdout, out.stdout
    print("E2E OK: mastership won past the blackhole endpoint; grant served")
finally:
    stop(proc)
    blackhole.close()
    fake.stop()
    os.unlink(cfg)
