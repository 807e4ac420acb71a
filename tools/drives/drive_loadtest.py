"""Loadtest on the real TPU: batch+native server enforcing cap 1000 over
120 recipe-driven workers; measure aggregate QPS at the target."""
import os
import re
import sys
import time
import urllib.request

from _common import platform_args, spawn as _spawn, stop, tail, write_config


cfg = write_config("""
resources:
  - identifier_glob: "loadtest"
    capacity: 1000
    safe_capacity: 10
    algorithm:
      kind: PROPORTIONAL_SHARE
      lease_length: 60
      refresh_interval: 2
      learning_mode_duration: 0
  - identifier_glob: "*"
    capacity: 100
    algorithm:
      kind: FAIR_SHARE
      lease_length: 60
      refresh_interval: 2
      learning_mode_duration: 0
""")

procs = []


def spawn(args):
    p = _spawn(args, name="loadtest")
    procs.append(p)
    return p

try:
    target = spawn([sys.executable, "-m", "doorman_tpu.loadtest.target",
                    "--port", "16061", "--metrics-port", "16062"])
    server = spawn([sys.executable, "-m", "doorman_tpu.cmd.server",
                    "--port", "16060", "--debug-port", "-1",
                    "--mode", "batch", "--native-store",
                    "--tick-interval", "0.5",
                    "--config", f"file:{cfg}",
                    "--server-id", "127.0.0.1:16060"] + platform_args())
    time.sleep(25)  # server compile warm-up happens on first ticks
    for w in range(3):
        spawn([sys.executable, "-m", "doorman_tpu.loadtest.worker",
               "--server", "127.0.0.1:16060", "--target", "127.0.0.1:16061",
               "--resource", "loadtest",
               "--client-id", f"lt-{w}",
               "--recipes", "40x15+random_change(10)",
               "--recipe-interval", "20",
               "--minimum-refresh-interval", "2",
               "--duration", "150"])

    def scrape():
        with urllib.request.urlopen(
            "http://127.0.0.1:16062/metrics", timeout=5
        ) as r:
            text = r.read().decode()
        m = re.search(r"^doorman_loadtest_target_qps ([0-9.eE+-]+)$",
                      text, re.M)
        return float(m.group(1)) if m else 0.0

    samples = []
    t0 = time.time()
    while time.time() - t0 < 150:
        time.sleep(5)
        q = scrape()
        if time.time() - t0 > 60:  # steady state only
            samples.append(q)
        print(f"t={time.time()-t0:5.0f}s qps={q:8.1f}", flush=True)
        if any(p.poll() not in (None, 0) for p in procs[:2]):
            print(tail(procs[1], 3000))
            sys.exit("server/target died")
    avg = sum(samples) / len(samples)
    peak = max(samples)
    print(f"steady-state avg qps = {avg:.1f}, peak = {peak:.1f} (cap 1000)")
    assert 800 <= avg <= 1150, avg
    print("LOADTEST OK")
finally:
    for p in procs:
        stop(p)
    os.unlink(cfg)
