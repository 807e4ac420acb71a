"""Run the validation drives as one release gate.

    python tools/drives/run_all.py [--platform cpu] [--slow] [--scale]

Default: the quick control-plane drives. --slow adds the 10-minute soak;
--scale adds the 1M-lease drives (accelerator-speed solves assumed).
Each drive runs as its own subprocess; the summary lists PASS/FAIL per
drive and the exit code is non-zero if any failed."""

import argparse
import os
import subprocess
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))

# name -> generous wall-clock bound (a hung drive must fail the gate,
# not block it forever).
QUICK = [
    ("drive_election_blackhole.py", 420),
    ("drive_flip.py", 420),
    ("drive_warm_takeover.py", 420),
    ("drive_priority.py", 420),
    ("drive_tree.py", 480),
    ("drive_tree3.py", 480),
    ("drive_loadtest.py", 480),
    # Scales with the platform: 50k wide clients on cpu, 1M on device.
    ("drive_wide.py", 900),
]
SLOW = [("soak.py", 900)]
SCALE = [
    ("drive_1m.py", 900),
    ("drive_1m_chaos.py", 900),
    ("drive_idle.py", 900),
]


def main() -> None:
    p = argparse.ArgumentParser(description="doorman-tpu validation drives")
    p.add_argument("--platform", default="",
                   help="e.g. 'cpu' to run without a device backend "
                        "(sets DOORMAN_DRIVE_PLATFORM for every drive)")
    p.add_argument("--slow", action="store_true", help="include the soak")
    p.add_argument("--scale", action="store_true",
                   help="include the 1M-lease drives")
    args = p.parse_args()

    drives = list(QUICK)
    if args.slow:
        drives += SLOW
    if args.scale:
        drives += SCALE

    env = dict(os.environ)
    if args.platform:
        env["DOORMAN_DRIVE_PLATFORM"] = args.platform

    results = []
    for name, bound_s in drives:
        t0 = time.time()
        # Each drive runs in its own session so a hang can be killed
        # WITH the servers it spawned — otherwise one hung drive leaks
        # children on fixed ports and poisons every later drive.
        child = subprocess.Popen(
            [sys.executable, os.path.join(HERE, name)],
            env=env, stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
            text=True, start_new_session=True,
        )
        try:
            out, _ = child.communicate(timeout=bound_s)
            rc = child.returncode
        except subprocess.TimeoutExpired:
            import signal

            try:
                os.killpg(child.pid, signal.SIGKILL)
            except ProcessLookupError:
                pass
            out_partial, _ = child.communicate()
            rc = -1
            out = (
                f"{out_partial or ''}\n"
                f"HUNG: no result within {bound_s}s (process group killed)"
            )
        ok = rc == 0
        results.append((name, ok, time.time() - t0))
        status = "PASS" if ok else f"FAIL rc={rc}"
        print(f"{status:12s} {name} ({results[-1][2]:.0f}s)", flush=True)
        if not ok:
            print(out[-1500:], flush=True)

    failed = [n for n, ok, _ in results if not ok]
    print(f"\n{len(results) - len(failed)}/{len(results)} drives passed")
    if failed:
        sys.exit(1)


if __name__ == "__main__":
    main()
