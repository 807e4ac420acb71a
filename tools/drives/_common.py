"""Shared plumbing for the validation drives: repo-rooted subprocess
spawning with file-backed logs (a PIPE nobody drains blocks the child
once the OS buffer fills), teardown, and the 1M-lease bulk loader."""

from __future__ import annotations

import os
import subprocess
import sys
import tempfile
import time

REPO = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", ".."))
sys.path.insert(0, REPO)

NUM_RES, PER_RES = 10_000, 100

# DOORMAN_DRIVE_PLATFORM=cpu runs every drive against the CPU backend
# (no chip needed): spawned servers get --jax-platform and the
# in-process drives pin jax themselves. A drive's parent never touches
# JAX before it spawns servers: the chip belongs to one process.
PLATFORM = os.environ.get("DOORMAN_DRIVE_PLATFORM", "")


def platform_args() -> list:
    """Extra server CLI args pinning the backend when the drive runs
    on an explicit platform."""
    return ["--jax-platform", PLATFORM] if PLATFORM else []


def pin_platform_in_process() -> None:
    """For drives that run the solver in THIS process."""
    if PLATFORM:
        import jax

        jax.config.update("jax_platforms", PLATFORM)
        if PLATFORM == "cpu":
            jax.config.update("jax_enable_x64", True)


def ensure_ports_free(*ports) -> None:
    """Fail LOUDLY if a drive's fixed port is already bound — a stale
    server leaked by an earlier interrupted run otherwise answers the
    drive's clients with confusing not-master errors (a zombie from a
    killed parent whose `finally: stop()` never ran cost a debugging
    session). Run this before spawning anything."""
    import socket

    for port in ports:
        with socket.socket(socket.AF_INET, socket.SOCK_STREAM) as s:
            s.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
            try:
                s.bind(("127.0.0.1", port))
            except OSError as e:
                raise SystemExit(
                    f"port {port} is already in use (stale server from an "
                    f"interrupted drive? `pkill -f doorman_tpu.cmd.server` "
                    f"and retry): {e}"
                )


def spawn(args, name="proc"):
    """Start a child with stdout+stderr appended to a temp log file
    (returned alongside, for tailing on failure). The parent closes its
    handle right after Popen — the child holds its own fd, and a drive
    spawning many children must not leak one fd per child."""
    log = tempfile.NamedTemporaryFile(
        "w+", suffix=f".{name}.log", delete=False
    )
    try:
        proc = subprocess.Popen(
            args, cwd=REPO, stdout=log, stderr=subprocess.STDOUT, text=True
        )
    finally:
        log.close()
    proc._drive_log = log.name  # type: ignore[attr-defined]
    return proc


def tail(proc, n=2000) -> str:
    path = getattr(proc, "_drive_log", None)
    if not path or not os.path.exists(path):
        return "<no log>"
    with open(path) as f:
        return f.read()[-n:]


def stop(proc) -> None:
    """Terminate a spawned child. The log is deleted only on a CLEAN
    exit (rc 0 or our own terminate signal): a drive that notices a
    failure after tearing its servers down in a finally block still has
    the child log to tail."""
    already_failed = proc.poll() is not None and proc.returncode not in (0,)
    proc.terminate()
    try:
        proc.wait(5)
    except subprocess.TimeoutExpired:
        proc.kill()
        proc.wait()
    path = getattr(proc, "_drive_log", None)
    if not path or not os.path.exists(path):
        return
    # -15/-9 are OUR terminate/kill above — those are clean teardowns.
    clean = not already_failed and proc.returncode in (0, -15, -9)
    if clean:
        os.unlink(path)
    else:
        print(f"kept child log (rc={proc.returncode}): {path}",
              flush=True)


def write_config(body: str) -> str:
    cfg = tempfile.NamedTemporaryFile("w", suffix=".yml", delete=False)
    cfg.write(body)
    cfg.close()
    return cfg.name


def load_1m(server, seed: int = 1):
    """Register NUM_RES resources on `server` and bulk-load
    NUM_RES*PER_RES leases straight through its native engine (the
    store the server itself serves from). Returns (rids, cids)."""
    import numpy as np

    engine = server._store_factory.__self__
    rng = np.random.default_rng(seed)
    n = NUM_RES * PER_RES
    rids = np.empty(n, np.int32)
    for r in range(NUM_RES):
        res = server.get_or_create_resource(f"res{r}")
        rids[r * PER_RES : (r + 1) * PER_RES] = res.store._rid
    cids = np.array(
        [engine.client_handle(f"c{i}") for i in range(n)], np.int64
    )
    engine.bulk_assign(
        rids,
        cids,
        np.full(n, time.time() + 600.0),
        np.full(n, 16.0),
        np.zeros(n),
        rng.integers(1, 100, n).astype(np.float64),
        np.ones(n, np.int32),
    )
    return rids, cids
