"""Kernels: the chunked engine's tick program's share of its roofline in
the traced ticks, its slot scatter, delivery gather and whole-table
staging included: it is the program's share, not a kernel's (the
program has no named Pallas kernel, so `kernel_s` reads 0 here, and it
is the only device work of a wide cell). Least time = the work's bytes
(leases in the solve's scope: the scope's resources times clients per
resource, times roofline.BYTES_PER_LEASE; chunk padding not counted)
over the HBM peak (roofline.least_time), divided by the device's busy
time in the traced window."""

import roofline


def read(r):
    if r.trace is None or r.trace["busy_s"] <= 0 or r.peaks is None:
        return None
    scopes = [t["engines"]["resident_wide"]["scope"]
              for t in r.trace_ticks if "resident_wide" in t["engines"]]
    if not scopes:
        return None
    leases = sum(s["resources"] for s in scopes) * r.config[
        "clients_per_resource"]
    least, _bound = roofline.least_time(leases, r.peaks)
    return 100.0 * least / r.trace["busy_s"]
