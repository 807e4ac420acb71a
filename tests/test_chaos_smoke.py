"""Fast deterministic chaos smoke suite (tier-1).

Every shipped plan runs on CPU and must uphold the invariants:
Σgrants <= capacity each tick, at most one master, lag-but-never-lead
leases, and post-heal reconvergence within the plan's budget. Beyond
the verdict bit, each scenario's event log is asserted to show the
behavior the plan was designed to provoke — a plan whose faults never
bite would pass vacuously."""

import asyncio

import pytest

import tests.conftest  # noqa: F401

from doorman_tpu.chaos import ChaosRunner, get_plan
from doorman_tpu.chaos.plans import PLANS


def run_plan(name):
    return asyncio.run(ChaosRunner(get_plan(name)).run())


@pytest.fixture(scope="module")
def verdicts():
    return {name: run_plan(name) for name in PLANS}


@pytest.mark.parametrize("name", sorted(PLANS))
def test_plan_upholds_invariants(verdicts, name):
    v = verdicts[name]
    assert v["violations"] == [], v["event_log"]
    assert v["ok"], v
    assert v["converged_after_heal_ticks"] is not None
    assert (
        v["converged_after_heal_ticks"]
        <= get_plan(name).reconverge_ticks
    )


def _kinds(v):
    return [e[1] for e in v["event_log"]]


def _masters_timeline(v):
    return [(e[0], e[2]) for e in v["event_log"] if e[1] == "master"]


def test_master_flap_fails_over_without_split_brain(verdicts):
    v = verdicts["master_flap"]
    timeline = _masters_timeline(v)
    # s0 wins, steps down during the brownout (a masterless gap is
    # expected — never two masters), s1 takes over.
    assert timeline[0][1] == ["s0"]
    assert [] in [m for _, m in timeline]
    assert ["s1"] in [m for _, m in timeline]


def test_master_flap_streaming_leg(verdicts):
    """The streaming subscriber rides the flap: establish + snapshot
    push at t0, SILENT at steady state (the RPC win — no poll events
    while the stream is healthy), terminal mastership redirect at the
    flip, poll fallback while masterless, re-establishment once a
    master answers — with every lease-window invariant intact (the
    plan-level ok covers the stream client too)."""
    v = verdicts["master_flap"]
    flap_tick = next(
        e[0] for e in v["event_log"] if e[1] == "fault"
    )
    streams = [e for e in v["event_log"] if e[1] == "stream"]
    assert streams, "no streaming leg in master_flap"
    by_tick = {e[0]: e[3] for e in streams}
    # Establishment with the snapshot push, before the fault.
    assert by_tick[0] == "establish" and streams[0][4] == 1
    # Healthy steady state is SILENT: no poll events before the flap
    # after establishment (pure pushes at most).
    for e in streams:
        if 0 < e[0] < flap_tick:
            assert "poll" not in e[3], f"steady-state poll at {e}"
    # The flip terminates the stream with a mastership redirect and
    # the client degrades to polling.
    assert any(
        "redirect" in ev and "poll" in ev
        for t, ev in by_tick.items() if t >= flap_tick
    ), "no redirect+poll fallback at the flip"
    # And a later clean re-establishment (snapshot push again).
    assert any(
        e[3] == "establish" and e[0] > flap_tick and e[4] >= 1
        for e in streams
    ), "stream never re-established after the flap"


def test_master_flap_warm_restores_instead_of_relearning(verdicts):
    v = verdicts["master_flap_warm"]
    plan = get_plan("master_flap_warm")
    restores = [e for e in v["event_log"] if e[1] == "restore"]
    # The initial election finds an empty backend (cold), the takeover
    # finds the predecessor's state (warm).
    assert [e[3] for e in restores] == ["cold_empty", "warm"]
    warm = restores[-1]
    server, mode, leases, clean_down, learning = warm[2:]
    assert server == "s1" and leases == len(plan.setup["wants"])
    # s0 stepped down cleanly, so the journal is complete and learning
    # is skipped outright for the restored resource...
    assert clean_down is True
    assert learning == [["r0", "skip"]]
    # ...which is what makes the 2-tick reconvergence budget meetable:
    # the cold path would spend learning_mode_duration (10 ticks)
    # serving conservative grants first.
    assert plan.reconverge_ticks < plan.setup["learning_mode_duration"]
    assert (
        v["converged_after_heal_ticks"] <= plan.reconverge_ticks
    )
    # The takeover happened during the fault window, not after heal:
    # restore, not relearn, is what closed the gap.
    assert warm[0] < v["heal_tick"]


WARM_VARIANTS = sorted(n for n in PLANS if n.startswith("master_flap_warm_"))


@pytest.mark.parametrize("name", WARM_VARIANTS)
def test_master_flap_warm_arc_per_fairness_lane(verdicts, name):
    """The warm-takeover contract is algorithm-independent: every
    fairness-portfolio lane (fair/maxmin/balanced/logutil) restores
    instead of relearning, skips learning on the clean step-down, and
    reconverges inside the SAME budget the proportional plan meets."""
    v = verdicts[name]
    plan = get_plan(name)
    restores = [e for e in v["event_log"] if e[1] == "restore"]
    assert [e[3] for e in restores] == ["cold_empty", "warm"]
    warm = restores[-1]
    server, _mode, leases, clean_down, learning = warm[2:]
    assert server == "s1" and leases == len(plan.setup["wants"])
    assert clean_down is True
    assert learning == [["r0", "skip"]]
    assert v["converged_after_heal_ticks"] <= plan.reconverge_ticks
    assert warm[0] < v["heal_tick"]


def test_master_flap_warm_variant_logs_deterministic(verdicts):
    """One representative portfolio parametrization replayed from
    scratch produces the module fixture's event log byte-for-byte —
    the per-kind determinism pin (the seeded-replay contract extends
    to the new lanes' solve paths)."""
    name = "master_flap_warm_maxmin"
    again = run_plan(name)
    assert again["event_log"] == verdicts[name]["event_log"]
    assert again["converged_after_heal_ticks"] == (
        verdicts[name]["converged_after_heal_ticks"]
    )


def test_client_storm_sheds_bottom_up_with_top_band_floor(verdicts):
    v = verdicts["client_storm"]
    plan = get_plan("client_storm")
    storm_tick = plan.events[0].at_tick
    tallies = v["admission"]["s0"]
    # The goodput floor: the top band is NEVER shed; the swarm's band
    # eats nearly all of the shedding; the middle band sees some (the
    # level collapse walks up from the bottom) but keeps its leases.
    assert tallies["GetCapacity/2"]["shed"] == 0
    assert tallies["GetCapacity/0"]["shed"] > tallies["GetCapacity/1"]["shed"] > 0
    # The shed matrix is law: every release is admitted — including
    # the swarm's own 20 releases when it drains at heal.
    assert tallies["ReleaseCapacity/0"]["shed"] == 0
    assert tallies["ReleaseCapacity/0"]["admitted"] >= 20
    storm = [e for e in v["event_log"] if e[1] == "storm"]
    assert len(storm) == plan.events[0].duration_ticks
    # The hard per-window cap bites in the storm's FIRST window — some
    # of the swarm is admitted under the budget (no blanket denial),
    # the rest sheds before the AIMD level ever moved...
    assert 0 < storm[0][2] < plan.events[0].params["clients"]
    # ...and once the level collapses the swarm is fully shed.
    assert storm[-1][2] == 0
    adm = [e for e in v["event_log"] if e[1] == "admission"]
    # Nothing shed before the storm, and the post-heal additive
    # recovery readmits every band before the run ends.
    assert all(e[4] == 0 for e in adm if e[0] < storm_tick)
    assert all(e[4] == 0 for e in adm[-3:])
    # The baseline clients ride through byte-unchanged: shed refreshes
    # retain leases, so convergence is immediate at heal.
    assert v["converged_after_heal_ticks"] == 0


def test_etcd_brownout_survives_single_hiccup_then_relearns(verdicts):
    v = verdicts["etcd_brownout"]
    plan = get_plan("etcd_brownout")
    hiccup_tick = plan.events[0].at_tick
    brownout_tick = plan.events[2].at_tick
    changes = _masters_timeline(v)
    # The single dropped renewal round-trip is retried, not a loss:
    assert all(t != hiccup_tick for t, _ in changes[1:])
    # The sustained brownout IS a loss, at exactly its start tick.
    assert (brownout_tick, []) in changes
    # ... and the same server re-wins after the heal.
    assert changes[-1][1] == ["s0"]


def test_device_outage_degrades_to_tick_errors(verdicts):
    v = verdicts["device_outage"]
    errors = [e for e in v["event_log"] if e[1] == "tick_error"]
    # The dead backend surfaces as per-tick errors, never as a
    # violation or a crash; serving continued from the stores.
    assert len(errors) == 3
    assert all("chaos: device backend unreachable" in e[3] for e in errors)


def test_intermediate_partition_degrades_then_heals(verdicts):
    v = verdicts["intermediate_partition"]
    kinds = _kinds(v)
    # The parent-lease expiry visibly degraded the clients (capacity
    # decays toward zero — no overcommit), then healed to baseline.
    assert "degraded" in kinds and "converged" in kinds
    degraded_tick = next(e[0] for e in v["event_log"] if e[1] == "degraded")
    assert degraded_tick < v["heal_tick"]


@pytest.mark.parametrize("name", sorted(PLANS))
def test_verdicts_carry_slo_and_flightrec(verdicts, name):
    """Every plan's verdict is an SLO surface and a black box: a
    reconvergence verdict always, per-band tallies on admission plans,
    and no flight-recorder dump on a clean run (violations are what
    trigger the dump — tests/test_flightrec.py forces one)."""
    v = verdicts[name]
    slo_v = {x["slo"]: x for x in v["slo"]["verdicts"]}
    recon = slo_v[f"{name}:reconverge_ticks"]
    assert recon["status"] == "pass"
    assert recon["observed"] == v["converged_after_heal_ticks"]
    assert recon["target"] == get_plan(name).reconverge_ticks
    # The deltas field is always present (None until a prior round
    # embedded the same verdict) — the trajectory contract.
    assert all("delta_vs_prev" in x for x in v["slo"]["verdicts"])
    assert v["slo"]["ok"]
    assert v["flightrec_dump"] is None


def test_grant_corruption_caught_by_shadow_audit(verdicts):
    """The shadow-oracle acceptance arc: a silently scaled grant that
    no structural invariant can see (it SHRINKS a row — capacity
    conservation, lag-never-lead, and band floors all still hold) is
    confirmed by the fixpoint audit within 2K ticks of the fault, with
    a deterministic verdict."""
    v = verdicts["grant_corruption"]
    plan = get_plan("grant_corruption")
    fault_tick = plan.events[0].at_tick
    sample_k = plan.setup["audit_sample"]
    # Invariants held — the corruption is invisible to them...
    assert v["violations"] == [] and v["ok"]
    # ...but the audit confirmed exactly one divergent state.
    audit = v["audit"]["s0"]
    assert audit["divergences"] == 1
    detail = audit["details"][0]
    assert detail["rid"] == "r0" and detail["clients"] == ["c0"]
    # The corrupted grant is the oracle's answer scaled by the fault's
    # factor — the audit caught the exact corruption, not noise.
    factor = plan.events[0].params["factor"]
    assert detail["has"][0] == pytest.approx(
        detail["expected"][0] * factor
    )
    # Detection latency: strike one at the first sample with stable
    # corrupted inputs, confirmation one sample later — within 2K
    # ticks of the fault, and the event log pins the exact tick.
    entries = [e for e in v["event_log"] if e[1] == "audit_divergence"]
    assert entries == [[detail["tick"], "audit_divergence", "s0", 1]]
    assert fault_tick < detail["tick"] <= fault_tick + 2 * sample_k
    # The anomaly detector's floor watch flags every post-confirmation
    # record (the standing-alarm property: a bit-identity violation
    # never reads as healthy again).
    det = v["detect"]
    assert det["per_field"]["audit_divergence"] > 0
    assert all(
        d["field"] == "audit_divergence" and d["value"] >= 1.0
        for d in det["detections"]
    )


def test_grant_corruption_verdict_is_byte_stable(verdicts):
    """Replaying the plan reproduces the audit verdict byte-for-byte:
    the inline comparator runs on virtual time, so divergence ticks,
    digests, and the detector's windowed output are all part of the
    seeded-replay contract."""
    again = run_plan("grant_corruption")
    v = verdicts["grant_corruption"]
    assert again["event_log"] == v["event_log"]
    assert again["log_sha256"] == v["log_sha256"]
    assert again["audit"] == v["audit"]
    assert again["detect"] == v["detect"]


def test_clean_plan_pins_audit_silence(verdicts):
    """The other half of the audit acceptance: a fault plan that never
    corrupts grants (device_outage runs the same auditor at the
    same cadence) reports zero divergences and zero anomalies — the
    auditor does not cry wolf through solver outages, slow solves, or
    resident overflows."""
    v = verdicts["device_outage"]
    audit = v["audit"]["s0"]
    assert audit["divergences"] == 0 and audit["details"] == []
    assert audit["samples"] > 0  # it actually ran
    assert v["detect"] is not None
    assert v["detect"]["anomalies"] == 0
    # Plans without an armed auditor carry an explicit None, never a
    # fabricated block.
    assert verdicts["master_flap"]["audit"] is None


def test_client_storm_slo_embeds_per_band_tallies(verdicts):
    """The acceptance surface: chaos client_storm emits a machine-
    readable top-band goodput verdict whose detail carries the exact
    per-band admitted/shed tallies."""
    v = verdicts["client_storm"]
    slo_v = {x["slo"]: x for x in v["slo"]["verdicts"]}
    floor = slo_v["client_storm:top_band_goodput"]
    assert floor["status"] == "pass" and floor["observed"] == 1.0
    per_band = floor["detail"]["per_band"]
    # Bottom band shed hardest, the top band never (mirrors the
    # top_band_floor invariant, now with a numeric trajectory).
    assert per_band["0"]["shed"] > 0
    assert per_band[str(floor["detail"]["band"])]["shed"] == 0
    # The verdict tallies agree with the runner's admission block.
    adm = v["admission"]["s0"]
    for band, counts in per_band.items():
        key = f"GetCapacity/{band}"
        assert adm[key]["admitted"] == counts["admitted"]
        assert adm[key]["shed"] == counts["shed"]


def test_frontend_worker_crash_resets_to_redirect_and_reestablishes(verdicts):
    """The serving-plane crash arc: the dead worker's streams end with
    a mastership redirect THE SAME TICK (never a silent lapse), their
    clients re-establish on the survivor the next tick, the streams the
    survivor held never notice, and the worker restarts at heal — with
    the full population re-homed and held by the end of the run."""
    v = verdicts["frontend_worker_crash"]
    crash = next(e for e in v["event_log"] if e[1] == "worker_crash")
    tick, _, server, worker, dropped = crash
    assert server == "s0" and worker == 0
    assert dropped > 0, "the crash must actually drop streams"
    # Every dropped stream's client saw the redirect the crash tick...
    redirects = [
        e for e in v["event_log"]
        if e[1] == "stream" and e[0] == tick and "redirect" in e[3]
    ]
    assert len(redirects) == dropped
    # ...and re-established the very next tick (onto the survivor —
    # pushes resume before the dead worker returns).
    reestablished = {
        e[2] for e in v["event_log"]
        if e[1] == "stream" and tick < e[0] < v["heal_tick"]
        and "establish" in e[3]
    }
    assert reestablished == {e[2] for e in redirects}
    restore = next(e for e in v["event_log"] if e[1] == "worker_restore")
    assert restore[0] == v["heal_tick"] and restore[3] == worker
    fe = v["frontend"]["s0"]
    assert fe["crashes"] == 1 and fe["restores"] == 1
    # Everyone is held again at the end; both workers live.
    assert fe["held"] == get_plan("frontend_worker_crash").setup["streams"]
    assert fe["live"] == [0, 1]
    # The restarted worker's reader resumed at the ring head: no frame
    # replay (a fresh cursor reports zero laps and no backlog debt).
    w0 = fe["per_worker"][0]
    assert w0["reader"]["laps"] == 0


def test_frontend_ring_stall_laps_loudly_on_resume(verdicts):
    """The serving-plane stall arc: a frozen pump over a tiny ring is
    LAPPED by the tick edge (appends never block); the resume pump
    reports the lap and resets every held stream to a redirect — the
    loud failure mode — after which clients re-establish and the pool
    returns to steady state."""
    v = verdicts["frontend_ring_stall"]
    stall = next(e for e in v["event_log"] if e[1] == "ring_stall")
    resume = next(e for e in v["event_log"] if e[1] == "ring_resume")
    assert stall[0] == get_plan("frontend_ring_stall").events[0].at_tick
    assert resume[0] == v["heal_tick"]
    # The resume pump surfaced the lap...
    pump = next(
        e for e in v["event_log"]
        if e[1] == "frontend_pump" and e[0] == resume[0]
    )
    assert pump[4] >= 1  # lapped
    # ...which reset the stalled worker's streams to redirects that
    # tick; the survivor's streams saw no redirect the whole run.
    redirected = {
        e[2] for e in v["event_log"]
        if e[1] == "stream" and e[0] == resume[0] and "redirect" in e[3]
    }
    assert redirected
    all_redirected = {
        e[2] for e in v["event_log"]
        if e[1] == "stream" and "redirect" in e[3]
    }
    assert all_redirected == redirected, (
        "streams outside the stalled worker were reset"
    )
    # Steady state after re-establishment: no redirects in the final
    # quarter of the run (the oscillation guard — the ring must hold a
    # healthy tick's traffic).
    last_q = v["ticks"] - (v["ticks"] - v["heal_tick"]) // 2
    assert not [
        e for e in v["event_log"]
        if e[1] == "stream" and e[0] >= last_q and "redirect" in e[3]
    ]
    fe = v["frontend"]["s0"]
    assert fe["held"] == get_plan("frontend_ring_stall").setup["streams"]
    assert fe["stalled"] == []


def test_frontend_crash_log_is_deterministic(verdicts):
    """The serving-plane arcs replay byte-identically: rings, pumps,
    crash/restore, redirects and re-establishments are all driven on
    the virtual clock."""
    again = run_plan("frontend_worker_crash")
    assert again["log_sha256"] == verdicts["frontend_worker_crash"]["log_sha256"]
    assert again["frontend"] == verdicts["frontend_worker_crash"]["frontend"]
