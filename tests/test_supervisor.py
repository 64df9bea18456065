"""Training supervisor: heartbeats, watchdogs, topology, event log, and
the end-to-end detect -> rollback -> shrink drill.

Unit layers are jax-free (the supervisor is host-side control plane);
the e2e drill runs real worker subprocesses through
``tests/helpers/supervisor_drill.py``.
"""
import json
import os
import threading
import time

import pytest

from repro.core.tuner import shrink_plan
from repro.launch.mesh import BarrierTimeout, FileBarrier, HostTopology
from repro.launch.supervisor import EventLog, format_status, read_events
from repro.runtime.resilience import (FaultPlan, FaultPlanError, Heartbeat,
                                      StragglerDetector, Watchdog,
                                      read_heartbeats, write_heartbeat)

from helpers import run_helper


# ---------------------------------------------------------------------------
# Heartbeats
# ---------------------------------------------------------------------------

def test_heartbeat_roundtrip(tmp_path):
    d = str(tmp_path)
    write_heartbeat(d, Heartbeat(0, 5, "train", loss=1.25, grad_norm=0.5))
    write_heartbeat(d, Heartbeat(1, 4, "ckpt", gen=2))
    beats = read_heartbeats(d)
    assert set(beats) == {0, 1}
    assert beats[0].step == 5 and beats[0].loss == 1.25
    assert beats[0].t > 0 and beats[0].pid == os.getpid()
    assert beats[1].phase == "ckpt"


def test_heartbeat_gen_filter_and_torn_file(tmp_path):
    d = str(tmp_path)
    write_heartbeat(d, Heartbeat(0, 5, "train", gen=0))
    write_heartbeat(d, Heartbeat(1, 9, "train", gen=1))
    (tmp_path / "hb_h00002.json").write_text('{"host_id": 2, "st')  # torn
    (tmp_path / "hb_h00003.json").write_text('{"bogus": true}')     # schema
    beats = read_heartbeats(d, gen=1)
    assert set(beats) == {1}
    assert set(read_heartbeats(d)) == {0, 1}


# ---------------------------------------------------------------------------
# Watchdog
# ---------------------------------------------------------------------------

def _hb(host, step, phase="train", t=0.0):
    return {host: Heartbeat(host, step, phase, t=t)}


def test_watchdog_progress_based_not_write_based():
    """A hung host can still WRITE heartbeats — only (phase, step)
    advancing counts as progress."""
    dog = Watchdog([0], stall_timeout=10, miss_budget=3, now=0.0)
    dog.observe(_hb(0, 0), now=0.0)              # first train step: lenient
    dog.observe(_hb(0, 1), now=0.0)              # past it: stall deadline
    for t in range(1, 35):
        dog.observe(_hb(0, 1), now=float(t))     # same step, fresh writes
    assert dog.check(now=11.0)[0] == "suspect"
    assert dog.check(now=31.0)[0] == "hung"
    dog.observe(_hb(0, 2), now=31.0)             # progress resets the age
    assert dog.check(now=32.0)[0] == "ok"
    assert dog.progress(0) == ("train", 2)


def test_watchdog_startup_vs_stall_deadlines():
    dog = Watchdog([0, 1], stall_timeout=5, startup_timeout=100,
                   miss_budget=2, now=0.0)
    dog.observe(_hb(0, -1, "init"), now=0.0)
    dog.observe(_hb(1, 0, "train"), now=0.0)
    dog.observe(_hb(1, 1, "train"), now=0.0)
    # at t=20: host 0 still compiling (within startup_timeout) is ok,
    # host 1 past its first train step is judged on the stall deadline
    checks = dog.check(now=20.0)
    assert checks[0] == "ok" and checks[1] == "hung"
    # a host never seen at all is judged from construction time
    assert Watchdog([7], startup_timeout=100,
                    now=0.0).check(now=101.0)[7] == "suspect"


def test_watchdog_first_train_step_is_lenient():
    """The step in flight after the FIRST train beat still pays residual
    jit warmup — it gets the startup deadline, not the stall one."""
    dog = Watchdog([0], stall_timeout=5, startup_timeout=100,
                   miss_budget=2, now=0.0)
    dog.observe(_hb(0, 4, "train"), now=0.0)     # e.g. a resumed worker
    assert dog.check(now=20.0)[0] == "ok"        # warmup tolerated
    assert dog.check(now=101.0)[0] == "suspect"  # startup cap still bites
    dog.observe(_hb(0, 5, "train"), now=101.0)
    assert dog.check(now=107.0)[0] == "suspect"  # now on the tight clock


def test_watchdog_warm_worker_first_seen_past_its_start():
    """A warm worker (compile-cache hit) can finish its first steps
    between two polls: the lenient step is the worker's own start step,
    not the first train beat the monitor happens to see."""
    dog = Watchdog([0], stall_timeout=5, startup_timeout=100,
                   miss_budget=2, now=0.0)
    dog.observe({0: Heartbeat(0, 5, "train", start=0)}, now=0.0)
    assert dog.check(now=11.0)[0] == "hung"      # stall clock, not startup


def test_watchdog_done_and_ckpt_phases():
    dog = Watchdog([0], stall_timeout=5, miss_budget=2, now=0.0)
    dog.observe(_hb(0, 3, "ckpt"), now=0.0)
    assert dog.check(now=6.0)[0] == "suspect"    # ckpt uses stall deadline
    dog.observe(_hb(0, 9, "done"), now=6.0)
    assert dog.check(now=1000.0)[0] == "done"    # clean exit never stalls


# ---------------------------------------------------------------------------
# Straggler detection
# ---------------------------------------------------------------------------

def _feed(det, host, steps, dt, t0=0.0):
    t = t0
    for s in range(steps):
        det.observe({host: Heartbeat(host, s, "train", t=t)})
        t += dt


def test_straggler_flags_persistently_slow_host():
    det = StragglerDetector(factor=2.0, patience=3)
    _feed(det, 0, 10, dt=1.0)
    _feed(det, 1, 10, dt=1.0)
    _feed(det, 2, 10, dt=3.0)                    # 3x the peer median
    out = det.stragglers()
    assert set(out) == {2} and out[2] == pytest.approx(3.0)


def test_straggler_needs_patience_and_peers():
    det = StragglerDetector(factor=2.0, patience=5)
    _feed(det, 0, 4, dt=1.0)
    _feed(det, 1, 4, dt=9.0)                     # slow, but only 3 steps
    assert det.stragglers() == {}
    solo = StragglerDetector()
    _feed(solo, 0, 10, dt=9.0)                   # no peers, no verdict
    assert solo.stragglers() == {}


def test_straggler_detected_under_sparse_polling():
    """A starved monitor observes beats in multi-step jumps; the worker
    -reported step_s samples and step-counted streaks still flag the
    slow host (time-derived averages would wash the slowdown out)."""
    det = StragglerDetector(factor=2.0, patience=3)
    # host 0 fast, host 1 3x slow — each observed only every 4 steps,
    # with wall-clock t polluted by warmup (huge first gap)
    for h, dur in ((0, 1.0), (1, 3.0)):
        t = 100.0
        for s in (0, 4, 8, 12):
            det.observe({h: Heartbeat(h, s, "train", t=t, step_s=dur)})
            t += 4 * dur
    out = det.stragglers()
    assert set(out) == {1} and out[1] == pytest.approx(3.0)


def test_straggler_recovers_when_speed_returns():
    det = StragglerDetector(factor=2.0, patience=2, window=4)
    _feed(det, 0, 12, dt=1.0)
    _feed(det, 1, 6, dt=5.0)
    assert 1 in det.stragglers()
    _feed(det, 1, 6, dt=1.0, t0=100.0)           # window forgets old steps
    assert det.stragglers() == {}


# ---------------------------------------------------------------------------
# FaultPlan edge cases + host scoping
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("spec,reason,fragment", [
    ("kill@", "syntax", "kill@"),
    ("explode@3", "unknown-kind", "explode"),
    ("kill@-1", "negative-step", "kill@-1"),
    ("nan@3,nan@3", "duplicate", "nan@3"),
    ("kill@2:oops", "bad-arg", "kill@2:oops"),
    ("iofail@2:0", "bad-arg", "N >= 1"),
    ("hostdown@5", "missing-host", "hostdown@5"),
    ("slow@5", "missing-factor", "slow@5"),
    ("slow@5:0.5", "bad-arg", "0.5"),
    ("hang@5:x", "bad-arg", "hang@5:x"),
])
def test_faultplan_rejects_malformed_tokens(spec, reason, fragment):
    with pytest.raises(FaultPlanError) as ei:
        FaultPlan.parse(spec)
    assert ei.value.reason == reason
    assert fragment in str(ei.value)             # names the offending token
    assert isinstance(ei.value, ValueError)      # legacy callers survive


def test_faultplan_multihost_verbs_parse():
    fp = FaultPlan.parse("hostdown@30:1,hang@40,slow@50:2.5:1,nan@10")
    a = {x.kind: x for x in fp.actions}
    assert a["hostdown"].host == 1
    assert a["hang"].host == 0                   # default host 0
    assert a["slow"].factor == 2.5 and a["slow"].host == 1
    assert a["nan"].host is None                 # host-less: every host


def test_faultplan_for_host_filters_and_validates():
    fp = FaultPlan.parse("hostdown@30:1,hang@40,nan@10")
    h0 = [x.kind for x in fp.for_host(0, 2).actions]
    h1 = [x.kind for x in fp.for_host(1, 2).actions]
    assert h0 == ["hang", "nan"] and h1 == ["hostdown", "nan"]
    with pytest.raises(FaultPlanError) as ei:
        fp.for_host(0, 1)                        # host 1 does not exist
    assert ei.value.reason == "unknown-host"
    assert "hostdown@30:1" in str(ei.value)


def test_faultplan_hang_and_slow_hooks():
    fp = FaultPlan.parse("hang@5,slow@3:4.0")
    slept = []
    assert fp.hang_before(5, sleep=slept.append, seconds=7.0)
    assert slept == [7.0]
    assert not fp.hang_before(4, sleep=slept.append)
    assert fp.slow_factor(2) == 1.0
    assert fp.slow_factor(3) == 4.0 and fp.slow_factor(9) == 4.0


# ---------------------------------------------------------------------------
# Shrink re-planning
# ---------------------------------------------------------------------------

def test_shrink_plan_sheds_dp_first():
    assert shrink_plan(2, dp=2, pp=2, zero_stage=2) == (1, 2, 0)
    assert shrink_plan(6, dp=4, pp=2, zero_stage=1) == (3, 2, 1)


def test_shrink_plan_folds_pipeline_when_it_must():
    assert shrink_plan(1, dp=2, pp=2) == (1, 1, 0)
    assert shrink_plan(3, dp=2, pp=4) == (1, 3, 0)


def test_shrink_plan_rejects_empty_cluster():
    with pytest.raises(ValueError):
        shrink_plan(0, dp=2, pp=2)


# ---------------------------------------------------------------------------
# Host topology + file barrier
# ---------------------------------------------------------------------------

def test_host_topology_mapping_and_ring():
    topo = HostTopology(num_hosts=3, devices_per_host=4)
    assert topo.num_devices == 12
    assert topo.host_of_device(0) == 0 and topo.host_of_device(11) == 2
    assert list(topo.host_devices(1)) == [4, 5, 6, 7]
    assert topo.ring_neighbors(0) == (2, 1)
    assert topo.ring_neighbors(2) == (1, 0)
    with pytest.raises(ValueError):
        topo.host_of_device(12)
    with pytest.raises(ValueError):
        topo.host_devices(3)


def test_host_topology_cross_host_edges():
    topo = HostTopology(num_hosts=2, devices_per_host=2)
    # stages on devices 0,1 (host 0) then 2,3 (host 1): one crossing
    assert topo.cross_host_edges([0, 1, 2, 3]) == [(0, 1)]
    assert topo.cross_host_edges([0, 1]) == []
    # zig-zag placement crosses twice but each direction reported once
    assert topo.cross_host_edges([0, 2, 1, 3]) == [(0, 1), (1, 0)]
    assert "cross-host hops" in topo.describe([0, 1, 2, 3])


def test_file_barrier_rendezvous_and_timeout(tmp_path):
    d = str(tmp_path)
    a = FileBarrier(d, host_id=0, num_hosts=2)
    b = FileBarrier(d, host_id=1, num_hosts=2)
    done = []
    t = threading.Thread(target=lambda: (a.wait("s", timeout=10),
                                         done.append(0)))
    t.start()
    time.sleep(0.1)
    assert not done                              # host 1 not there yet
    b.wait("s", timeout=10)
    t.join(timeout=10)
    assert done == [0]
    with pytest.raises(BarrierTimeout) as ei:
        a.wait("t2", timeout=0.2, poll=0.02)
    assert ei.value.missing == [1]
    a.reset("t2")
    assert not any(n.startswith("t2.") for n in os.listdir(d))


# ---------------------------------------------------------------------------
# Event log + status reader
# ---------------------------------------------------------------------------

def test_event_log_roundtrip_and_torn_tail(tmp_path):
    path = str(tmp_path / "events.jsonl")
    log = EventLog(path)
    log.emit("launch", gen=0, hosts=2)
    log.emit("hostdown", gen=0, host=1, rc=42)
    with open(path, "a") as f:
        f.write('{"t": 1, "kind": "tor')          # crashed writer
    events = read_events(path)
    assert [e["kind"] for e in events] == ["launch", "hostdown"]
    assert events[1]["rc"] == 42 and events[0]["t"] > 0
    assert read_events(str(tmp_path / "missing.jsonl")) == []


def test_format_status_renders_events_and_heartbeats(tmp_path):
    run_dir = str(tmp_path)
    assert "(no events yet)" in format_status(run_dir)
    log = EventLog(os.path.join(run_dir, "events.jsonl"))
    log.emit("launch", gen=0, hosts=2)
    log.emit("rollback", gen=0, step=8, reason="hostdown")
    write_heartbeat(os.path.join(run_dir, "hb"),
                    Heartbeat(0, 7, "train", loss=2.5))
    out = format_status(run_dir)
    assert "rollback" in out and "step=8" in out
    assert "host 0" in out and "loss=2.5000" in out
    assert "launch x1" in out and "rollback x1" in out


# ---------------------------------------------------------------------------
# Concurrent multi-host checkpoint commit (satellite: GC vs writers race)
# ---------------------------------------------------------------------------

def test_concurrent_writers_gc_never_collects_inflight_step(tmp_path):
    out = run_helper("concurrent_ckpt.py", str(tmp_path), timeout=300)
    assert "CONCURRENT CKPT: ALL OK" in out


# ---------------------------------------------------------------------------
# End-to-end supervisor drill (real worker subprocesses, fp32 wire)
# ---------------------------------------------------------------------------

@pytest.fixture(scope="session")
def supervisor_drill_out():
    return run_helper("supervisor_drill.py", "hostdown", "hang",
                      timeout=1800)


def test_drill_hostdown_rollback_and_shrink(supervisor_drill_out):
    assert "hostdown: detect(hostdown) -> rollback(8) -> " \
        "shrink(dp=1 x P=2) -> resume OK" in supervisor_drill_out


def test_drill_hang_watchdog_detection(supervisor_drill_out):
    assert "hang: detect(hang) -> rollback(4) -> " \
        "shrink(dp=1 x P=2) -> resume OK" in supervisor_drill_out


def test_drill_all_ok(supervisor_drill_out):
    assert "SUPERVISOR DRILL: ALL OK" in supervisor_drill_out
