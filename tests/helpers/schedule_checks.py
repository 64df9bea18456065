"""Shared assertions: lowered step programs/tables vs ``Schedule.grid()``.

One source of truth for the "the lowering matches the schedule
slot-for-slot" contract, used by the in-process planning tests
(test_schedule, test_auto_pipeline) and the multi-device subprocess
equivalence helper (auto_pipeline_equiv).
"""
from repro.core.schedule import forward_ticks
from repro.runtime.schedule_exec import IDLE, RUN_DEC, RUN_ENC, StepTables


def assert_programs_match_grid(sched):
    """``Schedule.device_programs()`` equals ``grid()`` slot-for-slot."""
    progs = sched.device_programs()
    grid = sched.grid()
    assert progs.num_devices == sched.D
    assert progs.num_steps == sched.makespan
    for d in range(sched.D):
        for t in range(sched.makespan):
            p = grid[d][t]
            assert bool(progs.valid[d, t]) == (p is not None), (d, t)
            if p is None:
                assert progs.virtual[d, t] == -1
                assert progs.microbatch[d, t] == -1
            else:
                assert progs.virtual[d, t] == p.virtual, (d, t)
                assert progs.microbatch[d, t] == p.microbatch, (d, t)
    assert int(progs.valid.sum()) == len(sched.placements)
    return progs


def assert_step_tables_match_grid(sched, folded, device_of_stage=None):
    """The executor-facing ``StepTables`` cover exactly the schedule's
    forward placements, each at its tick (``forward_ticks``) with the
    right selector/microbatch/slot (the enc/dec boundary is S/2 — a device
    may hold V slots per kind), and each device runs them in the order
    the schedule gives them."""
    tabs = StepTables.from_schedule(sched, folded=folded,
                                    device_of_stage=device_of_stage)
    ticks = forward_ticks(sched)
    S = sched.S
    half = S // 2 if folded else S
    fwd = [p for p in sched.placements if p.virtual < S]
    for p in fwd:
        d, k = p.device, ticks[(p.virtual, p.microbatch)]
        want = RUN_DEC if folded and p.virtual >= half else RUN_ENC
        assert tabs.sel[d, k] == want, (d, k)
        assert tabs.mb[d, k] == p.microbatch, (d, k)
        assert 0 <= tabs.slot[d, k] < tabs.V, (d, k)
    assert int((tabs.sel != IDLE).sum()) == len(fwd)
    for d in range(sched.D):
        mine = sorted((p for p in fwd if p.device == d),
                      key=lambda p: p.step)
        assert [ticks[(p.virtual, p.microbatch)] for p in mine] == sorted(
            ticks[(p.virtual, p.microbatch)] for p in mine), d
    return tabs
