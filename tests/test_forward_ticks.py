"""The executors' forward program is timed by its dependencies.

``core.schedule.forward_ticks`` lays each device's forward placements onto
the scan's ticks in the schedule's per-device order, each as early as its
device is free and its producer ran a tick before.  The schedule's slots
for backward placements then leave no idle tick: a folded V = 1 greedy
wave runs in 2M + 2(D - 1) ticks, the fill and the drain.
"""
import pytest

from repro.core.partition import interleaved_wave_devices
from repro.core.schedule import forward_ticks, greedy_schedule
from repro.runtime.schedule_exec import IDLE, StepTables


def _fold(D):
    return interleaved_wave_devices(2 * D, D).__getitem__


def _schedule_steps(sched):
    """The old timing: each forward placement at its rank among the
    schedule's steps that hold a forward placement."""
    fwd = [p for p in sched.placements if p.virtual < sched.S]
    rank = {t: k for k, t in enumerate(sorted({p.step for p in fwd}))}
    return {(p.virtual, p.microbatch): rank[p.step] for p in fwd}


def _check_timing(sched, ticks):
    fwd = [p for p in sched.placements if p.virtual < sched.S]
    assert set(ticks) == {(p.virtual, p.microbatch) for p in fwd}
    busy = set()
    for p in fwd:
        k = ticks[(p.virtual, p.microbatch)]
        # at most one task per device per tick
        assert (p.device, k) not in busy, (p.device, k)
        busy.add((p.device, k))
        # every consumer runs at least one tick after its producer
        if p.virtual > 0:
            assert k >= ticks[(p.virtual - 1, p.microbatch)] + 1, p
    # each device's forward order is the schedule's
    for d in range(sched.D):
        mine = sorted((p for p in fwd if p.device == d), key=lambda p: p.step)
        got = [ticks[(p.virtual, p.microbatch)] for p in mine]
        assert got == sorted(got), d


@pytest.mark.parametrize("D, M", sorted({(D, M) for D in (1, 2, 4)
                                         for M in (1, D, 2 * D)}))
def test_folded_greedy_reaches_fill_plus_drain(D, M):
    """Folded V = 1 greedy waves: T = 2M + 2(D - 1), the order, the
    dependencies and device exclusivity hold, and the lowered tables run
    exactly those ticks."""
    sched = greedy_schedule(2 * D, M, _fold(D), D)
    ticks = forward_ticks(sched)
    _check_timing(sched, ticks)
    T = 1 + max(ticks.values())
    assert T == 2 * M + 2 * (D - 1)
    assert T <= 1 + max(_schedule_steps(sched).values())
    tabs = StepTables.from_schedule(sched, folded=True,
                                    device_of_stage=_fold(D))
    assert tabs.num_steps == T
    assert (tabs.running_steps == 2 * M).all()
    for p in sched.placements:
        if p.virtual < sched.S:
            k = ticks[(p.virtual, p.microbatch)]
            assert tabs.sel[p.device, k] != IDLE
            assert tabs.mb[p.device, k] == p.microbatch


@pytest.mark.parametrize("D, M", [(1, 1), (1, 2), (1, 4), (2, 1), (2, 2),
                                  (4, 1), (4, 4)])
def test_no_gap_keeps_schedule_steps(D, M):
    """Where the schedule's B slots leave no forward gap (one device, or
    M <= D), the ticks are the schedule's forward steps: the lowered
    program is the one the schedule's slots gave."""
    sched = greedy_schedule(2 * D, M, _fold(D), D)
    assert forward_ticks(sched) == _schedule_steps(sched)


@pytest.mark.parametrize("kind, D, M", [
    ("wave-v2", 2, 2), ("wave-v2", 2, 4), ("wave-v2", 4, 8),
    ("linear", 2, 4), ("linear", 4, 8), ("linear-v2", 2, 4),
    ("linear-v2", 4, 8)])
def test_never_longer_than_schedule_steps(kind, D, M):
    """Interleaved and linear plans: the re-timing keeps every dependency
    and order and is never longer than the schedule's forward steps."""
    V = 2 if kind.endswith("v2") else 1
    if kind.startswith("wave"):
        S = 2 * V * D
        devices = interleaved_wave_devices(S, D)
        dev = lambda s: devices[s]
    else:
        S = V * D
        dev = lambda s: s % D
    sched = greedy_schedule(S, M, dev, D)
    ticks = forward_ticks(sched)
    _check_timing(sched, ticks)
    assert max(ticks.values()) <= max(_schedule_steps(sched).values())
    tabs = StepTables.from_schedule(sched, folded=kind.startswith("wave"),
                                    device_of_stage=dev)
    assert tabs.num_steps == 1 + max(ticks.values())
