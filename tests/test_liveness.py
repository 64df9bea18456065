"""Liveness windows & channel activity: lowering == event-driven replay.

The lowering (``StepTables.from_schedule``) derives, by first-fit interval
coloring, the rotating-buffer windows W_down/W_up/W_turn/W_skip and the
per-step ring-activity masks the executors lower.  These property tests
cross-check every window against an INDEPENDENT event-driven replay of the
schedule (a message is in flight from the tick after its producer runs
until its consumer runs, inclusive; a stash entry from its write until its
last read; ticks from an independent dependency-driven timing), across
random valid schedules: greedy, duration-aware timed greedy in all
priority orientations, and (nightly) the exact ILP, for interleave
degrees V in {1, 2, 4}.

They also hold the planner and the executor to AGREE on the overlap
accounting: the planner-side ``core.schedule.comm_stats`` must report the
same windows, live-hop counts, and exposed/hidden hop split as the
executors' own lowering, and the double-buffered overlap mode never needs
buffers beyond the proven windows (exposed + hidden == live, per ring).
"""
import random

import pytest

from helpers.hypothesis_compat import given, settings, st
from repro.core.comm_model import overlap_accounting
from repro.core.partition import interleaved_wave_devices
from repro.core.schedule import (TIMED_PRIORITIES, comm_stats,
                                 greedy_schedule, greedy_schedule_timed,
                                 ilp_schedule, template_1f1b, template_wave,
                                 validate_schedule)
from repro.runtime.schedule_exec import StepTables


def asap_ticks(sched):
    """Independent timing oracle: every forward task's tick relaxed to the
    fixpoint of ``tick = max(device predecessor + 1, producer + 1)``, each
    device's tasks in the schedule's step order (a longest-path sweep, not
    the lowering's single pass in step order)."""
    fwd = [p for p in sched.placements if p.virtual < sched.S]
    order = {}
    for p in sorted(fwd, key=lambda p: p.step):
        order.setdefault(p.device, []).append((p.virtual, p.microbatch))
    tick = {(p.virtual, p.microbatch): 0 for p in fwd}
    changed = True
    while changed:
        changed = False
        for tasks in order.values():
            for i, (v, m) in enumerate(tasks):
                need = max(tick[tasks[i - 1]] + 1 if i else 0,
                           tick[(v - 1, m)] + 1 if v else 0)
                if need > tick[(v, m)]:
                    tick[(v, m)] = need
                    changed = True
    return tick


def replay_windows(sched, device_of_stage, folded):
    """Event-driven reference: max simultaneously-live entries per buffer.

    Deliberately brute force (per-tick overlap counting, no coloring, its
    own timing oracle) so a bug in the lowering's interval analysis cannot
    hide in a shared implementation.
    """
    S = sched.S
    half = S // 2 if folded else S
    fwd = [p for p in sched.placements if p.virtual < S]
    k_of = asap_ticks(sched)
    T = 1 + max(k_of.values())

    def peak(intervals_by_dev):
        best = 0
        for ivs in intervals_by_dev.values():
            for k in range(T):
                best = max(best, sum(1 for a, b in ivs if a <= k <= b))
        return best

    rings = {"down": {}, "up": {}}
    n_msgs = {"down": 0, "up": 0}
    n_exposed = {"down": 0, "up": 0}
    for p in fwd:
        v, m = p.virtual, p.microbatch
        if v >= S - 1 or (folded and v == half - 1):
            continue                       # loss stage / local turnaround
        ring = "down" if v < half else "up"
        dst = device_of_stage(v + 1)
        rings[ring].setdefault(dst, []).append(
            (k_of[(v, m)] + 1, k_of[(v + 1, m)]))
        n_msgs[ring] += 1
        # exposed = the consumer runs on the very next forward step, so
        # the overlapped executor has no compute to hide the hop under
        if k_of[(v + 1, m)] == k_of[(v, m)] + 1:
            n_exposed[ring] += 1

    turn = {}
    if folded:
        for m in range(sched.M):
            kw = k_of.get((half - 1, m))
            kr = k_of.get((half, m))
            if kw is not None and kr is not None:
                turn.setdefault(device_of_stage(half - 1), []).append(
                    (kw, kr))

    # conservative skip liveness: an encoder slot's stash entry lives from
    # its write until the device's LAST decoder task of that microbatch
    skip = {}
    if folded:
        last_dec = {}
        for p in fwd:
            if p.virtual >= half:
                key = (p.device, p.microbatch)
                k = k_of[(p.virtual, p.microbatch)]
                if last_dec.get(key, -1) < k:
                    last_dec[key] = k
        for p in fwd:
            if p.virtual < half:
                end = last_dec.get((p.device, p.microbatch))
                if end is not None:
                    skip.setdefault(p.device, []).append(
                        (k_of[(p.virtual, p.microbatch)], end))

    return {"W_down": peak(rings["down"]), "W_up": peak(rings["up"]),
            "W_turn": peak(turn), "W_skip": peak(skip),
            "n_down": n_msgs["down"], "n_up": n_msgs["up"],
            "x_down": n_exposed["down"], "x_up": n_exposed["up"], "T": T}


def _check(sched, device_of_stage, folded):
    tabs = StepTables.from_schedule(sched, folded=folded,
                                    device_of_stage=device_of_stage)
    ref = replay_windows(sched, device_of_stage, folded)
    assert tabs.num_steps == ref["T"], (tabs.num_steps, ref["T"])
    assert tabs.W_down == ref["W_down"], (tabs.W_down, ref)
    assert tabs.W_up == ref["W_up"], (tabs.W_up, ref)
    assert tabs.W_turn == ref["W_turn"], (tabs.W_turn, ref)
    assert tabs.W_skip == ref["W_skip"], (tabs.W_skip, ref)
    # the send masks mark exactly the hops that carry a message
    down, up = tabs.live_hops
    assert down == ref["n_down"] and up == ref["n_up"]
    assert down + up <= tabs.dense_hops
    # overlap accounting: every live hop is exposed or hidden, nothing
    # else — the double-buffered mode restructures WHEN hops are issued,
    # never how many, so it cannot widen the proven windows above
    assert tabs.exposed_down == ref["x_down"], (tabs.exposed_down, ref)
    assert tabs.exposed_up == ref["x_up"], (tabs.exposed_up, ref)
    assert tabs.exposed_hops + tabs.hidden_hops == down + up
    assert 0 <= tabs.hidden_hops
    # planner/executor agreement: the pure-python analysis the synthesizer
    # and tuner consult reports the identical windows + hop classification
    stats = comm_stats(sched, device_of_stage, folded)
    assert (stats.W_down, stats.W_up, stats.W_turn, stats.W_skip) == \
        (tabs.W_down, tabs.W_up, tabs.W_turn, tabs.W_skip)
    assert stats.live_hops == tabs.live_hops
    assert (stats.exposed_down, stats.exposed_up) == \
        (tabs.exposed_down, tabs.exposed_up)
    assert overlap_accounting(stats) == overlap_accounting(tabs)
    return tabs


def test_templates_windows_below_M():
    """Classic templates: the receive windows the lowering proves are far
    below the O(M) buffers the executors used to carry."""
    for D, M in [(2, 4), (4, 8), (4, 3)]:
        tabs = _check(template_wave(D, M),
                      lambda s, S=2 * D: min(s, S - 1 - s), True)
        assert tabs.W_down < M and tabs.W_up < M
        assert tabs.W_turn <= 2
    for D, M in [(2, 4), (4, 8)]:
        tabs = _check(template_1f1b(D, M), lambda s: s, False)
        assert tabs.W_down < M
        assert tabs.rings == 1 and tabs.W_up == 0 == tabs.W_turn


@given(st.integers(2, 4), st.integers(2, 5), st.sampled_from([1, 2, 4]),
       st.integers(0, 10_000))
@settings(max_examples=20, deadline=None)
def test_windows_match_replay_greedy_and_timed(D, M, V, seed):
    """Lowering-derived windows == event-driven replay for the greedy and
    all duration-aware timed-greedy schedules on interleaved folds."""
    rnd = random.Random(seed)
    S = 2 * V * D
    devices = interleaved_wave_devices(S, D)
    dev = lambda s: devices[s]
    _check(greedy_schedule(S, M, dev, D), dev, True)
    times = [rnd.uniform(0.1, 2.0) for _ in range(S)]
    for prio in TIMED_PRIORITIES:
        sched = greedy_schedule_timed(S, M, dev, D, times, priority=prio,
                                      p2p_time=rnd.uniform(0.0, 0.3))
        assert not validate_schedule(sched, dev)
        _check(sched, dev, True)


@given(st.integers(2, 4), st.integers(2, 5), st.sampled_from([1, 2]),
       st.integers(0, 10_000))
@settings(max_examples=15, deadline=None)
def test_windows_match_replay_linear(D, M, V, seed):
    """Same cross-check on linear S = VD schedules (down ring only)."""
    rnd = random.Random(seed)
    S = V * D
    dev = lambda s: s % D
    tabs = _check(greedy_schedule(S, M, dev, D), dev, False)
    assert tabs.W_up == 0 and tabs.W_skip == 0
    times = [rnd.uniform(0.1, 2.0) for _ in range(S)]
    sched = greedy_schedule_timed(S, M, dev, D, times, priority="backward")
    _check(sched, dev, False)


def test_sparse_skip_consumers_shrink_window():
    """Layout-derived skip_consumers elide dead stores: an encoder slot no
    decoder row consumes is never written and the skip window shrinks
    below the conservative all-slots analysis."""
    D, M = 2, 4
    sched = template_wave(D, M)
    dev = lambda s, S=2 * D: min(s, S - 1 - s)
    conservative = StepTables.from_schedule(sched, folded=True,
                                            device_of_stage=dev)
    none_consumed = StepTables.from_schedule(
        sched, folded=True, device_of_stage=dev,
        skip_consumers=(((),), ((),)))
    assert none_consumed.W_skip == 0
    assert not none_consumed.skip_wr.any()
    assert conservative.W_skip > 0
    with pytest.raises(ValueError, match="skip_consumers"):
        StepTables.from_schedule(sched, folded=True, device_of_stage=dev,
                                 skip_consumers=(((),),))   # wrong shape
    with pytest.raises(ValueError, match="enc slot"):
        StepTables.from_schedule(sched, folded=True, device_of_stage=dev,
                                 skip_consumers=(((7,),), ((0,),)))


@pytest.mark.slow
@given(st.integers(2, 3), st.integers(2, 3), st.integers(0, 1000))
@settings(max_examples=3, deadline=None)
def test_windows_match_replay_ilp(D, M, seed):
    """Exact ILP schedules (Eqs. 6-13) through the same cross-check —
    liveness analysis is schedule-shape-agnostic, not greedy-specific."""
    S = 2 * D
    dev = lambda s: min(s, S - 1 - s)
    colloc = [(s, S - 1 - s) for s in range(D)]
    sched = ilp_schedule(S, M, D, device_of_stage=dev, collocated=colloc)
    assert not validate_schedule(sched, dev, collocated=colloc)
    _check(sched, dev, True)
