"""The reduction of a profiler trace to busy time, idle share, exposed
collective time and the breakdown."""
import pytest

from bench import trace as tr


def test_union_and_minus():
    assert tr.union([(5, 7), (0, 2), (1, 3), (7, 8)]) == [(0, 3), (5, 8)]
    assert tr.minus([(0, 10)], [(2, 3), (5, 12)]) == [(0, 2), (3, 5)]
    assert tr.minus([(0, 4), (6, 9)], [(3, 7)]) == [(0, 3), (7, 9)]
    assert tr.minus([(0, 4)], []) == [(0, 4)]
    assert tr.length(tr.union([(0, 5), (3, 9)])) == 9


HAND = {
    "devices": {
        "/device:TPU:0": [["fusion.1", 0, 40],
                          ["collective-permute-done.3", 40, 50],
                          ["fusion.2", 45, 60],
                          ["fusion.1", 200, 260]],        # outside
        "/device:TPU:1": [["fusion.1", 10, 90]],
    },
    "host": [["traced_window", 0, 100], ["dispatch", 55, 70],
             ["loss_read", 70, 100]],
}


def test_hand_made_trace():
    r = tr.reduce(HAND)
    assert r["devices"] == 2
    assert r["window_s"] == pytest.approx(100e-9)
    # busy: device 0 the union [0, 60], device 1 [10, 90]
    assert r["busy_s"] == pytest.approx(70e-9)
    assert r["idle_share"] == pytest.approx(0.3)
    # the permute's [40, 50] is covered by compute after 45
    assert r["collective_exposed_s"] == pytest.approx(2.5e-9)
    assert r["collective_ops"] == 1
    assert r["device_ops"][0] == ["fusion.1", pytest.approx(60e-9)]
    # device 0 idles [60, 100] under loss_read; device 1 [0, 10], [90, 100]
    assert r["idle_gaps"][0] == ["loss_read", pytest.approx(40e-9)]
    assert sorted(g[0] for g in r["idle_gaps"]) == [
        "between_spans", "loss_read", "loss_read"]


NESTED = {
    # a scan's while loop on the ops line, its body's operations inside it
    "devices": {"/device:TPU:0": [
        ["while.7", 0, 100],
        ["fusion.1", 0, 30],
        ["collective-permute-done.2", 30, 50],
        ["conditional.3", 60, 100],
        ["fusion.4", 60, 100],
    ]},
    "host": [["traced_window", 0, 100]],
}


def test_leaves_drop_the_operations_that_contain_others():
    assert sorted(n for n, _, _ in tr.leaves(NESTED["devices"][
        "/device:TPU:0"])) == ["collective-permute-done.2", "fusion.1",
                               "fusion.4"]
    # an event overlapping another's end, not inside it, leaves both
    assert len(tr.leaves([("a", 0, 10), ("b", 5, 15)])) == 2
    assert tr.leaves([("a", 0, 10), ("b", 3, 3)]) == [("a", 0, 10)]


def test_nested_control_flow_hides_neither_idle_nor_collectives():
    r = tr.reduce(NESTED)
    assert r["busy_s"] == pytest.approx(90e-9)
    assert r["idle_share"] == pytest.approx(0.1)
    assert r["collective_exposed_s"] == pytest.approx(20e-9)
    assert [n for n, _ in r["device_ops"]] == [
        "fusion.4", "fusion.1", "collective-permute-done.2"]


def test_no_device_operation_reads_nothing():
    assert tr.reduce({"devices": {"/device:TPU:0": []}, "host": []}) is None



def test_real_v5e_trace_cut():
    """A cut of a real trace of cell ``uvit_h8.r32.b32`` on a TPU v5e: the
    step's ``conditional`` spans the whole cut, and the 10.996 us in which
    none of its operations runs is idle, not hidden by it."""
    import json
    import os

    path = os.path.join(os.path.dirname(__file__), "fixtures",
                        "v5e_trace_cut.json")
    with open(path) as f:
        raw = json.load(f)
    ops = raw["devices"]["/device:TPU:0"]
    (lo, hi), = [(s, e) for n, s, e in raw["host"] if n == tr.WINDOW]
    spans = [(s, e) for n, s, e in ops if n.endswith(" conditional")]
    assert spans and all(s <= lo and e >= hi for s, e in spans)
    r = tr.reduce(raw)
    assert r["idle_gaps"][0][1] == pytest.approx(10.996e-6)
    # and gaps of a few ns between its operations, 0.103 us in all
    idle = r["window_s"] - r["busy_s"]
    assert idle == pytest.approx(11.099e-6)
    assert r["idle_share"] == pytest.approx(idle / r["window_s"])
    assert r["collective_ops"] == 0
    assert not any(n.endswith(" conditional") for n, _ in r["device_ops"])
