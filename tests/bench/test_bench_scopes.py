"""The attribution of a trace's device operations to the program's named
scopes, layers and passes (``bench/scopes.py``), and the outputs of
``bench/trace.py::reduce`` on the recorded chip trace, pinned."""
import json
import os

import pytest

from bench import scopes as sc
from bench import trace as tr

FIXTURES = os.path.join(os.path.dirname(__file__), "fixtures")
FWD = "jit(step)/jvp()/pulse.executor/while/body/"
BWD = "jit(step)/transpose(jvp())/pulse.executor/while/body/"
REMAT = BWD + "checkpoint/rematted_computation/"

HAND = {
    "devices": {
        "/device:TPU:0": [
            # the scan's while spans its body's operations
            ("while.1", 0, 100, "jit(step)/jvp()/pulse.executor/while"),
            ("fusion.1", 0, 20, FWD + "pulse.stage_dec/attention/dot"),
            ("fusion.2", 20, 30, REMAT + "pulse.stage_enc/mlp/dot"),
            ("fusion.3", 30, 45, BWD + "pulse.stage_enc/attention/exp"),
            ("collective-permute-done.4", 40, 55,
             FWD + "pulse.hop/ppermute"),
            ("fusion.5", 50, 60, FWD + "pulse.stash/dynamic_update_slice"),
            ("select.6", 60, 70, FWD + "select_n"),
            ("fusion.7", 70, 80, "jit(step)/pulse.optimizer/mul"),
            ("fusion.8", 80, 90, "jit(step)/jvp(jit(_uniform))/add"),
            ("fusion.9", 95, 130, FWD + "pulse.stage_dec/add"),  # clipped
            ("fusion.10", 150, 160, FWD + "pulse.stage_dec/add"),  # outside
        ],
        "/device:TPU:1": [
            ("fusion.1", 10, 50, FWD + "pulse.stage_enc/mlp/dot"),
            ("all-reduce.2", 50, 70,
             "jit(step)/jvp()/pulse.executor/pulse.loss_allreduce/psum"),
        ],
    },
    "host": [("traced_window", 0, 100), ("train", 0, 50),
             ("train", 50, 100), ("batch", 1, 2)],
}


def rows(tab, dev):
    return {tuple(r[:4]): pytest.approx(r[4])
            for r in tab["devices"][dev]["rows"]}


def test_each_operation_counts_once_by_layer_scope_and_pass():
    tab = sc.table(HAND)
    assert tab["window_s"] == pytest.approx(100e-9)
    assert tab["steps"] == 2
    assert rows(tab, "/device:TPU:0") == {
        # attention nests inside the decoder stage and counts under both
        ("stage", "attention", "fwd", "compute"): 20e-9,
        ("stage", "mlp", "recompute", "compute"): 10e-9,
        ("stage", "attention", "bwd", "compute"): 15e-9,
        # the hop is a collective, of the executor's layer
        ("executor", "pulse.hop", "fwd", "collective"): 15e-9,
        ("executor", "pulse.stash", "fwd", "compute"): 10e-9,
        ("executor", "pulse.executor", "fwd", "compute"): 10e-9,
        ("optimizer", "pulse.optimizer", "fwd", "compute"): 10e-9,
        ("other", "other", "fwd", "compute"): 10e-9,
        # clipped to the window's end; fusion.10 lies outside it
        ("stage", "pulse.stage_dec", "fwd", "compute"): 5e-9,
    }
    d0 = tab["devices"]["/device:TPU:0"]
    assert d0["compute_s"] == pytest.approx(90e-9)
    assert d0["busy_s"] == pytest.approx(95e-9)
    # the hop's [40, 55] runs beside compute but for [45, 50]
    assert d0["hop_exposed_s"] == pytest.approx(5e-9)
    d1 = tab["devices"]["/device:TPU:1"]
    assert d1["compute_s"] == pytest.approx(40e-9)
    # a collective that is not a hop is no hop wait
    assert d1["hop_exposed_s"] == 0.0


def test_per_step_averages_over_devices_and_leaves_collectives_out():
    ms = sc.per_step(sc.table(HAND), 2)
    per = 1e-6 / 2 / 2          # ns -> ms, over 2 steps and 2 devices
    assert ms["stage_ms"] == pytest.approx((50 + 40) * per)
    assert ms["executor_ms"] == pytest.approx(20 * per)
    assert ms["optimizer_ms"] == pytest.approx(10 * per)
    assert ms["other_ms"] == pytest.approx(10 * per)
    assert ms["attention_ms"] == pytest.approx(35 * per)
    assert ms["compute_ms"] == pytest.approx((90 + 40) * per)
    assert ms["hop_exposed_ms"] == pytest.approx(5 * per)
    # stage, executor, optimizer and other sum to the compute union here,
    # where no two compute operations overlap
    assert sum(ms[f"{lay}_ms"] for lay in sc.LAYERS) == pytest.approx(
        ms["compute_ms"])


def test_without_a_window_the_trace_spans_all_operations():
    raw = {"devices": {"/device:TPU:0": [
        ("fusion.1", 10, 20, FWD + "pulse.stage_enc/add"),
        ("fusion.2", 30, 50, "jit(step)/pulse.optimizer/mul")]},
        "host": []}
    tab = sc.table(raw)
    assert tab["window_s"] == pytest.approx(40e-9)
    assert tab["steps"] == 0
    assert sc.table({"devices": {"/device:TPU:0": []}, "host": []}) is None


def test_op_names_come_from_the_traces_hlo_modules(tmp_path):
    """Each device operation's op_name is its instruction's in the HLO
    module of its program, which the trace's metadata plane holds; a copy
    the compiler put in a loop body takes the loop's."""
    Space, Hlo = sc._protos()
    hlo = Hlo()
    entry = hlo.hlo_module.computations.add(id=1)
    entry.instructions.add(name="fusion.1").metadata.op_name = (
        FWD + "pulse.stage_enc/mlp/dot")
    loop = entry.instructions.add(name="while.3", called_computation_ids=[2])
    loop.metadata.op_name = "jit(step)/jvp()/pulse.executor/while"
    hlo.hlo_module.computations.add(id=2).instructions.add(name="copy.2")
    space = Space()
    meta = space.planes.add(name="/host:metadata")
    e = meta.event_metadata.add(key=1)
    e.value.name = "jit_step(77)"
    e.value.stats.add(metadata_id=1, bytes_value=hlo.SerializeToString())
    dev = space.planes.add(name="/device:TPU:0")
    dev.stat_metadata.add(key=5).value.name = "program_id"
    for key, (name, pid) in enumerate([
            ("%fusion.1 = bf16[8]{0} fusion(%p), kind=kLoop", 77),
            ("%copy.2 = bf16[8]{0} copy(%p)", 77),
            ("%fusion.1 = f32[] fusion(%q), kind=kLoop", 78)]):  # unknown
        m = dev.event_metadata.add(key=key)
        m.value.name = name
        m.value.stats.add(metadata_id=5, uint64_value=pid)
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(space.SerializeToString())
    assert sc.op_names(str(path)) == {"/device:TPU:0": {
        "%fusion.1 = bf16[8]{0} fusion(%p), kind=kLoop":
            FWD + "pulse.stage_enc/mlp/dot",
        "%copy.2 = bf16[8]{0} copy(%p)":
            "jit(step)/jvp()/pulse.executor/while"}}


def test_attribute_reads_layer_scope_and_pass():
    assert sc.attribute(FWD + "cond/pulse.stage_dec/skip_proj/dot") == (
        "stage", "skip_proj", "fwd")
    assert sc.attribute(REMAT + "pulse.stage_enc/attention/exp") == (
        "stage", "attention", "recompute")
    assert sc.attribute(BWD + "pulse.stash/dynamic_update_slice") == (
        "executor", "pulse.stash", "bwd")
    assert sc.attribute(FWD + "pulse.stage_enc/pulse.embed/dot") == (
        "stage", "pulse.embed", "fwd")
    # a model part counts as stage work where the stage's scope is lost
    assert sc.attribute(REMAT + "cond/attention/broadcast_in_dim") == (
        "stage", "attention", "recompute")
    assert sc.attribute("jit(step)/pulse.optimizer/sqrt") == (
        "optimizer", "pulse.optimizer", "fwd")
    assert sc.attribute("") == ("other", "other", "fwd")


def test_format_table_names_every_device_and_scope():
    tab = sc.table(HAND)
    text = sc.format_table(tab, 2)
    for dev in HAND["devices"]:
        assert dev in text
    for scope in ("attention", "pulse.hop", "pulse.stash", "other"):
        assert scope in text


def test_trace_reduce_is_unchanged_on_the_recorded_chip_trace():
    """Every number ``bench/trace.py::reduce`` gives on the recorded cut of
    a cell ``uvit_h8.r32.b32`` trace, as it gave before the scopes were
    added."""
    with open(os.path.join(FIXTURES, "v5e_trace_cut.json")) as f:
        r = tr.reduce(json.load(f))
    assert r["devices"] == 1
    assert r["window_s"] == pytest.approx(0.000210996, rel=1e-12)
    assert r["busy_s"] == pytest.approx(0.000199897, rel=1e-12)
    assert r["idle_share"] == pytest.approx(0.052602892945837865,
                                            rel=1e-12)
    assert r["collective_exposed_s"] == 0.0
    assert r["collective_ops"] == 0
    assert [n for n, _ in r["device_ops"]] == [
        "%fusion.129 fusion", "%copy-done.45 copy-done", "%fusion.48 fusion",
        "%fusion.51 fusion", "%fusion.137 fusion", "%fusion.151 fusion",
        "%fusion.219 fusion", "%fusion.201 fusion", "%fusion.157 fusion",
        "%fusion.207 fusion"]
    assert [t for _, t in r["device_ops"]] == pytest.approx(
        [7.3364e-05, 3.4767e-05, 3.4582e-05, 3.0609e-05, 1.0473e-05,
         1.85e-06, 1.652e-06, 1.526e-06, 1.436e-06, 1.316e-06], rel=1e-12)
    assert r["idle_gaps"][0] == ["between_spans",
                                 pytest.approx(1.0996e-05, rel=1e-12)]
    assert r["idle_gaps"][1:] == [["between_spans",
                                   pytest.approx(2e-09, rel=1e-12)]] * 9
    assert set(r) == {"devices", "window_s", "busy_s", "idle_share",
                      "collective_exposed_s", "collective_ops",
                      "device_ops", "idle_gaps"}


def test_real_four_chip_trace_names_stages_executor_and_hops():
    """A cut of a cell ``uvit_h32.p4.r32.b32`` trace on four v5e chips,
    traced with the program's scopes: every device shows stage and
    executor compute and a hop, and the layers' sums are the compute
    union."""
    with open(os.path.join(FIXTURES, "v5e4_scopes_cut.json")) as f:
        tab = sc.table(json.load(f))
    assert len(tab["devices"]) == 4
    for dev, d in tab["devices"].items():
        kinds = {(r[0], r[1], r[3]) for r in d["rows"]}
        assert any(k[0] == "stage" and k[2] == "compute" for k in kinds), dev
        assert ("executor", sc.EXECUTOR, "compute") in kinds, dev
        assert ("executor", sc.HOP, "collective") in kinds, dev
        assert d["hop_exposed_s"] > 0
    ms = sc.per_step(tab, 1)
    assert sum(ms[f"{lay}_ms"] for lay in sc.LAYERS) == pytest.approx(
        ms["compute_ms"], rel=0.01)
    assert ms["other_ms"] < 0.05 * ms["compute_ms"]
