"""Auto-pipeline compile path: planning invariants + differential tests.

Planning-layer tests run in-process on one device.  Numerical equivalence
against the single-device reference (and, differentially, against the
closed-form executors) runs in a subprocess with 8 forced host devices
(tests/helpers/auto_pipeline_equiv.py): the uneven-partition configs — the
capability the hand-written executors lacked — and the M < D config only
the table-driven lowering can run are tier-1; the even S=D / S=2D configs
and the ILP schedule are `slow`.
"""
import dataclasses

import jax
import numpy as np
import pytest

from helpers import run_helper

from repro.core.partition import partition
from repro.core.schedule import schedule_for_partition, validate_schedule
from repro.core.tuner import tune
from repro.models.diffusion import UViTConfig, uvit_pipeline_graph
from repro.models.layers import AttnConfig
from repro.models.lm import LMConfig, lm_pipeline_graph
from repro.runtime.adapters import diffusion_model_fns, lm_model_fns
from repro.runtime.compile import StageLayout, auto_pipeline
from repro.runtime.schedule_exec import StepTables

from helpers.schedule_checks import (assert_programs_match_grid,
                                     assert_step_tables_match_grid)

def _run_equiv(*configs):
    out = run_helper("auto_pipeline_equiv.py", *configs)
    assert "AUTO PIPELINE EQUIVALENCE: ALL OK" in out
    return out


# ---------------------------------------------------------------------------
# planning layer (fast, single device)
# ---------------------------------------------------------------------------

def _lm_cfg():
    return LMConfig(name="t", vocab=64, d_model=32, n_layers=8,
                    attn=AttnConfig(32, 4, 2, 8), d_ff=64,
                    tied_embeddings=True)


def _uvit_cfg():
    return UViTConfig("t", img_size=8, in_ch=4, patch=2, d_model=32,
                      n_layers=8, n_heads=4, d_ff=64, n_classes=10)


def test_auto_pipeline_schedule_validates():
    """Every lowered plan ships with a schedule that passes all six
    constraint families for its own stage->device mapping."""
    for cp in (
        auto_pipeline(lm_pipeline_graph(_lm_cfg()), lm_model_fns(_lm_cfg()),
                      4, pipeline_devices=4, microbatches=4),
        auto_pipeline(uvit_pipeline_graph(_uvit_cfg()),
                      diffusion_model_fns(_uvit_cfg(), "uvit"),
                      2, pipeline_devices=2, microbatches=4),
    ):
        part = cp.partition
        errs = validate_schedule(cp.schedule, part.device_of_stage,
                                 collocated=part.collocated_pairs())
        assert not errs
        assert cp.schedule.M == cp.pcfg.num_microbatches
        assert cp.schedule.D == part.num_devices


def test_auto_pipeline_uneven_partition_plan():
    cfg = _lm_cfg()
    g = lm_pipeline_graph(cfg, fwd_times=[4, 1, 1, 1, 1, 1, 1, 4])
    cp = auto_pipeline(g, lm_model_fns(cfg), 4, pipeline_devices=4,
                       microbatches=4, lam=0.0)
    assert len(set(cp.layout.counts)) > 1          # genuinely uneven
    assert sum(cp.layout.counts) == g.n
    assert cp.partition.objective <= 4.0 + 1e-9    # balanced around block 0/7


def test_layout_split_merge_roundtrip():
    """split_params -> merge_params is the identity on real parameters,
    including uneven and folded layouts (this is the same path gradients
    take back to model form)."""
    key = jax.random.PRNGKey(0)
    cfg = _lm_cfg()
    cases = [
        auto_pipeline(lm_pipeline_graph(cfg,
                                        fwd_times=[4, 1, 1, 1, 1, 1, 1, 4]),
                      lm_model_fns(cfg), 4, pipeline_devices=4,
                      microbatches=4, lam=0.0),
        auto_pipeline(uvit_pipeline_graph(_uvit_cfg(),
                                          fwd_times=[3, 1, 1, 1, 1, 1, 1, 3]),
                      diffusion_model_fns(_uvit_cfg(), "uvit"), 2,
                      pipeline_devices=2, microbatches=4, lam=0.0),
    ]
    for cp in cases:
        assert len(set(cp.layout.counts)) > 1    # the hard (padded) layouts
        params = cp.model_fns.init_fn(key)
        stacks, edge = cp.split_params(params)
        back = cp.merge_params(stacks, edge)
        for a, b in zip(jax.tree.leaves(params), jax.tree.leaves(back)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_tuner_choice_carries_partition():
    g = uvit_pipeline_graph(_uvit_cfg())
    choices = tune(g, 4)
    assert choices
    for c in choices:
        assert c.partition is not None
        if c.P > 1:
            assert c.partition.num_devices == c.P


def test_tuner_driven_auto_pipeline():
    """Without a pinned pipeline degree the tuner supplies the plan."""
    g = uvit_pipeline_graph(_uvit_cfg())
    cp = auto_pipeline(g, diffusion_model_fns(_uvit_cfg(), "uvit"), 4,
                       microbatches=4)
    assert cp.choice is not None and cp.choice.P > 1
    assert cp.partition is cp.choice.partition
    assert not validate_schedule(cp.schedule, cp.partition.device_of_stage,
                                 collocated=cp.partition.collocated_pairs())


def test_tuner_driven_executes_scored_microbatch_count():
    """The tuner records the M its iteration-time score assumed
    (TunerChoice.M) and auto_pipeline executes exactly that M — previously
    the tuner scored M = P while the executor silently ran M = 2D."""
    g = uvit_pipeline_graph(_uvit_cfg())
    choices = tune(g, 4)
    for c in choices:
        assert c.M == max(c.P, 1)          # Eq. (15)'s closed-form setting
    cp = auto_pipeline(g, diffusion_model_fns(_uvit_cfg(), "uvit"), 4)
    assert cp.choice is not None
    assert cp.pcfg.num_microbatches == cp.choice.M
    assert cp.schedule.M == cp.choice.M


def test_device_programs_match_grid():
    """Schedule.device_programs() agrees with grid() slot-for-slot, and the
    executor-facing StepTables cover exactly the forward placements."""
    for cp in (
        auto_pipeline(lm_pipeline_graph(_lm_cfg()), lm_model_fns(_lm_cfg()),
                      4, pipeline_devices=4, microbatches=4),
        auto_pipeline(uvit_pipeline_graph(_uvit_cfg()),
                      diffusion_model_fns(_uvit_cfg(), "uvit"),
                      2, pipeline_devices=2, microbatches=4),
    ):
        assert_programs_match_grid(cp.schedule)
        tabs = assert_step_tables_match_grid(cp.schedule, cp.folded)
        assert all(p.step in tabs.forward_steps
                   for p in cp.schedule.placements
                   if p.virtual < cp.schedule.S)


def test_step_tables_reject_infeasible_schedule():
    """A schedule whose consumer runs before its input can arrive (or whose
    shape does not fit the executor) raises at lowering, not mid-scan."""
    from repro.core.schedule import Schedule, template_1f1b

    good = template_1f1b(2, 2)
    with pytest.raises(ValueError, match="folded|linear"):
        StepTables.from_schedule(good, folded=True)   # S=D, not S=2D

    # shift microbatch 0's stage-1 F to step 0: before its input exists
    bad_places = tuple(
        dataclasses.replace(p, step=0)
        if (p.virtual, p.microbatch) == (1, 0) else p
        for p in good.placements)
    bad = Schedule(good.S, good.M, good.D, bad_places)
    with pytest.raises(ValueError):
        StepTables.from_schedule(bad, folded=False)

    out_of_range = Schedule(good.S, good.M, good.D, tuple(
        dataclasses.replace(p, device=7)
        if (p.virtual, p.microbatch) == (0, 0) else p
        for p in good.placements))
    with pytest.raises(ValueError, match="validate_schedule"):
        StepTables.from_schedule(out_of_range, folded=False)
    assert any("out of range" in e for e in validate_schedule(out_of_range))

    # a *valid* schedule with a permuted stage->device mapping (what an ILP
    # free-mapping solve can legally return) is not realizable on the
    # executors' canonical stage layout — must raise, not run the wrong
    # stage's parameters silently
    from repro.core.schedule import greedy_schedule
    swapped = greedy_schedule(2, 2, lambda s: 1 - s, 2)
    assert not validate_schedule(swapped, lambda s: 1 - s)
    with pytest.raises(ValueError, match="stage layout"):
        StepTables.from_schedule(swapped, folded=False)


def test_closed_form_wave_rejects_short_iterations():
    """M < D folded plans lower through the table executor; the closed-form
    wave executor must refuse them with an actionable error."""
    cfg = _uvit_cfg()
    cp = auto_pipeline(uvit_pipeline_graph(cfg),
                       diffusion_model_fns(cfg, "uvit"), 4,
                       pipeline_devices=4, microbatches=3)
    assert cp.pcfg.num_microbatches == 3 < cp.pcfg.num_devices
    cp.build()                                        # table path: fine
    with pytest.raises(ValueError, match="M >= D"):
        dataclasses.replace(cp, executor="closed_form").build()
    with pytest.raises(ValueError, match="executor"):
        dataclasses.replace(cp, executor="wat").build()


def _asym_skipvit():
    """make_unet_like(3, 2)-shaped model whose costs force a
    mirror-ASYMMETRIC fold (turnaround cut inside the bottleneck run)."""
    from repro.models.diffusion import SkipViTConfig, skipvit_pipeline_graph
    cfg = SkipViTConfig("t", n_enc=3, n_mid=2, n_dec=3)
    return cfg, skipvit_pipeline_graph(cfg, fwd_times=[1, 1, 4, .5, .5, .5, 1, 1])


def test_layout_accepts_asymmetric_fold():
    """StageLayout.from_partition no longer raises on legal asymmetric
    folds: independent enc/dec counts and the stash pairing come from the
    partition's actual skip edges."""
    from repro.core.graph import make_unet_like
    cfg, g = _asym_skipvit()
    part = partition(g, 2, lam=0.0)
    assert part.folded and not part.mirror_symmetric()
    assert part.validate_collocation(g)
    layout = StageLayout.from_partition(part, g)
    assert layout.V == 1
    assert layout.enc_counts != layout.dec_counts
    assert (sum(c for cs in layout.enc_counts for c in cs)
            + sum(c for cs in layout.dec_counts for c in cs) == g.n)
    # every skip edge resolved to a stash row; skip-less rows are -1
    n_paired = sum(1 for dev in layout.skip_rows for row in dev
                   for r in row if r >= 0)
    assert n_paired == len(g.skips)
    # the synthetic acceptance graph partitions and lays out as well
    g2 = make_unet_like(3, 2)
    part2 = partition(g2, 2, lam=0.0)
    StageLayout.from_partition(part2, g2)


def test_asymmetric_fold_compiles_through_auto_pipeline():
    from repro.runtime.adapters import skipvit_model_fns
    cfg, g = _asym_skipvit()
    cp = auto_pipeline(g, skipvit_model_fns(cfg), 2, pipeline_devices=2,
                       microbatches=4, lam=0.0)
    assert not cp.partition.mirror_symmetric()
    assert not validate_schedule(cp.schedule, cp.partition.device_of_stage,
                                 collocated=cp.partition.collocated_pairs())
    cp.build()                       # lowers without a mirror gate
    # split/merge roundtrip on the asymmetric layout (the gradient path)
    key = jax.random.PRNGKey(0)
    params = cp.model_fns.init_fn(key)
    stacks, edge = cp.split_params(params)
    back = cp.merge_params(stacks, edge)
    for a, b in zip(jax.tree.leaves(params), jax.tree.leaves(back)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_layout_rejects_malformed_folds():
    """Genuinely unliftable shapes still raise: non-paired device mappings
    and skip edges that do not cross the fold."""
    import dataclasses as dc
    from repro.core.graph import BlockGraph, SkipEdge
    part = partition(lm_pipeline_graph(_lm_cfg()), 4)  # linear (no skips)
    assert StageLayout.from_partition(part).counts  # linear fine
    # identity device mapping marked folded: no enc/dec stage pairing
    bad = dc.replace(part, cuts=(0, 1, 2, 5, 8), folded=True)
    with pytest.raises(ValueError):
        StageLayout.from_partition(bad)
    # legal asymmetric cuts but a skip whose endpoints sit on one side
    cfg, g = _asym_skipvit()
    good = partition(g, 2, lam=0.0)
    g_bad = BlockGraph(g.blocks, g.skips + (SkipEdge(6, 7, 1),))
    with pytest.raises(ValueError, match="encoder-half|collocation"):
        StageLayout.from_partition(good, g_bad)
    # mirror-asymmetric fold without a graph: no pairing derivable
    with pytest.raises(ValueError, match="graph"):
        StageLayout.from_partition(good)


def test_hunyuan_config_plans_through_auto_pipeline():
    """configs/hunyuan_dit wires the paper's own model through the compile
    path: the full-size config plans, schedules and lays out (planning is
    host-side; the numerical smoke test runs in the subprocess harness)."""
    from repro.configs import hunyuan_dit
    cp = hunyuan_dit.auto_plan(8, pipeline_devices=8, microbatches=8)
    assert cp.folded and cp.partition.num_stages == 16
    assert cp.partition.validate_collocation(cp.graph)
    assert (sum(c for cs in cp.layout.enc_counts for c in cs)
            + sum(c for cs in cp.layout.dec_counts for c in cs) == 32)
    assert not validate_schedule(cp.schedule, cp.partition.device_of_stage,
                                 collocated=cp.partition.collocated_pairs())


def test_auto_pipeline_reports_dropped_plans():
    """When no plan survives, the error lists every candidate and why it
    was dropped (previously a bare 'no feasible, lowerable plan')."""
    # a 2-block skip graph on N=4: P=1 is pure DP, P=2 needs S=4 > 2
    # blocks, P=4 needs S=8 — nothing survives
    from repro.core.graph import Block, BlockGraph, SkipEdge
    g = BlockGraph((Block("a", 1.0, act_bytes=8), Block("b", 1.0)),
                   (SkipEdge(0, 1, 8),))
    cfg = _lm_cfg()
    with pytest.raises(ValueError) as ei:
        auto_pipeline(g, lm_model_fns(cfg), 4)
    msg = str(ei.value)
    assert "P=1" in msg and "P=2" in msg and "P=4" in msg
    assert "pure data parallelism" in msg
    assert "stages" in msg           # S > n explanation present


def test_auto_pipeline_zero_memory_drop_reasons():
    """On a memory-infeasible budget the raised error carries the full
    per-candidate drop list, naming the ZeRO constraint that killed each
    candidate — including that even ZeRO-2 sharding over the dp axis
    could not fit the smallest microbatch."""
    from repro.core.hw import TPU_V5E
    hw = dataclasses.replace(TPU_V5E, mem_limit=float(1 << 10))
    cfg = _lm_cfg()
    with pytest.raises(ValueError) as ei:
        auto_pipeline(lm_pipeline_graph(cfg), lm_model_fns(cfg), 4, hw)
    msg = str(ei.value)
    assert "memory budget" in msg
    assert "even with ZeRO-2 param/optimizer state sharded over dp=" in msg


def test_schedule_for_partition_greedy_matches_templates():
    g = uvit_pipeline_graph(_uvit_cfg())
    part = partition(g, 2)
    sched = schedule_for_partition(part, 4)
    assert sched.makespan >= 4 * 4       # work bound: 2 stages x (F+B) x M


# ---------------------------------------------------------------------------
# interleaved (virtual-stage) plans: V > 1 stage slot pairs per device
# ---------------------------------------------------------------------------

def _interleaved_skipvit(V=2, D=2):
    from repro.models.diffusion import SkipViTConfig, skipvit_pipeline_graph
    cfg = SkipViTConfig("t", n_enc=4, n_mid=2, n_dec=4)
    g = skipvit_pipeline_graph(
        cfg, fwd_times=[1, 1, 2, 4, 0.5, 0.5, 0.5, 1, 1, 2])
    return cfg, g


def test_interleaved_partition_layout_and_schedule():
    """partition(interleave=V) emits S = 2VD stages on the cyclic slot
    placement, keeps skip collocation, and StageLayout carries per-device
    slot lists — the S == 2D gate is gone."""
    cfg, g = _interleaved_skipvit()
    part = partition(g, 2, lam=0.0, interleave=2)
    assert part.folded and part.num_stages == 8 and part.num_devices == 2
    assert part.interleave == 2
    assert part.devices == (0, 1, 0, 1, 1, 0, 1, 0)
    assert part.validate_collocation(g)
    layout = StageLayout.from_partition(part, g)
    assert layout.V == 2
    assert all(len(ss) == 2 for ss in layout.enc_slots)
    assert all(len(ss) == 2 for ss in layout.dec_slots)
    assert (sum(c for cs in layout.enc_counts for c in cs)
            + sum(c for cs in layout.dec_counts for c in cs) == g.n)
    # every skip edge resolves to a flat (slot, row) stash index
    n_paired = sum(1 for dev in layout.skip_rows for row in dev
                   for r in row if r >= 0)
    assert n_paired == len(g.skips)
    assert all(0 <= r < layout.V * layout.enc_pad
               for dev in layout.skip_rows for row in dev for r in row
               if r >= 0)
    sched = schedule_for_partition(part, 4)
    assert not validate_schedule(sched, part.device_of_stage,
                                 collocated=part.collocated_pairs())


def test_interleaved_split_merge_roundtrip():
    """split_params -> merge_params stays the identity on V=2 interleaved
    layouts (the gradient path through [D, V, pad, ...] stacks)."""
    from repro.runtime.adapters import skipvit_model_fns
    cfg, g = _interleaved_skipvit()
    cp = auto_pipeline(g, skipvit_model_fns(cfg), 2, pipeline_devices=2,
                       microbatches=4, lam=0.0, interleave=2)
    assert cp.layout.V == 2
    params = cp.model_fns.init_fn(jax.random.PRNGKey(0))
    stacks, edge = cp.split_params(params)
    assert jax.tree.leaves(stacks[0])[0].shape[:2] == (2, 2)  # [D, V, ...]
    back = cp.merge_params(stacks, edge)
    for a, b in zip(jax.tree.leaves(params), jax.tree.leaves(back)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # closed-form executors cannot realize V > 1 slots
    with pytest.raises(ValueError, match="closed-form"):
        dataclasses.replace(cp, executor="closed_form").build()


def test_tuner_scores_interleave_axis():
    """V is a tuner search axis: V > 1 candidates carry their own V-fold
    partition, and drop reasons name the candidate's interleave degree."""
    cfg, g = _interleaved_skipvit()
    choices = tune(g, 4, lam=0.0, interleave_options=(1, 2))
    vs = {c.V for c in choices if c.P > 1}
    assert 1 in vs and 2 in vs
    for c in choices:
        if c.P > 1 and c.V > 1:
            assert c.partition.num_stages == 2 * c.V * c.P
            assert c.partition.interleave == c.V
    # a V too deep for the graph is dropped with its V recorded
    drops: list[str] = []
    tune(g, 4, lam=0.0, interleave_options=(4,), drops=drops)
    assert any("V=4" in d and "stages" in d for d in drops)


def test_compiled_pipeline_windows_and_wire():
    """The compiled plan's step tables carry schedule-proven liveness
    windows below M (the executors allocate W-slot rotating buffers, not
    [M] arrays), live-hop masks below the dense hop count, and the wire
    dtype threads from auto_pipeline to the executor config."""
    cfg = _uvit_cfg()
    cp = auto_pipeline(uvit_pipeline_graph(cfg),
                       diffusion_model_fns(cfg, "uvit"), 2,
                       pipeline_devices=2, microbatches=8)
    tabs = cp.step_tables()
    M = cp.schedule.M
    assert tabs.W_down < M and tabs.W_up < M and tabs.W_turn < M
    down, up = tabs.live_hops
    assert 0 < down + up < tabs.dense_hops
    assert cp.step_tables() is tabs            # memoized lowering
    assert cp.pcfg.wire_dtype == "bfloat16"    # default wire
    fp = auto_pipeline(uvit_pipeline_graph(cfg),
                       diffusion_model_fns(cfg, "uvit"), 2,
                       pipeline_devices=2, microbatches=4,
                       wire_dtype="float32")
    assert fp.pcfg.wire_dtype == "float32"
    import dataclasses as dc
    bad = dc.replace(cp, pcfg=dc.replace(cp.pcfg, wire_dtype="fp8"))
    with pytest.raises(ValueError, match="wire_dtype"):
        bad.build()


def test_tuner_prices_windowed_buffers():
    """tune() synthesizes + lowers every P > 1 candidate's schedule and
    prices peak_memory with the proven liveness windows.  The windows are
    steady-state properties — they do NOT grow with M — so the rx/turn
    footprint the tuner charges is M-independent, unlike any [M]-sized
    dense buffer sizing (the 'smaller proven footprints admit larger M'
    mechanism)."""
    from repro.core.schedule import schedule_for_partition
    from repro.core.tuner import peak_memory, profile_partition
    g = uvit_pipeline_graph(_uvit_cfg())
    choices = tune(g, 4)
    assert choices
    for c in choices:
        if c.P <= 1:
            continue
        sched = schedule_for_partition(c.partition, c.M)
        tabs = StepTables.from_schedule(sched, folded=c.partition.folded,
                                        devices=c.partition.devices)
        prof = profile_partition(g, c.partition)
        windowed = peak_memory(
            prof, c.P, c.b, wave=c.wave, V=c.V,
            windows=(tabs.W_down + tabs.W_up, tabs.W_turn, tabs.W_skip),
            dp=c.dp, zero_stage=c.zero_stage)
        assert c.peak_mem == windowed     # the score used the windows
        # vs the legacy 2-tuple (skip charged dense inside m_act), the
        # 3-tuple moves the skip stash to its proven rotating window:
        # out go P dense in-flight copies, in come W_skip fp32 entries
        legacy = peak_memory(
            prof, c.P, c.b, wave=c.wave, V=c.V,
            windows=(tabs.W_down + tabs.W_up, tabs.W_turn),
            dp=c.dp, zero_stage=c.zero_stage)
        if c.wave and c.V == 1:
            i, j = c.P - 1, c.P
            skips = prof.skip_bytes_per_sample
            dense_charge = c.P * (skips[i] + skips[j]) * c.b
            window_charge = tabs.W_skip * max(skips[i], skips[j]) * c.b * 2
            assert windowed == pytest.approx(
                legacy - dense_charge + window_charge)
        if c.V > 1:
            # interleaved greedy schedules may genuinely buffer O(M)
            # arrivals on a multiplexed slot — the window then reports
            # it honestly, and the tuner charges for it
            continue
        # V=1 wave templates: windows saturate at a steady-state
        # constant — doubling an already-large M leaves them unchanged
        # (and far below M), unlike any [M]-sized dense buffer sizing
        big = StepTables.from_schedule(
            schedule_for_partition(c.partition, 4 * c.M),
            folded=c.partition.folded, devices=c.partition.devices)
        bigger = StepTables.from_schedule(
            schedule_for_partition(c.partition, 8 * c.M),
            folded=c.partition.folded, devices=c.partition.devices)
        assert (big.W_down, big.W_up, big.W_turn) == \
            (bigger.W_down, bigger.W_up, bigger.W_turn)
        assert bigger.W_down < 8 * c.M and bigger.W_up < 8 * c.M


def test_step_tables_memoized_lowering():
    """Passing the mapping as a devices tuple memoizes the O(S*M*steps)
    lowering (same schedule + partition -> the identical StepTables
    object), and matches the callable-mapping build."""
    cfg, g = _interleaved_skipvit()
    part = partition(g, 2, lam=0.0, interleave=2)
    sched = schedule_for_partition(part, 4)
    t1 = StepTables.from_schedule(sched, folded=True, devices=part.devices)
    t2 = StepTables.from_schedule(sched, folded=True, devices=part.devices)
    assert t1 is t2
    t3 = StepTables.from_schedule(sched, folded=True,
                                  device_of_stage=part.device_of_stage)
    assert t3 is not t1
    np.testing.assert_array_equal(t1.sel, t3.sel)
    np.testing.assert_array_equal(t1.slot, t3.slot)
    # a schedule's dense programs are memoized per schedule too
    assert sched.device_programs() is sched.device_programs()


# ---------------------------------------------------------------------------
# differential executor tests (subprocess, mocked multi-device mesh)
# ---------------------------------------------------------------------------

_TIER1_EQUIV = ("linear-uneven", "wave-uneven", "wave-short",
                "wave-asym", "wave-sparse", "wave-interleaved",
                "linear-zero2", "wave-zero1", "wave-zero2")


@pytest.fixture(scope="session")
def tier1_equiv_out():
    """ONE subprocess for every tier-1 differential config: the
    multi-device jax startup (~8 s) is paid once instead of per test;
    each test below asserts on its own configs' result lines."""
    return _run_equiv(*_TIER1_EQUIV)


def test_auto_pipeline_equivalence_uneven_and_short(tier1_equiv_out):
    """Uneven DP partitions (linear + folded wave) lowered through the
    table-driven executor match the single-device reference AND the
    closed-form executors (loss + grads, rtol 1e-4) — the configs the
    hand-written S=D / S=2D executors could not run at all.  Plus the
    M = D - 1 wave: only the table-driven lowering can realize it (pinned
    behavior: the closed-form executor raises), and it matches the
    reference."""
    for cfg in ("linear-uneven", "wave-uneven", "wave-short"):
        assert f"{cfg}: " in tier1_equiv_out and "grads OK" in tier1_equiv_out
    assert "closed-form executor rejects M < D" in tier1_equiv_out


def test_auto_pipeline_equivalence_asymmetric_folds(tier1_equiv_out):
    """Mirror-ASYMMETRIC folds (make_unet_like(3, 2) shape + a sparse-skip
    variant) compile through auto_pipeline and their table executors match
    the single-device reference (loss + grads, rtol 1e-4); the asymmetric
    config is additionally checked against the closed-form wave executor.
    These are exactly the partitions StageLayout.from_partition used to
    reject."""
    assert "wave-asym: table executor == closed-form" in tier1_equiv_out
    assert "wave-sparse: cuts=" in tier1_equiv_out


def test_auto_pipeline_equivalence_interleaved(tier1_equiv_out):
    """V=2 interleaved wave on SkipViT (S = 4D stage slots, uneven slots,
    wraparound rings, slot-resolved skip stash): the table-driven executor
    matches the single-device reference (loss + grads, rtol 1e-4) — the
    region of the plan space the S == 2D layout gate made unreachable."""
    assert "wave-interleaved: closed-form executor rejects V=2" \
        in tier1_equiv_out
    assert "wave-interleaved: cuts=" in tier1_equiv_out


def test_auto_pipeline_equivalence_zero_hybrid(tier1_equiv_out):
    """Hybrid ZeRO x pipeline (dp=2, P=2, fp32 wire): with zero_stage=1
    (optimizer-state-only sharding) and zero_stage=2 (param stacks sharded
    at rest, all-gather-on-use inside the scan body, grads reduce-scattered
    over the data axis) the table executor still matches the unsharded
    single-replica reference on loss AND grads at rtol 1e-4."""
    for cfg in ("linear-zero2", "wave-zero1", "wave-zero2"):
        assert f"{cfg}: " in tier1_equiv_out and "grads OK" in tier1_equiv_out


@pytest.mark.slow
def test_auto_pipeline_equivalence_interleaved_ilp():
    """ILP-synthesized (Eqs. 6-13) V=2 interleaved schedule through the
    table-driven lowering matches the single-device reference — exact
    interleaved orders execute as synthesized, not just greedy ones.
    Plus the skip-free side of the axis: a V=2 interleaved linear 1F1B
    (S = VD, wraparound down ring) against the same reference."""
    _run_equiv("wave-interleaved-ilp", "linear-interleaved")


@pytest.mark.slow
def test_auto_pipeline_equivalence_hunyuan():
    """Hunyuan-DiT model_fns coverage (ROADMAP item): a small Hunyuan
    config through the full compile path matches hunyuan_apply (loss) and
    the aux-as-data block-loop reference (grads)."""
    _run_equiv("wave-hunyuan")


@pytest.mark.slow
def test_auto_pipeline_equivalence_even_and_forced_wave():
    """Even S=D / S=2D plans and the skip-free forced-wave (symmetric-fold
    partitioner + empty-skip executor) through the same compile path."""
    _run_equiv("linear-even", "wave-even", "wave-lm-uneven")


@pytest.mark.slow
def test_auto_pipeline_equivalence_ilp():
    """auto_pipeline(use_ilp=True) on a tiny graph: the exact ILP schedule
    validates, lowers via the table-driven executor (step tables == grid),
    and matches the single-device reference."""
    _run_equiv("wave-ilp")


def test_bf16_model_through_the_table_executor():
    """bf16 weights and compute (the published UViT-H / Hunyuan-DiT
    dtypes) through the one-device wave fold: loss within bf16 rounding
    of a float32 reference on the same weights, bf16 gradients."""
    import jax.numpy as jnp

    from repro.models.diffusion import init_uvit, uvit_loss
    from repro.runtime.adapters import make_diffusion_microbatches
    cfg = UViTConfig("bf16", img_size=8, in_ch=4, patch=2, d_model=64,
                     n_layers=4, n_heads=4, d_ff=128, n_classes=10,
                     dtype=jnp.bfloat16, param_dtype=jnp.bfloat16)
    key = jax.random.PRNGKey(0)
    B, M = 4, 2
    cp = auto_pipeline(uvit_pipeline_graph(cfg, batch=B // M),
                       diffusion_model_fns(cfg, "uvit"), 1,
                       pipeline_devices=1, microbatches=M)
    mesh = jax.make_mesh((1, 1), ("data", "model"))
    params = init_uvit(key, cfg)
    batch = {"latents": jax.random.normal(key, (B, 8, 8, 4)),
             "labels": jnp.arange(B) % 10}
    loss_of_mb = cp.bind(mesh)

    def loss(state):
        mb, aux = make_diffusion_microbatches(batch, key, M, cfg, "uvit")
        return loss_of_mb(state, mb, aux)

    lp, grads = jax.jit(jax.value_and_grad(loss))(cp.split_params(params))
    f32 = dataclasses.replace(cfg, dtype=jnp.float32)
    with jax.default_matmul_precision("highest"):
        lr = jax.jit(lambda p: uvit_loss(p, batch, key, f32))(params)
    np.testing.assert_allclose(float(lp), float(lr), rtol=2e-2)
    assert {g.dtype for g in jax.tree.leaves(grads)} == {jnp.dtype("bfloat16")}
    assert all(bool(jnp.all(jnp.isfinite(g))) for g in jax.tree.leaves(grads))
