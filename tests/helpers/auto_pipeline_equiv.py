"""Subprocess helper: auto_pipeline executor == single-device reference.

Differential tests for the compile path (graph -> partition -> schedule ->
executor): for each config, `auto_pipeline` plans and lowers a pipeline on
mocked multi-device meshes (forced host devices) and

- the table-driven executor's loss + merged gradients must match a plain
  single-device forward/backward within rtol 1e-4;
- where the closed-form executors apply (greedy template orders, M >= D),
  the table-driven executor must also match them differentially
  (loss + grads) — the closed forms are the hand-written references;
- the lowered step tables must match ``Schedule.grid()`` exactly
  (``device_programs`` slot-for-slot; ``StepTables`` on the forward
  placements), for greedy *and* ILP schedules;
- on selected configs, the overlapped (double-buffered ring hops)
  executor must match the synchronous reference lowering
  (``PipelineConfig.overlap=False``) — loss + grads at rtol 1e-4 on the
  exact fp32 wire.

Configs (pass names as argv to run a subset; default: all):
  linear-even    LM, S=D=4, uniform costs -> even 1F1B split
  linear-uneven  LM, S=D=4, heterogeneous profiled times -> uneven DP cuts
  wave-even      UViT, S=2D (D=2), uniform costs -> even folded wave
  wave-uneven    UViT, S=2D (D=2), heterogeneous times -> uneven symmetric
                 cuts from the bidirectional DP (Algorithm 1)
  wave-short     UViT, D=4, M=D-1: the closed-form wave executor must
                 refuse (stale-row clip), the table executor must match ref
  wave-ilp       UViT, D=2, ILP-synthesized schedule through the
                 table-driven lowering
  wave-asym      SkipViT 3 enc + 2 mid + 3 dec (make_unet_like(3, 2)
                 shape), heterogeneous times -> mirror-ASYMMETRIC fold:
                 independent enc/dec counts + graph-derived skip pairing
  wave-sparse    SkipViT with a sparse skip set (one pair dropped) ->
                 asymmetric fold with skip-less decoder rows
  wave-hunyuan   Hunyuan-DiT at tiny widths, depth 8 (5 + 3 blocks, two
                 unpaired), P=2: the fold pinned to the stack boundary;
                 the conditioning's grads flow back through aux; vs
                 hunyuan_loss and bench/reference/hydit.py
  wave-hunyuan-dp2
                 the same on a (2, 2) mesh: two data-parallel replicas
  linear-zero2 / wave-zero1 / wave-zero2
                 hybrid ZeRO x pipeline (dp=2, P=2): ZeRO-sharded
                 param/optimizer stacks vs the unsharded reference

Run with XLA_FLAGS=--xla_force_host_platform_device_count=8.
"""
import dataclasses
import os
import sys

os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"

import jax
import jax.numpy as jnp
import numpy as np

from repro.models.diffusion import (SkipViTConfig, UViTConfig,
                                    hunyuan_pipeline_graph, skipvit_apply,
                                    skipvit_pipeline_graph,
                                    uvit_apply, uvit_pipeline_graph)
from repro.models.layers import AttnConfig
from repro.models.lm import LMConfig, lm_loss, lm_pipeline_graph
from repro.runtime.adapters import (diffusion_model_fns,
                                    hunyuan_model_fns, lm_model_fns,
                                    make_diffusion_microbatches,
                                    skipvit_model_fns)
from repro.runtime.compile import auto_pipeline

from schedule_checks import (assert_programs_match_grid,
                             assert_step_tables_match_grid)

KEY = jax.random.PRNGKey(0)
RTOL = 1e-4
# bf16-wire vs fp32-wire tolerance: every boundary hop rounds the
# activation (and, in the transposed scan, its cotangent) to bf16's 8-bit
# mantissa (~0.4% relative per hop); losses and grads of these small
# configs stay within a few percent relative, with near-zero entries
# absorbed by the absolute floor.  Documented in README "Wire format &
# buffer liveness" — exactness is what wire_dtype="float32" is for.
WIRE_RTOL = 5e-2
WIRE_ATOL = 1e-3


def _check_grads(gm, gr, label, rtol=RTOL, atol=1e-6):
    flat_m = jax.tree_util.tree_flatten_with_path(gm)[0]
    flat_r = jax.tree.leaves(gr)
    assert len(flat_m) == len(flat_r)
    for (path, a), b in zip(flat_m, flat_r):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=rtol, atol=atol,
            err_msg=f"{label}: grad mismatch at "
                    f"{jax.tree_util.keystr(path)}")


def _check_tables_match_grid(cp, label):
    """The lowered step programs equal Schedule.grid() slot-for-slot."""
    assert_programs_match_grid(cp.schedule)
    tabs = assert_step_tables_match_grid(cp.schedule, cp.folded)
    n_fwd = int((tabs.sel != 0).sum())
    print(f"{label}: step tables == grid "
          f"({n_fwd} forward slots over {tabs.num_steps} steps)")


def _check_windows(cp, label):
    """The rx buffers are sized by the schedule-proven liveness window,
    not by the microbatch count (the acceptance-criterion assertion)."""
    tabs = cp.step_tables()
    M = cp.schedule.M
    assert tabs.W_down < M and tabs.W_up < M, (
        label, tabs.W_down, tabs.W_up, M)
    live_d, live_u = tabs.live_hops
    assert live_d + live_u < tabs.dense_hops
    print(f"{label}: rx windows W_down={tabs.W_down} W_up={tabs.W_up} "
          f"< M={M}; live hops {live_d}+{live_u} < dense "
          f"{tabs.dense_hops}")


def _diff_wire(cp, mesh, state, batch_args, label):
    """bf16-wire executor vs the fp32-wire escape hatch: loss + grads
    within the documented bf16 rounding tolerance (WIRE_RTOL)."""
    fp = dataclasses.replace(
        cp, pcfg=dataclasses.replace(cp.pcfg, wire_dtype="float32"))
    bf = dataclasses.replace(
        cp, pcfg=dataclasses.replace(cp.pcfg, wire_dtype="bfloat16"))
    lb, gb = jax.jit(jax.value_and_grad(bf.bind(mesh)))(state, *batch_args)
    lf, gf = jax.jit(jax.value_and_grad(fp.bind(mesh)))(state, *batch_args)
    np.testing.assert_allclose(float(lb), float(lf), rtol=WIRE_RTOL)
    _check_grads(cp.merge_params(gb[0], gb[1]),
                 cp.merge_params(gf[0], gf[1]), f"{label}[bf16-vs-fp32]",
                 rtol=WIRE_RTOL, atol=WIRE_ATOL)
    print(f"{label}: bf16-wire == fp32-wire within rtol {WIRE_RTOL} "
          f"(loss {float(lb):.6f} vs {float(lf):.6f})")


def _diff_overlap(cp, mesh, state, batch_args, label):
    """Overlapped (double-buffered) executor vs the synchronous reference
    lowering (``PipelineConfig.overlap=False``): loss + grads at rtol RTOL
    on the exact fp32 wire — moving each step's ring sends to the top of
    the next step's scan body must not change any value, only when the
    collective runs relative to compute."""
    ov = dataclasses.replace(
        cp, pcfg=dataclasses.replace(cp.pcfg, overlap=True))
    sync = dataclasses.replace(
        cp, pcfg=dataclasses.replace(cp.pcfg, overlap=False))
    lo, go = jax.jit(jax.value_and_grad(ov.bind(mesh)))(state, *batch_args)
    ls, gs = jax.jit(jax.value_and_grad(sync.bind(mesh)))(state, *batch_args)
    np.testing.assert_allclose(float(lo), float(ls), rtol=RTOL)
    _check_grads(cp.merge_params(go[0], go[1]),
                 cp.merge_params(gs[0], gs[1]), f"{label}[overlap-vs-sync]")
    print(f"{label}: overlapped executor == synchronous lowering "
          f"(loss {float(lo):.6f}; grads OK)")


def _diff_executors(cp, mesh, state, batch_args, label):
    """Table executor vs closed-form executor: loss + grads (rtol 1e-4)."""
    cf = dataclasses.replace(cp, executor="closed_form")
    table_loss = cp.bind(mesh)
    closed_loss = cf.bind(mesh)
    lt, gt = jax.jit(jax.value_and_grad(table_loss))(state, *batch_args)
    lc, gc = jax.jit(jax.value_and_grad(closed_loss))(state, *batch_args)
    np.testing.assert_allclose(float(lt), float(lc), rtol=RTOL)
    _check_grads(cp.merge_params(gt[0], gt[1]),
                 cp.merge_params(gc[0], gc[1]), f"{label}[table-vs-closed]")
    print(f"{label}: table executor == closed-form executor "
          f"(loss {float(lt):.6f}; grads OK)")


def _run_lm(name, fwd_times, expect_uneven, *, force_wave=None,
            pipeline_devices=4, compare_closed=True, interleave=None,
            check_overlap=False, zero_stage=None):
    cfg = LMConfig(name="t", vocab=64, d_model=32, n_layers=8,
                   attn=AttnConfig(32, 4, 2, 8), d_ff=64,
                   tied_embeddings=True)
    graph = lm_pipeline_graph(cfg, fwd_times=fwd_times)
    # wire_dtype="float32": the exact-wire escape hatch — these checks
    # demand rtol 1e-4 against the reference; _diff_wire covers bf16
    cp = auto_pipeline(graph, lm_model_fns(cfg), pipeline_devices,
                       pipeline_devices=pipeline_devices, microbatches=4,
                       lam=0.0, dp_size=2, force_wave=force_wave,
                       interleave=interleave, wire_dtype="float32",
                       zero_stage=zero_stage)
    if zero_stage is not None:
        assert cp.pcfg.zero_stage == zero_stage, (name, cp.pcfg.zero_stage)
        if zero_stage >= 2:
            specs, dims = cp._zero_layout()
            assert specs is not None
            flat_dims = jax.tree.leaves(dims)
            assert any(d >= 0 for d in flat_dims), (
                f"{name}: ZeRO-2 layout sharded no stack leaf", flat_dims)
    V = interleave or 1
    if force_wave:
        assert cp.folded
        assert cp.partition.num_stages == 2 * V * pipeline_devices
    else:
        assert not cp.folded
        assert cp.partition.num_stages == V * pipeline_devices   # S = VD
    assert cp.layout.V == V
    uneven = len(set(cp.layout.counts)) > 1
    assert uneven == expect_uneven, (name, cp.layout.counts)
    _check_tables_match_grid(cp, name)

    mesh = jax.make_mesh((2, pipeline_devices), ("data", "model"))
    params = cp.model_fns.init_fn(KEY)
    state = cp.split_params(params)
    B, S, M = 8, 16, 4
    tokens = jax.random.randint(KEY, (B, S), 0, 64)
    mbs = {"tokens": tokens.reshape(M, B // M, S)}

    bound = cp.bind(mesh)
    # folded executors take (params, mbs, aux); LM carries no aux
    loss = (lambda st, mb: bound(st, mb, {})) if cp.folded else bound
    lp, gp = jax.jit(jax.value_and_grad(loss))(state, mbs)

    def ref(params):
        return jnp.mean(jnp.asarray(
            [lm_loss(params, {"tokens": mbs["tokens"][m]}, cfg)
             for m in range(M)]))

    lr, gr = jax.jit(jax.value_and_grad(ref))(params)
    np.testing.assert_allclose(float(lp), float(lr), rtol=RTOL)
    _check_grads(cp.merge_params(gp[0], gp[1]), gr, name)
    print(f"{name}: counts={cp.layout.counts} loss={float(lp):.6f} "
          f"== ref {float(lr):.6f}; grads OK")
    batch_args = (mbs, {}) if cp.folded else (mbs,)
    if compare_closed:
        _diff_executors(cp, mesh, state, batch_args, name)
    if check_overlap:
        _diff_overlap(cp, mesh, state, batch_args, name)


def _run_uvit(name, fwd_times, expect_uneven, *, pipeline_devices=2,
              microbatches=4, use_ilp=False, compare_closed=True,
              expect_closed_rejects=False, check_wire=False):
    cfg = UViTConfig("t", img_size=8, in_ch=4, patch=2, d_model=32,
                     n_layers=8, n_heads=4, d_ff=64, n_classes=10)
    graph = uvit_pipeline_graph(cfg, fwd_times=fwd_times)
    cp = auto_pipeline(graph, diffusion_model_fns(cfg, "uvit"),
                       pipeline_devices, pipeline_devices=pipeline_devices,
                       microbatches=microbatches, lam=0.0, dp_size=2,
                       use_ilp=use_ilp, wire_dtype="float32")
    assert cp.folded and cp.partition.num_stages == 2 * pipeline_devices
    uneven = len(set(cp.layout.counts)) > 1
    assert uneven == expect_uneven, (name, cp.layout.counts)
    _check_tables_match_grid(cp, name)
    _check_windows(cp, name)
    if expect_closed_rejects:
        # M < D: the closed-form wave executor's clip reads stale rows —
        # it must refuse, while the table-driven lowering stays correct.
        try:
            dataclasses.replace(cp, executor="closed_form").build()
        except ValueError as e:
            assert "M >= D" in str(e), e
            print(f"{name}: closed-form executor rejects M < D as expected")
        else:
            raise AssertionError(
                f"{name}: closed-form executor accepted M < D")

    mesh = jax.make_mesh((2, pipeline_devices), ("data", "model"))
    params = cp.model_fns.init_fn(KEY)
    state = cp.split_params(params)
    M = microbatches
    B = 2 * M            # per-microbatch batch 2, sharded over data axis 2
    batch = {"latents": jax.random.normal(KEY, (B, 8, 8, 4)),
             "labels": jax.random.randint(KEY, (B,), 0, 10)}
    mb, aux = make_diffusion_microbatches(batch, KEY, M, cfg, "uvit")

    loss = cp.bind(mesh)
    lp, gp = jax.jit(jax.value_and_grad(loss))(state, mb, aux)

    def ref(params):
        losses = []
        for m in range(M):
            pred = uvit_apply(params, mb["xt"][m], aux["t"][m],
                              {"labels": mb["labels"][m]}, cfg)
            losses.append(jnp.mean(jnp.square(pred - mb["noise"][m])))
        return jnp.mean(jnp.asarray(losses))

    lr, gr = jax.jit(jax.value_and_grad(ref))(params)
    np.testing.assert_allclose(float(lp), float(lr), rtol=RTOL)
    _check_grads(cp.merge_params(gp[0], gp[1]), gr, name)
    print(f"{name}: counts={cp.layout.counts} loss={float(lp):.6f} "
          f"== ref {float(lr):.6f}; grads OK")
    if compare_closed:
        _diff_executors(cp, mesh, state, (mb, aux), name)
    if check_wire:
        _diff_wire(cp, mesh, state, (mb, aux), name)


def _run_skipvit(name, cfg, fwd_times, *, pipeline_devices=2,
                 microbatches=4, compare_closed=True, interleave=None,
                 use_ilp=False, expect_asym=True, remat=True,
                 check_wire=False, check_overlap=False):
    """SkipViT (homogeneous stack, sparse/mid-block skips): the partitions
    are mirror-ASYMMETRIC folds — the configs StageLayout used to reject.
    Table executor vs single-device reference; closed-form wave (which now
    also reads the generalized counts/pairing) differentially when M>=D.
    ``interleave=V`` pins a V-fold interleaved plan (S = 2VD stage slots;
    the closed-form executors cannot realize those at all)."""
    graph = skipvit_pipeline_graph(cfg, fwd_times=fwd_times)
    cp = auto_pipeline(graph, skipvit_model_fns(cfg), pipeline_devices,
                       pipeline_devices=pipeline_devices,
                       microbatches=microbatches, lam=0.0, dp_size=2,
                       interleave=interleave, use_ilp=use_ilp,
                       remat=remat, wire_dtype="float32")
    if interleave is not None and interleave > 1:
        assert cp.layout.V == interleave, (name, cp.layout.V)
        assert cp.partition.num_stages == 2 * interleave * pipeline_devices
        try:
            dataclasses.replace(cp, executor="closed_form").build()
        except ValueError as e:
            assert "closed-form" in str(e), e
            print(f"{name}: closed-form executor rejects V={interleave} "
                  "as expected")
        else:
            raise AssertionError(
                f"{name}: closed-form executor accepted V={interleave}")
    if expect_asym:
        assert cp.folded and not cp.partition.mirror_symmetric(), (
            name, cp.partition.cuts)
        assert cp.layout.enc_counts != cp.layout.dec_counts
    _check_tables_match_grid(cp, name)

    mesh = jax.make_mesh((2, pipeline_devices), ("data", "model"))
    params = cp.model_fns.init_fn(KEY)
    state = cp.split_params(params)
    M = microbatches
    B = 2 * M
    batch = {"latents": jax.random.normal(KEY, (B, 8, 8, 4)),
             "labels": jax.random.randint(KEY, (B,), 0, 10)}
    mb, aux = make_diffusion_microbatches(batch, KEY, M, cfg, "uvit")

    loss = cp.bind(mesh)
    lp, gp = jax.jit(jax.value_and_grad(loss))(state, mb, aux)

    def ref(params):
        losses = []
        for m in range(M):
            pred = skipvit_apply(params, mb["xt"][m], aux["t"][m],
                                 {"labels": mb["labels"][m]}, cfg)
            losses.append(jnp.mean(jnp.square(pred - mb["noise"][m])))
        return jnp.mean(jnp.asarray(losses))

    lr, gr = jax.jit(jax.value_and_grad(ref))(params)
    np.testing.assert_allclose(float(lp), float(lr), rtol=RTOL)
    _check_grads(cp.merge_params(gp[0], gp[1]), gr, name)
    print(f"{name}: cuts={cp.partition.cuts} enc={cp.layout.enc_counts} "
          f"dec={cp.layout.dec_counts} loss={float(lp):.6f} "
          f"== ref {float(lr):.6f}; grads OK")
    if compare_closed:
        _diff_executors(cp, mesh, state, (mb, aux), name)
    if check_wire:
        _check_windows(cp, name)
        _diff_wire(cp, mesh, state, (mb, aux), name)
    if check_overlap:
        _diff_overlap(cp, mesh, state, (mb, aux), name)


def _run_hunyuan(name, *, pipeline_devices=2, microbatches=4, dp_size=1):
    """Hunyuan-DiT (its block, conditioning and final layer at tiny widths,
    depth 8 shaped like DiT-g/2's 40: 5 blocks without skip parameters and
    3 with, blocks 3 and 4 unpaired) through auto_pipeline on a P=2 mesh
    vs the single-device ``hunyuan_loss`` and vs the benchmark's plain
    float32 reference (``bench/reference/hydit.py``, which imports nothing
    of the program): loss and every leaf's gradient at rtol 1e-4.  The
    conditioning runs once outside the executor; its cotangent comes back
    through the shard_map transpose, summed over the pipeline axis and,
    with ``dp_size`` replicas, over the data axis too."""
    sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))))
    from bench.reference import hydit as reference
    from repro.configs.smoke import hunyuan_tiny, hunyuan_tiny_batch
    from repro.models.diffusion import hunyuan_loss
    from repro.runtime.adapters import hunyuan_microbatches

    # the published path's memory setting: query blocks
    cfg = hunyuan_tiny("t", n_layers=8, q_block=8)
    graph = hunyuan_pipeline_graph(cfg)
    cp = auto_pipeline(graph, hunyuan_model_fns(cfg), pipeline_devices,
                       pipeline_devices=pipeline_devices,
                       microbatches=microbatches, lam=0.0, dp_size=dp_size,
                       wire_dtype="float32")
    S = cp.partition.num_stages
    assert cp.folded and S == 2 * pipeline_devices
    assert cp.partition.cuts[S // 2] == cfg.n_enc == 5, cp.partition.cuts
    assert "turnaround at block 5 (pinned" in cp.describe()
    assert "skip partner: 3, 4" in cp.describe()
    _check_tables_match_grid(cp, name)

    mesh = jax.make_mesh((dp_size, pipeline_devices), ("data", "model"))
    params = cp.model_fns.init_fn(KEY)
    state = cp.split_params(params)
    M, B = microbatches, 2 * microbatches   # rows of a microbatch: 2
    batch = hunyuan_tiny_batch(jax.random.PRNGKey(5), cfg, B)
    rng = jax.random.PRNGKey(7)
    loss_of_mb = cp.bind(mesh)

    def loss(state):
        mb, aux = hunyuan_microbatches(state[1], batch, rng, M, cfg)
        return loss_of_mb(state, mb, aux)

    lp, gp = jax.jit(jax.value_and_grad(loss))(state)
    gp = cp.merge_params(gp[0], gp[1])
    with jax.default_matmul_precision("highest"):
        ls, gs = jax.jit(jax.value_and_grad(
            lambda p: hunyuan_loss(p, batch, rng, cfg)))(params)
    np.testing.assert_allclose(float(lp), float(ls), rtol=RTOL)
    _check_grads(gp, gs, name + " vs hunyuan_loss")

    c = {k: getattr(cfg, k) for k in (
        "in_ch", "out_ch", "patch", "d_model", "n_layers", "n_heads",
        "d_ff", "text_dim", "clip_len", "t5_dim", "t5_len", "t5_hidden",
        "pool_heads", "pool_dim", "freq_dim", "rope_theta", "rope_base",
        "norm_eps")}
    c.update(head_dim=cfg.d_model // cfg.n_heads, param_dtype="float32",
             dtype="float32")
    assert all(bool(jnp.all(a == b)) for a, b in zip(
        jax.tree.leaves(reference.init(KEY, c)), jax.tree.leaves(params)))
    # the reference runs a sample per device over as many devices as the
    # pipeline's mesh (two rounds of four, or four of two)
    ref = reference.Reference(c, cfg.img_size,
                              jax.devices()[:pipeline_devices * dp_size])
    lr, gr = ref.loss_and_grads(ref.init(KEY), batch, rng)
    np.testing.assert_allclose(float(lp), lr, rtol=RTOL)
    np.testing.assert_allclose(float(ls), lr, rtol=RTOL)
    gr = reference._model_space(gr)
    _check_grads(gp, gr, name + " vs reference")
    _check_grads(gs, gr, "hunyuan_loss vs reference")
    print(f"{name}: cuts={cp.partition.cuts} enc={cp.layout.enc_counts} "
          f"dec={cp.layout.dec_counts} loss={float(lp):.6f} == "
          f"hunyuan_loss {float(ls):.6f} == reference {lr:.6f}; grads OK")


CONFIGS = {
    "linear-even": lambda: _run_lm("linear-even", None, False),
    "linear-uneven": lambda: _run_lm(
        "linear-uneven", [4, 1, 1, 1, 1, 1, 1, 4], True,
        check_overlap=True),
    "wave-even": lambda: _run_uvit("wave-even", None, False),
    "wave-uneven": lambda: _run_uvit(
        "wave-uneven", [3, 1, 1, 1, 1, 1, 1, 3], True, check_wire=True),
    # skip-free graph forced into a fold: symmetric-fold partitioner +
    # empty-skip wave executor (partition_symmetric_fold)
    "wave-lm-uneven": lambda: _run_lm(
        "wave-lm-uneven", [4, 1, 1, 1, 1, 1, 1, 4], True,
        force_wave=True, pipeline_devices=2),
    # M = D - 1: only the table-driven lowering can run this; the
    # closed-form executor must reject it (stale-row clip)
    "wave-short": lambda: _run_uvit(
        "wave-short", None, False, pipeline_devices=4, microbatches=3,
        compare_closed=False, expect_closed_rejects=True),
    # exact ILP schedule (Eqs. 6-13) through the table-driven lowering;
    # the closed-form executor cannot realize a non-template order at all
    "wave-ilp": lambda: _run_uvit(
        "wave-ilp", None, False, microbatches=2, use_ilp=True,
        compare_closed=False),
    # mirror-ASYMMETRIC fold (make_unet_like(3, 2) shape): block costs pull
    # the turnaround cut off-centre -> cuts (0,2,3,6,8), enc/dec counts
    # (2,1)/(2,3) — the partitions StageLayout.from_partition rejected
    "wave-asym": lambda: _run_skipvit(
        "wave-asym", SkipViTConfig("t", n_enc=3, n_mid=2, n_dec=3),
        [1, 1, 4, 0.5, 0.5, 0.5, 1, 1], check_overlap=True),
    # sparse skips: pair (1, 6) dropped -> decoder rows without a skip
    # read zeros via the pairing table's -1 sentinel (closed-form diff
    # covered by wave-asym; skipped here to keep tier-1 lean)
    "wave-sparse": lambda: _run_skipvit(
        "wave-sparse",
        SkipViTConfig("t", n_enc=3, n_mid=2, n_dec=3,
                      skip_pairs=((0, 7), (2, 5))),
        [1, 1, 4, 0.5, 0.5, 0.5, 1, 1], compare_closed=False),
    # Hunyuan-DiT: its block, conditioning and final layer through the
    # full compile path vs the single-device model and the plain reference
    "wave-hunyuan": lambda: _run_hunyuan("wave-hunyuan"),
    # the same over two data-parallel replicas of the P=2 pipeline: each
    # replica's conditioning cotangent is summed over both mesh axes
    "wave-hunyuan-dp2": lambda: _run_hunyuan("wave-hunyuan-dp2", dp_size=2),
    # Hybrid ZeRO x pipeline (dp=2, P=2, fp32 wire): the executor runs DP
    # replicas of the pipeline with ZeRO-sharded state, and must still
    # match the unsharded single-replica reference at rtol 1e-4.
    # zero1 shards only optimizer state (executors untouched — this pins
    # that the plan records the stage without perturbing values); zero2
    # stores the stacks sharded at rest, all-gathers each slot row on use
    # inside the remat region, and reduce-scatters param grads over data.
    "linear-zero2": lambda: _run_lm(
        "linear-zero2", [4, 1, 1, 1, 1, 1, 1, 4], False,
        pipeline_devices=2, zero_stage=2, compare_closed=False),
    "wave-zero1": lambda: _run_lm(
        "wave-zero1", [4, 1, 1, 1, 1, 1, 1, 4], True, force_wave=True,
        pipeline_devices=2, zero_stage=1, compare_closed=False),
    "wave-zero2": lambda: _run_lm(
        "wave-zero2", [4, 1, 1, 1, 1, 1, 1, 4], True, force_wave=True,
        pipeline_devices=2, zero_stage=2, compare_closed=False),
    # V=2 interleaved 1F1B (linear S = VD, cyclic slot placement, the
    # wraparound down ring): the skip-free side of the interleave axis
    "linear-interleaved": lambda: _run_lm(
        "linear-interleaved", [4, 1, 1, 1, 1, 1, 1, 4], True,
        pipeline_devices=2, interleave=2, compare_closed=False),
    # V=2 interleaved wave (S = 4D stage slots, two (enc, dec) slot pairs
    # per device, wraparound rings, slot-resolved skip pairing): the plans
    # the S == 2D layout gate used to reject outright
    "wave-interleaved": lambda: _run_skipvit(
        "wave-interleaved",
        SkipViTConfig("t", n_enc=4, n_mid=2, n_dec=4),
        [1, 1, 2, 4, 0.5, 0.5, 0.5, 1, 1, 2],
        interleave=2, compare_closed=False, expect_asym=False,
        remat=False, check_wire=True, check_overlap=True),
    # ILP-synthesized (Eqs. 6-13) V=2 interleaved schedule through the
    # same table-driven lowering — exact orders, not just greedy ones
    "wave-interleaved-ilp": lambda: _run_skipvit(
        "wave-interleaved-ilp",
        SkipViTConfig("t", n_enc=3, n_mid=2, n_dec=3),
        [1, 1, 4, 0.5, 0.5, 0.5, 1, 1],
        interleave=2, microbatches=2, use_ilp=True,
        compare_closed=False, expect_asym=False),
}


if __name__ == "__main__":
    names = sys.argv[1:] or list(CONFIGS)
    for n in names:
        CONFIGS[n]()
    print("AUTO PIPELINE EQUIVALENCE: ALL OK")
