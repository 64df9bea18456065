"""End-to-end training driver: a thin loop over ``auto_pipeline`` +
``CheckpointManager`` + a fault plan.

Runs a real training loop on the host (CPU here; the same code path
drives TPU pods — the mesh/shardings come from launch.mesh):
synthetic-but-learnable data, AdamW, periodic async checkpointing with
verified manifests, exact resume, optional pipeline-parallel execution
over simulated devices with a (dp, pp) mesh and ZeRO sharding.

Fault-tolerance contract (exercised by examples/fault_tolerance.py and
tests/helpers/resilience_drill.py):

- ``--faults kill@K`` (or legacy ``--simulate-failure K``) hard-kills
  the process after step K; ``stop@K`` stops abruptly in-process;
  ``nan@K`` poisons a batch (the GradGuard skips the update);
  ``corrupt@K[:shard]`` / ``truncate@K[:shard]`` mutate the newest
  checkpoint shard; ``iofail@K:N`` makes the next N save attempts fail
  transiently (retry/backoff).  The same script parses from
  ``$REPRO_FAULTS``.
- Rerunning with ``--resume`` restores the newest *verified* checkpoint
  (corrupt/partial steps are skipped with a warning) and the stateless
  data pipeline regenerates the exact step stream, so the loss
  trajectory continues as if uninterrupted.
- Resume may use a DIFFERENT plan (``--pp``/``--dp``/``--zero-stage``/
  ``--interleave``): restore de-stacks the saved stage stacks through
  the manifest's recorded plan spec and re-stacks onto the new plan
  (runtime.resilience) — a P=4 run killed mid-epoch resumes as
  P=2 x dp=2 ZeRO-2 with an identical loss trajectory.

Multi-host worker mode (how ``launch/supervisor.py`` runs this driver —
one subprocess per host):

- ``--host-id h --num-hosts H`` makes this process host ``h`` of ``H``:
  it writes ONLY its own checkpoint shard (``shard_{h:05d}.npz``; host 0
  owns the manifest and GC) and blocks on ``wait_step_complete`` at each
  checkpoint step — the commit barrier that keeps any host from racing
  past a step its peers have not durably finished.  Startup rendezvous
  goes through a ``FileBarrier`` under the heartbeat dir.
- ``--heartbeat-dir D`` emits an atomic per-step heartbeat (host, step,
  phase, loss, grad-norm, wall-clock, generation) the supervisor's
  watchdog/straggler detectors consume.
- ``--escalation rollback`` turns an exhausted GradGuard skip budget
  into exit code ``EXIT_ESCALATE`` (43) instead of an abort, asking the
  supervisor to roll the cluster back to the last verified checkpoint.
- the multi-host fault verbs (``hostdown@K:h``, ``hang@K[:h]``,
  ``slow@K:factor[:h]``) are filtered per host via
  ``FaultPlan.for_host`` — malformed specs (unknown host, duplicate
  verb, negative step) fail at startup, not mid-training.

Usage:
    PYTHONPATH=src python -m repro.launch.train --arch uvit --steps 200
    PYTHONPATH=src python -m repro.launch.train --arch uvit --pipeline \
        --devices 8 --dp 2 --steps 50   # wave PP over 8 simulated devices
    PYTHONPATH=src python -m repro.launch.train --arch uvit-h --pipeline \
        --layers 8 --steps 10           # UViT-H widths on the devices present
    PYTHONPATH=src python -m repro.launch.train --arch hunyuan-dit \
        --pipeline --global-batch 8 --microbatches 8   # DiT-g/2, 4 chips
"""
import argparse
import dataclasses
import json
import os
from typing import Any

#: parameter init uses PRNGKey(SEED) and step k's timesteps and noise use
#: fold_in(PRNGKey(SEED), k) — what a reference run rebuilds to compare
SEED = 0
#: ``--arch`` values of the pipeline path (see :func:`pipeline_config`)
PIPELINE_ARCHS = ("uvit", "skipvit", "uvit-nano", "uvit-h", "hunyuan",
                  "hunyuan-dit")


def _parse_args(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="uvit",
                    help="smoke arch key (see repro.configs.smoke); with "
                         f"--pipeline one of {PIPELINE_ARCHS}")
    ap.add_argument("--layers", type=int, default=None,
                    help="pipeline path: depth cut (total blocks) of "
                         "--arch uvit-h or hunyuan-dit; widths stay "
                         "published")
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--global-batch", type=int, default=16)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--keep", type=int, default=3,
                    help="checkpoint retention (verified-complete steps)")
    ap.add_argument("--resume", action="store_true")
    ap.add_argument("--pipeline", action="store_true",
                    help="train through auto_pipeline on a (dp, pp) mesh")
    ap.add_argument("--devices", type=int, default=None,
                    help="simulate this many devices on the CPU backend "
                         "(sets JAX_PLATFORMS=cpu); default: the devices "
                         "JAX finds")
    ap.add_argument("--dp", type=int, default=1,
                    help="data-parallel degree of the (data, model) mesh")
    ap.add_argument("--pp", type=int, default=None,
                    help="pipeline degree (default: devices // dp)")
    ap.add_argument("--zero-stage", type=int, default=0, choices=(0, 1, 2))
    ap.add_argument("--interleave", type=int, default=None,
                    help="virtual stage slots per device (V)")
    ap.add_argument("--wire-dtype", default="bfloat16",
                    help="boundary-hop dtype; float32 = exact wire")
    ap.add_argument("--microbatches", type=int, default=4)
    ap.add_argument("--faults", default=None,
                    help="fault plan, e.g. 'kill@60,corrupt@80:shard_00000,"
                         "nan@10,iofail@20:2,hostdown@30:1,hang@40,"
                         "slow@50:2.5:1' (default: $REPRO_FAULTS)")
    ap.add_argument("--nan-skip-budget", type=int, default=3,
                    help="max consecutive non-finite steps before the "
                         "escalation policy fires")
    ap.add_argument("--escalation", default="abort",
                    choices=("abort", "rollback"),
                    help="exhausted GradGuard budget: 'abort' raises "
                         "(standalone default); 'rollback' exits "
                         "EXIT_ESCALATE=43 so a supervisor rolls back to "
                         "the last verified checkpoint")
    ap.add_argument("--host-id", type=int, default=0,
                    help="this process's host rank (multi-host worker "
                         "mode; writes shard_<host-id>.npz only)")
    ap.add_argument("--num-hosts", type=int, default=1,
                    help="total host processes cooperating on the run")
    ap.add_argument("--heartbeat-dir", default=None,
                    help="emit per-step heartbeats (+ host the startup "
                         "barrier) here for the training supervisor")
    ap.add_argument("--gen", type=int, default=0,
                    help="supervisor generation tag stamped into "
                         "heartbeats (stale-file filtering)")
    ap.add_argument("--commit-timeout", type=float, default=60.0,
                    help="multi-host barrier timeout (s) on checkpoint "
                         "step commit")
    ap.add_argument("--simulate-failure", type=int, default=0,
                    help="legacy alias for --faults kill@K")
    ap.add_argument("--logical-params", action="store_true",
                    help="return the merged model-space params in the "
                         "TrainResult (copies every weight to the host)")
    ap.add_argument("--out-json", default=None,
                    help="write the step->loss trajectory + resume "
                         "metadata here on exit")
    ap.add_argument("--log-every", type=int, default=10)
    return ap.parse_args(argv)


def _dump_losses(path: str, losses: dict, start: int) -> None:
    doc = {"losses": {str(k): v for k, v in losses.items()},
           "start": start, "partial": True}
    tmp = path + f".tmp{os.getpid()}"
    with open(tmp, "w") as f:
        json.dump(doc, f)
    os.replace(tmp, path)


@dataclasses.dataclass
class TrainResult:
    """What one driver invocation did (consumed by drills and examples)."""
    final_loss: float | None
    losses: dict                    # step -> float (host)
    start: int                      # first step this invocation ran
    resumed: Any = None             # RestoreInfo | None
    logical_params: Any = None      # model-space params (plan-independent;
    #                                 only with --logical-params)
    skipped_steps: int = 0          # non-finite updates the guard skipped
    step_s: dict = dataclasses.field(default_factory=dict)
    #                                 step -> seconds, host clock around the
    #                                 step up to its loss on the host
    compile_s: float | None = None  # pipeline path: step-program compile
    plan: dict | None = None        # pipeline path: compiled.state_spec()
    device_bytes: list | None = None  # bytes_in_use per local device with
    #                                   the training state still live


def main(argv=None):
    res = run(_parse_args(argv))
    return res.final_loss


def run(args) -> TrainResult:
    from repro.runtime.resilience import (EXIT_ESCALATE, FaultPlan,
                                          GradGuard, GradGuardEscalation,
                                          Heartbeat, restore_training_state,
                                          write_heartbeat)

    faults = FaultPlan.parse(args.faults)
    if args.simulate_failure:
        faults = faults.with_kill(args.simulate_failure)
    # validates host-scoped tokens against the real host count and keeps
    # this host's share — malformed specs die HERE, not mid-training
    faults = faults.for_host(args.host_id, args.num_hosts)

    def beat(step, phase, loss=None, gnorm=None, step_s=None):
        if args.heartbeat_dir:
            write_heartbeat(args.heartbeat_dir, Heartbeat(
                args.host_id, step, phase, loss=loss, grad_norm=gnorm,
                step_s=step_s, gen=args.gen,
                start=start if phase == "train" else None))

    beat(-1, "init")
    if args.pipeline and args.devices:
        # simulated devices live on the CPU backend: never reach for (or
        # silently fall back from) an accelerator for them
        os.environ["JAX_PLATFORMS"] = "cpu"
        os.environ.setdefault(
            "XLA_FLAGS",
            f"--xla_force_host_platform_device_count={args.devices}")

    import jax

    from repro.checkpoint import CheckpointManager, latest_step, \
        restore_checkpoint
    from repro.launch.compile_cache import enable_compile_cache
    from repro.optim import AdamWConfig, cosine_schedule

    print(f"[train] compile cache: {enable_compile_cache()}")
    opt_cfg = AdamWConfig(lr=args.lr)
    key = jax.random.PRNGKey(SEED)

    if args.pipeline:
        params, opt_state, step_fn, loader, pack, compiled = \
            _build_pipeline_trainer(args, key, opt_cfg)
    else:
        params, opt_state, step_fn, loader, pack = _build_smoke_trainer(
            args, key, opt_cfg)
        compiled = None

    mgr = CheckpointManager(
        args.ckpt_dir, keep=args.keep, host_id=args.host_id,
        num_hosts=args.num_hosts,
        plan=compiled.state_spec() if compiled is not None else None,
        io_fault=faults.io_fault) if args.ckpt_dir else None

    multi_host = args.num_hosts > 1
    if multi_host:
        from repro.launch.mesh import FileBarrier, HostTopology
        topo = HostTopology(args.num_hosts, max(
            (args.devices or len(jax.devices())) // args.num_hosts, 1))
        print("[train] " + topo.describe().replace("\n", "\n[train] "))
        if args.heartbeat_dir:
            barrier = FileBarrier(
                os.path.join(args.heartbeat_dir, "barrier"),
                host_id=args.host_id, num_hosts=args.num_hosts)
            barrier.wait(f"start.g{args.gen}", timeout=args.commit_timeout)

    start, resumed = 0, None
    if args.resume and args.ckpt_dir \
            and latest_step(args.ckpt_dir) is not None:
        state = {"params": params, "opt": opt_state}
        if compiled is not None:
            state, info = restore_training_state(
                args.ckpt_dir, compiled, state, strict=False)
            start, resumed = info.step, info
            print(f"[train] resumed from step {info.step}"
                  + (" (elastic restore: plan changed)" if info.elastic
                     else ""))
        else:
            state, start = restore_checkpoint(args.ckpt_dir, state,
                                              strict=False)
            print(f"[train] resumed from step {start}")
        params, opt_state = state["params"], state["opt"]

    guard = GradGuard(budget=args.nan_skip_budget)
    losses: dict[int, float] = {}
    step_times: dict[int, float] = {}

    def finish(loss) -> TrainResult:
        beat(args.steps, "done")
        logical = None
        if compiled is not None and args.logical_params:
            logical = jax.device_get(compiled.merge_params(*params))
        res = TrainResult(
            final_loss=None if loss is None else float(loss),
            losses=losses, start=start, resumed=resumed,
            logical_params=logical, skipped_steps=guard.skipped_total,
            step_s=step_times, compile_s=getattr(step_fn, "compile_s", None),
            plan=compiled.state_spec() if compiled is not None else None,
            device_bytes=[(d.memory_stats() or {}).get("bytes_in_use")
                          for d in jax.local_devices()])
        if args.out_json:
            doc = {"final_loss": res.final_loss,
                   "losses": {str(k): v for k, v in losses.items()},
                   "start": start,
                   "resumed_step": resumed.step if resumed else None,
                   "elastic": bool(resumed.elastic) if resumed else False,
                   "skipped_steps": res.skipped_steps}
            with open(args.out_json, "w") as f:
                json.dump(doc, f)
        return res

    if start >= args.steps:
        print(f"[train] nothing to do: resumed step {start} >= "
              f"--steps {args.steps}")
        return finish(None)

    import time

    from jax.profiler import StepTraceAnnotation, TraceAnnotation

    from repro.checkpoint import CheckpointError, wait_step_complete

    def save_at(step_next):
        """Single-host: async save.  Multi-host: blocking shard write +
        rendezvous on step completeness (the commit barrier)."""
        with TraceAnnotation("ckpt_save"):
            state = {"params": params, "opt": opt_state}
            # a checkpoint save IS progress — tell the watchdog so a slow
            # commit (device_get + hashing on a busy box) is not mistaken
            # for a stalled step loop
            beat(step_next, "ckpt")
            if not multi_host:
                mgr.save_async(step_next, state)
                return
            if mgr.save(step_next, state) is None:
                return              # degraded save: no barrier to meet
            try:
                wait_step_complete(args.ckpt_dir, step_next,
                                   timeout=args.commit_timeout)
            except CheckpointError as e:
                # degrade-and-warn, same contract as single-host iofail:
                # the supervisor's watchdog owns declaring a peer dead
                print(f"[train] WARNING: commit barrier at step "
                      f"{step_next} did not close: {e}")

    t0 = time.time()
    loss = None
    # iteration boundary, reset at the END of each loop body: the step
    # period it measures spans compute + host-side bookkeeping, which is
    # what a straggler's peers actually experience (the device-blocking
    # slice alone can be a small fraction of the wall period)
    t_step = time.time()
    # Host spans on the profiler's clock, under the benchmark harness's
    # names (batch, place, dispatch, loss_read), inside one ``train`` step
    # annotation a step; ``ckpt_save`` spans a checkpoint save (``save_at``).
    for step in range(start, args.steps):
        with StepTraceAnnotation("train", step_num=step):
            if faults.hang_before(step):
                # unreachable in practice (hang sleeps ~forever and the
                # supervisor kills us) — guard for mocked sleeps in tests
                print(f"[train] fault plan: woke from hang at step {step}")
                t_step = time.time()
            with TraceAnnotation("batch"):
                raw = loader.get(step)
            with TraceAnnotation("place"):
                batch = faults.poison_batch(pack(raw), step)
                rng = jax.random.fold_in(key, step)
            lr = cosine_schedule(step, base_lr=args.lr, warmup=20,
                                 total=args.steps)
            with TraceAnnotation("dispatch"):
                params, opt_state, loss, finite, gnorm = step_fn(
                    params, opt_state, batch, rng, lr)
            with TraceAnnotation("loss_read"):
                finite = bool(finite)
            try:
                guard.observe(finite, step)
            except GradGuardEscalation as e:
                if args.escalation == "rollback":
                    print(f"[train] {e}; requesting supervisor rollback")
                    if mgr:
                        mgr.wait()
                    beat(step, "done")
                    raise SystemExit(EXIT_ESCALATE) from None
                raise
            losses[step] = float(loss)
            step_times[step] = time.time() - t_step
            if args.out_json:
                # incremental (atomic) trajectory dump: a worker killed or
                # torn down mid-run still leaves its losses for the
                # supervisor to merge
                _dump_losses(args.out_json, losses, start)
            slow = faults.slow_factor(step)
            if slow > 1.0:     # straggle: stretch this step by the factor
                time.sleep(min((time.time() - t_step) * (slow - 1.0), 5.0))
            # the measured duration rides the heartbeat: a supervisor starved
            # of poll slots still gets exact per-step samples for straggler
            # detection (time-derived deltas would average over jit warmup)
            beat(step, "train", loss=float(loss), gnorm=float(gnorm),
                 step_s=time.time() - t_step)
            if step % args.log_every == 0 or step == args.steps - 1:
                sps = ((step - start + 1) * args.global_batch
                       / (time.time() - t0))
                print(f"[train] step {step:5d} loss {float(loss):.4f} "
                      f"lr {float(lr):.2e} ({sps:.1f} samples/s)")
            if mgr and (step + 1) % args.ckpt_every == 0:
                save_at(step + 1)
            if faults.post_step(step + 1, ckpt_dir=args.ckpt_dir,
                                flush=mgr.wait if mgr else None) == "stop":
                print(f"[train] fault plan: abrupt stop after step {step} "
                      "(no final save)")
                return finish(loss)
            t_step = time.time()   # boundary: commit barrier waits excluded
    if mgr:
        save_at(args.steps)
        mgr.wait()
    print(f"[train] done: final loss {float(loss):.4f}")
    return finish(loss)


def _grad_norm(grads):
    """Global L2 norm of a gradient pytree (reported in heartbeats so the
    supervisor can flag divergence before the GradGuard trips)."""
    import jax
    import jax.numpy as jnp
    leaves = jax.tree_util.tree_leaves(grads)
    return jnp.sqrt(sum(jnp.sum(jnp.square(g.astype(jnp.float32)))
                        for g in leaves))


def _build_smoke_trainer(args, key, opt_cfg):
    import jax
    from repro.configs.smoke import SMOKE_FACTORIES
    from repro.optim import adamw_init, adamw_update
    from repro.data import SyntheticLatentDataset, SyntheticTokenDataset, \
        SyntheticTextLatentDataset, ShardedLoader
    from repro.runtime.resilience import all_finite

    name = args.arch if args.arch in SMOKE_FACTORIES else {
        "uvit": "uvit-h", "hunyuan": "hunyuan-dit"}.get(args.arch, args.arch)
    loss_fn, init_fn, make_batch, _cfg = SMOKE_FACTORIES[name]()
    params = init_fn(key)
    opt_state = adamw_init(params)
    proto = make_batch(key)
    if "t5" in proto:
        ds = SyntheticTextLatentDataset(
            img_size=proto["latents"].shape[1],
            channels=proto["latents"].shape[-1],
            clip_len=proto["clip"].shape[1], clip_dim=proto["clip"].shape[2],
            t5_len=proto["t5"].shape[1], t5_dim=proto["t5"].shape[2])
    elif "latents" in proto:
        ds = SyntheticLatentDataset(
            img_size=proto["latents"].shape[1],
            channels=proto["latents"].shape[-1],
            n_classes=10,
            text_dim=(proto["text_embeds"].shape[-1]
                      if "text_embeds" in proto else 0),
            text_len=(proto["text_embeds"].shape[1]
                      if "text_embeds" in proto else 77))
    else:
        ds = SyntheticTokenDataset(vocab=256, seq_len=proto["tokens"].shape[1])
    loader = ShardedLoader(ds, global_batch=args.global_batch)

    def pack(raw):
        import jax.numpy as jnp
        out = {k: jnp.asarray(v) for k, v in raw.items()
               if k in proto or k == "labels"}
        if "frames" in proto:   # whisper: frames stub from latents? tokens ds
            out = {"frames": jax.random.normal(key, (args.global_batch,)
                                               + proto["frames"].shape[1:]),
                   "tokens": out["tokens"][:, :proto["tokens"].shape[1]]}
        return {k: v for k, v in out.items() if k in proto}

    @jax.jit
    def step_fn(params, opt_state, batch, rng, lr):
        loss, grads = jax.value_and_grad(loss_fn)(params, batch, rng)
        finite = all_finite(loss, grads)
        gnorm = _grad_norm(grads)
        new_p, new_o = adamw_update(params, grads, opt_state, opt_cfg,
                                    lr=lr)
        params, opt_state = jax.lax.cond(
            finite, lambda: (new_p, new_o), lambda: (params, opt_state))
        return params, opt_state, loss, finite, gnorm

    return params, opt_state, step_fn, loader, pack


def pipeline_config(arch: str, layers: int | None = None):
    """Model config of a pipeline-path ``--arch``.

    The small archs are fixed (independent of the mesh shape) so a
    checkpoint from one (pp, dp, zero, V) plan restores elastically onto
    any other; they keep the multi-device CPU drills fast.  ``uvit-h`` is
    the paper's UViT-H (``configs/uvit_h.CFG``) and ``hunyuan-dit``
    Hunyuan-DiT DiT-g/2 (``configs/hunyuan_dit.CFG``) at their published
    widths; ``layers`` cuts their depth only.
    """
    from repro.models.diffusion import SkipViTConfig, UViTConfig
    if arch in ("uvit-h", "hunyuan-dit"):
        if arch == "uvit-h":
            from repro.configs.uvit_h import CFG
        else:
            from repro.configs.hunyuan_dit import CFG
        if layers is None:
            return CFG
        least = 2 if arch == "uvit-h" else 4
        if layers < least or layers % 2:
            raise ValueError(f"--layers {layers}: {arch} needs an even "
                             f"block count of at least {least}")
        return dataclasses.replace(CFG, n_layers=layers)
    if layers is not None:
        raise ValueError("--layers cuts the depth of --arch uvit-h or "
                         "hunyuan-dit only")
    if arch == "skipvit":
        return SkipViTConfig("skipvit-pp", img_size=8, in_ch=4, patch=2,
                             d_model=64, n_heads=4, d_ff=128, n_classes=10,
                             n_enc=4, n_mid=2, n_dec=4)
    if arch == "uvit-nano":
        # smallest arch that still pipelines: keeps the multi-process
        # supervisor drill inside a CI time budget on a 1-core box
        return UViTConfig("uvit-nano", img_size=8, in_ch=4, patch=4,
                          d_model=32, n_layers=8, n_heads=2, d_ff=64,
                          n_classes=10)
    if arch == "uvit":
        return UViTConfig("uvit-pp", img_size=8, in_ch=4, patch=2,
                          d_model=64, n_layers=8, n_heads=4, d_ff=128,
                          n_classes=10)
    if arch == "hunyuan":
        # Hunyuan-DiT's block at CPU-drill widths, depth 8 (5 + 3 blocks)
        from repro.configs.smoke import hunyuan_tiny
        return hunyuan_tiny("hunyuan-pp", n_layers=8)
    raise ValueError(f"unknown pipeline arch {arch!r}; choose from "
                     f"{PIPELINE_ARCHS}")


def pipeline_loader(cfg, global_batch: int):
    """The pipeline path's data: class-conditioned synthetic latents at
    the config's resolution, channels and class count; for Hunyuan-DiT,
    text-conditioned ones with its CLIP and mT5 states."""
    from repro.data import (ShardedLoader, SyntheticLatentDataset,
                            SyntheticTextLatentDataset)
    from repro.models.diffusion import HunyuanDiTConfig
    if isinstance(cfg, HunyuanDiTConfig):
        ds = SyntheticTextLatentDataset(
            img_size=cfg.img_size, channels=cfg.in_ch,
            clip_len=cfg.clip_len, clip_dim=cfg.text_dim,
            t5_len=cfg.t5_len, t5_dim=cfg.t5_dim)
    else:
        ds = SyntheticLatentDataset(img_size=cfg.img_size,
                                    channels=cfg.in_ch,
                                    n_classes=cfg.n_classes)
    return ShardedLoader(ds, global_batch=global_batch)


def _pipeline_mesh(dp: int, pp: int):
    """(data, model) mesh over the first ``dp * pp`` devices JAX finds.
    A plan wider than the devices present fails here."""
    import jax
    devs = jax.devices()
    if dp * pp > len(devs):
        raise ValueError(
            f"the plan needs dp x pp = {dp} x {pp} = {dp * pp} devices but "
            f"JAX finds {len(devs)} ({devs[0].platform}); lower --dp/--pp "
            "or simulate devices on the CPU with --devices")
    return jax.make_mesh((dp, pp), ("data", "model"),
                         devices=devs[:dp * pp])


def _memory_line(exe) -> str:
    ma = exe.memory_analysis()
    if ma is None:
        return "memory analysis unavailable"
    gib = lambda b: f"{b / 2 ** 30:.2f} GiB"
    return (f"arguments {gib(ma.argument_size_in_bytes)}, outputs "
            f"{gib(ma.output_size_in_bytes)} (aliased "
            f"{gib(ma.alias_size_in_bytes)}), temporaries "
            f"{gib(ma.temp_size_in_bytes)}")


class _CompiledStep:
    """A jitted train step compiled ahead of its first call — timed apart
    from the step, with its memory analysis printed — and called with its
    inputs placed as the program reads them (a no-op for arrays already in
    place; places restored state and host-made batches).  The compile
    still happens inside step 0, where a supervisor's watchdog expects
    warm-up."""

    def __init__(self, jitted, shardings):
        self.jitted, self.shardings = jitted, shardings
        self.exe = None
        self.compile_s: float | None = None

    def __call__(self, *args):
        import time

        import jax
        args = jax.device_put(args, self.shardings)
        if self.exe is None:
            t0 = time.time()
            self.exe = self.jitted.lower(*args).compile()
            self.compile_s = time.time() - t0
            print(f"[train] step program compiled in {self.compile_s:.1f}"
                  f" s: {_memory_line(self.exe)}")
        return self.exe(*args)


def pipeline_plan(args):
    """``(cfg, compiled)``: the ``--arch`` config and its auto_pipeline
    plan for ``--dp`` x ``--pp`` (default pp: the devices JAX finds over
    dp), ZeRO stage, interleave, microbatches and wire dtype."""
    import jax

    from repro.models.diffusion import (HunyuanDiTConfig, SkipViTConfig,
                                        hunyuan_pipeline_graph,
                                        skipvit_pipeline_graph,
                                        uvit_pipeline_graph)
    from repro.runtime.adapters import diffusion_model_fns, \
        hunyuan_model_fns, skipvit_model_fns
    from repro.runtime.compile import auto_pipeline

    cfg = pipeline_config(args.arch, args.layers)
    dp = args.dp
    pp = args.pp or max(len(jax.devices()) // dp, 1)
    b = args.global_batch // args.microbatches
    if isinstance(cfg, SkipViTConfig):
        graph, fns = skipvit_pipeline_graph(cfg, batch=b), \
            skipvit_model_fns(cfg)
    elif isinstance(cfg, HunyuanDiTConfig):
        graph, fns = hunyuan_pipeline_graph(cfg, batch=b), \
            hunyuan_model_fns(cfg)
    else:
        graph, fns = uvit_pipeline_graph(cfg, batch=b), \
            diffusion_model_fns(cfg, "uvit")
    compiled = auto_pipeline(graph, fns, dp * pp, pipeline_devices=pp,
                             microbatches=args.microbatches, dp_size=dp,
                             zero_stage=args.zero_stage,
                             interleave=args.interleave,
                             wire_dtype=args.wire_dtype)
    return cfg, compiled


def pipeline_step(compiled, cfg, mesh, opt_cfg):
    """``(step, shardings)``: the jitted train step of the pipeline path
    on ``mesh`` — pipelined loss and grads, the non-finite guard, AdamW —
    and the shardings of its ``(params, opt_state, batch, rng, lr)``.
    The microbatches and their ``aux`` follow the config's model kind
    (``adapters.pipeline_microbatches``): Hunyuan-DiT's conditioning is
    computed from the edge parameters once a step, outside the executor.

    Stage stacks sit over ``"model"`` (ZeRO-2: one block dim over
    ``"data"`` too), edge params and inputs are replicated, and the step
    donates params and optimizer state, so the devices hold one copy of
    the training state.
    """
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.optim import adamw_update
    from repro.runtime import scopes
    from repro.runtime.adapters import pipeline_microbatches
    from repro.runtime.resilience import all_finite

    M = compiled.pcfg.num_microbatches
    loss_of_mb = compiled.bind(mesh)
    microbatches = pipeline_microbatches(cfg)

    def loss_of(params, batch, rng):
        mb, aux = microbatches(params[1], batch, rng, M)
        return loss_of_mb(params, mb, aux)

    def step(params, opt_state, batch, rng, lr):
        loss, grads = jax.value_and_grad(loss_of)(params, batch, rng)
        with jax.named_scope(scopes.OPTIMIZER):
            finite = all_finite(loss, grads)
            gnorm = _grad_norm(grads)
            # the update runs inside the taken branch, so with the state
            # donated the old and the new state are never live side by side
            params, opt_state = jax.lax.cond(
                finite,
                lambda: adamw_update(params, grads, opt_state, opt_cfg,
                                     lr=lr),
                lambda: (params, opt_state))
        return params, opt_state, loss, finite, gnorm

    rep = NamedSharding(mesh, P())
    p_shard = compiled.param_shardings(mesh)
    o_shard = {"m": p_shard, "v": p_shard, "step": rep}
    shardings = (p_shard, o_shard, rep, rep, rep)
    return jax.jit(step, donate_argnums=(0, 1),
                   out_shardings=shardings), shardings


def _build_pipeline_trainer(args, key, opt_cfg):
    """Trainer on the PULSE compile path: graph -> partition -> schedule
    -> table executor (runtime.compile) -> AdamW, on a (dp, pp) mesh of
    the devices present, with params and optimizer state created in
    place where the step reads them."""
    import jax

    from repro.optim import adamw_init

    cfg, compiled = pipeline_plan(args)
    print("[train] " + compiled.describe().replace("\n", "\n[train] "))
    mesh = _pipeline_mesh(compiled.pcfg.dp_size, compiled.pcfg.num_devices)
    step, shardings = pipeline_step(compiled, cfg, mesh, opt_cfg)
    p_shard, o_shard, rep = shardings[:3]
    params = jax.jit(compiled.init_pipeline_params,
                     out_shardings=p_shard)(key)
    opt_state = jax.jit(adamw_init, out_shardings=o_shard)(params)
    loader = pipeline_loader(cfg, args.global_batch)

    def pack(raw):
        return jax.device_put(raw, rep)

    return (params, opt_state, _CompiledStep(step, shardings), loader,
            pack, compiled)


if __name__ == "__main__":
    main()
