"""Elastic fault-tolerance drill over the production training driver.

Runs ``repro.launch.train.run`` in-process on 8 simulated host devices
(fp32 wire so trajectories compare at rtol 1e-4) and checks the
save -> kill -> elastic-restore round trip across plan changes:

- ``shrink``  — UViT: P=2 x dp=2 ZeRO-2 stopped abruptly mid-run resumes
  onto P=1 x dp=2 zero=0 (different plan fingerprint: de-stack/re-stack)
  with the uninterrupted run's loss trajectory and final model-space
  params; then the newest checkpoint shard is byte-flipped and a resume
  on the original plan detects the corruption via SHA-256, falls back to
  the previous complete step, and still reproduces the trajectory.
- ``vchange`` — SkipViT: V=2 x P=2 zero=0 resumes onto V=1 x P=2 ZeRO-2.

Usage: python tests/helpers/resilience_drill.py [shrink vchange ...]
Prints ``RESILIENCE DRILL: ALL OK`` when every scenario passes.
"""
import os
import sys
import tempfile

os.environ.setdefault("XLA_FLAGS",
                      "--xla_force_host_platform_device_count=8")

import numpy as np  # noqa: E402

BASE = ["--pipeline", "--devices", "8", "--dp", "2",
        "--microbatches", "2", "--global-batch", "4", "--steps", "6",
        "--ckpt-every", "2", "--log-every", "2", "--lr", "1e-3",
        "--wire-dtype", "float32", "--logical-params"]


def _run(extra):
    from repro.launch.train import _parse_args, run
    return run(_parse_args(BASE + extra))


def _losses_close(ref, got, what):
    for s, b in got.items():
        a = ref[s]
        assert abs(a - b) <= 1e-4 * abs(a) + 1e-6, \
            f"{what}: step {s} loss {b} != reference {a}"


def _params_close(ref, got, what):
    import jax
    for pa, pb in zip(jax.tree.leaves(ref), jax.tree.leaves(got)):
        np.testing.assert_allclose(np.asarray(pa), np.asarray(pb),
                                   rtol=1e-4, atol=1e-6, err_msg=what)


def scenario_shrink():
    from repro.checkpoint import latest_step
    from repro.runtime.resilience import corrupt_checkpoint

    plan_a = ["--arch", "uvit", "--pp", "2", "--zero-stage", "2"]
    ref = _run(plan_a)
    assert ref.losses and ref.logical_params is not None

    d = tempfile.mkdtemp(prefix="repro_drill_shrink_")
    killed = _run(plan_a + ["--ckpt-dir", d, "--faults", "stop@4"])
    assert max(killed.losses) == 3, "stop@4 should end after step 3"
    assert latest_step(d) == 4

    resumed = _run(["--arch", "uvit", "--pp", "1", "--zero-stage", "0",
                    "--ckpt-dir", d, "--resume"])
    assert resumed.resumed is not None and resumed.resumed.step == 4
    assert resumed.resumed.elastic, "P=2 -> P=1 must take the elastic path"
    _losses_close(ref.losses, resumed.losses, "shrink P=2->P=1 losses")
    _params_close(ref.logical_params, resumed.logical_params,
                  "shrink P=2->P=1 final params")
    print("[drill] shrink: elastic P=2 dp=2 zero2 -> P=1 dp=2 zero0 OK")

    # corrupt the newest checkpoint (step 6, written by the resumed run):
    # a further resume must detect it via SHA-256, fall back to step 4,
    # and still reproduce the reference trajectory.
    what = corrupt_checkpoint(d)
    print(f"[drill] shrink: {what}")
    assert latest_step(d) == 4, "corrupt step must fail verification"
    recovered = _run(plan_a + ["--ckpt-dir", d, "--resume"])
    assert recovered.resumed is not None and recovered.resumed.step == 4
    _losses_close(ref.losses, recovered.losses,
                  "corrupt-shard fallback losses")
    _params_close(ref.logical_params, recovered.logical_params,
                  "corrupt-shard fallback final params")
    print("[drill] shrink: corrupt-shard fallback to step 4 OK")


def scenario_vchange():
    from repro.checkpoint import latest_step

    plan_a = ["--arch", "skipvit", "--pp", "2", "--interleave", "2",
              "--zero-stage", "0"]
    ref = _run(plan_a)

    d = tempfile.mkdtemp(prefix="repro_drill_vchange_")
    _run(plan_a + ["--ckpt-dir", d, "--faults", "stop@4"])
    assert latest_step(d) == 4

    resumed = _run(["--arch", "skipvit", "--pp", "2", "--interleave", "1",
                    "--zero-stage", "2", "--ckpt-dir", d, "--resume"])
    assert resumed.resumed is not None and resumed.resumed.step == 4
    assert resumed.resumed.elastic, "V=2 -> V=1 must take the elastic path"
    _losses_close(ref.losses, resumed.losses, "V=2->V=1 losses")
    _params_close(ref.logical_params, resumed.logical_params,
                  "V=2->V=1 final params")
    print("[drill] vchange: elastic V=2 zero0 -> V=1 zero2 OK")


SCENARIOS = {"shrink": scenario_shrink, "vchange": scenario_vchange}


def main(argv):
    names = argv or list(SCENARIOS)
    for name in names:
        SCENARIOS[name]()
    print("RESILIENCE DRILL: ALL OK")


if __name__ == "__main__":
    main(sys.argv[1:])
