"""End-to-end training-supervisor drill: detect -> rollback -> shrink.

Launches a REAL 2-host supervised run (the supervisor spawns one
``repro.launch.train`` worker subprocess per simulated host, P=2 x dp=2,
fp32 wire) and kills it mid-run:

- ``hostdown`` — host 1 hard-exits after step 7 (``hostdown@8:1``): the
  supervisor sees the exit code, rolls back to the step-8 checkpoint,
  re-tunes onto the surviving host (dp=1 x P=2) and resumes;
- ``hang``     — host 0 stalls before step 6 (``hang@6``, a stuck
  collective: the process stays alive, its heartbeat step freezes; host
  1 wedges later at the step-8 commit barrier): the watchdog flags the
  ROOT hung host within ``stall_timeout * miss_budget``, the supervisor
  kills the generation, rolls back to step 4 and resumes shrunk.

Both scenarios must finish with the uninterrupted reference loss
trajectory (single process, same plan, no faults) at rtol 1e-4, with
the full detect/rollback/shrink/restart event sequence in events.jsonl.

Scenarios share the checkout's persistent compilation cache (see
``repro.launch.compile_cache``; reference plan == generation 0's plan, so
workers mostly reuse the reference run's compilations).

Usage: python tests/helpers/supervisor_drill.py [hostdown hang ...]
Prints ``SUPERVISOR DRILL: ALL OK`` when every scenario passes.
"""
import os
import sys
import tempfile

os.environ.setdefault("XLA_FLAGS",
                      "--xla_force_host_platform_device_count=4")

STEPS = 12
PLAN = ["--arch", "uvit-nano", "--pipeline", "--devices", "4",
        "--dp", "2", "--pp", "2", "--microbatches", "4",
        "--global-batch", "8", "--steps", str(STEPS), "--lr", "1e-3",
        "--wire-dtype", "float32", "--log-every", "4"]

_REF = {}


def _reference() -> dict:
    """Uninterrupted single-process trajectory on generation 0's plan."""
    if not _REF:
        from repro.launch.train import _parse_args, run
        res = run(_parse_args(PLAN))
        assert len(res.losses) == STEPS
        _REF.update(res.losses)
    return _REF


def _drill(name: str, faults: str, rollback_step: int,
           detect_kind: str) -> None:
    from repro.launch.supervisor import (Supervisor, SupervisorConfig,
                                         format_status, read_events)

    ref = _reference()
    d = tempfile.mkdtemp(prefix=f"repro_sup_{name}_")
    cfg = SupervisorConfig(
        run_dir=d, num_hosts=2, devices_per_host=2, steps=STEPS,
        global_batch=8, arch="uvit-nano", dp=2, pp=2, microbatches=4,
        wire_dtype="float32", lr=1e-3, ckpt_every=4, faults=faults,
        stall_timeout=8.0, miss_budget=2, poll=0.2, backoff_base=0.2,
        log_every=4)
    res = Supervisor(cfg).run()

    assert res.ok and res.outcome == "done", \
        f"{name}: supervisor ended {res.outcome}"
    assert res.generations == 2 and res.restarts == 1, \
        f"{name}: expected exactly one recovery, got " \
        f"{res.generations} gens / {res.restarts} restarts"
    assert res.final_hosts == 1 and res.final_plan == (1, 2, 0), \
        f"{name}: expected shrink to dp=1 x P=2 on 1 host, got " \
        f"{res.final_plan} on {res.final_hosts}"

    events = read_events(res.events_path)
    kinds = [e["kind"] for e in events]
    for k in (detect_kind, "rollback", "shrink", "restart", "gen-live",
              "done"):
        assert k in kinds, f"{name}: no {k!r} event in {kinds}"
    rb = next(e for e in events if e["kind"] == "rollback")
    assert rb["step"] == rollback_step, \
        f"{name}: rolled back to {rb['step']}, expected {rollback_step}"
    detect = next(e for e in events if e["kind"] == detect_kind)
    if detect_kind == "hang":
        # detected within the watchdog timeout (+ one poll of slack)
        budget = cfg.stall_timeout * cfg.miss_budget + 5 * cfg.poll
        assert detect["age"] <= budget, \
            f"{name}: hang detected after {detect['age']}s > {budget}s"
        assert detect["host"] == 0, \
            f"{name}: hang attributed to host {detect['host']}, not root 0"

    assert sorted(res.losses) == list(range(STEPS)), \
        f"{name}: merged trajectory incomplete: {sorted(res.losses)}"
    for s in range(STEPS):
        a, b = ref[s], res.losses[s]
        assert abs(a - b) <= 1e-4 * abs(a) + 1e-6, \
            f"{name}: step {s} loss {b} != reference {a}"

    status = format_status(d)
    assert detect_kind in status and "rollback" in status, status
    print(f"[drill] {name}: detect({detect_kind}) -> rollback("
          f"{rollback_step}) -> shrink(dp=1 x P=2) -> resume OK, "
          f"trajectory uninterrupted over {STEPS} steps")


def scenario_hostdown():
    # host 1 dies right after the step-8 checkpoint commits: rollback
    # loses nothing, the shrunk plan replays only steps 8..11
    _drill("hostdown", "hostdown@8:1", rollback_step=8,
           detect_kind="hostdown")


def scenario_hang():
    # host 0 freezes before step 6: last complete checkpoint is step 4
    # (host 1 parks its step-8 shard but the commit never closes)
    _drill("hang", "hang@6", rollback_step=4, detect_kind="hang")


SCENARIOS = {"hostdown": scenario_hostdown, "hang": scenario_hang}


def main(argv):
    names = argv or list(SCENARIOS)
    for name in names:
        SCENARIOS[name]()
    print("SUPERVISOR DRILL: ALL OK")


if __name__ == "__main__":
    main(sys.argv[1:])
