"""Recovery MTTR: how fast the training supervisor turns a fault into a
running shrunk cluster.

Two real supervised runs (2 simulated hosts, P=2 x dp=2, uvit-nano),
one per fault class:

- ``recovery.hostdown.mttr_s`` — host 1 hard-exits after a checkpoint
  commit; MTTR = hostdown-detected event -> the relaunched generation's
  ``gen-live`` event (all surviving hosts training again on the shrunk
  plan).  Includes teardown, rollback, re-tune, relaunch and the new
  plan's jit compile — the full pipeline a real recovery pays.
- ``recovery.hang.mttr_s`` — host 0 freezes with its process alive; the
  clock additionally starts only after the watchdog's progress deadline
  (``hang.detect_age_s``, informational) has flagged the root host.

Wall-clock rows on shared CI runners are noisy and compile-heavy, so the
``--compare`` gate carries a deliberately loose tolerance (see
``REGRESSION_RULES`` in benchmarks/run.py); both scenarios share one jit
compilation cache (the hang scenario runs second and mostly measures
the compile-warm path).
"""
from __future__ import annotations

import os
import shutil
import tempfile

STEPS = 10


def _mttr(events: list[dict], detect_kind: str) -> tuple[float, dict]:
    detect = next(e for e in events if e["kind"] == detect_kind)
    live = next(e for e in events
                if e["kind"] == "gen-live" and e["gen"] > detect["gen"])
    return live["t"] - detect["t"], detect


def _drill(name: str, faults: str, tmp: str):
    from repro.launch.supervisor import (Supervisor, SupervisorConfig,
                                         read_events)
    cfg = SupervisorConfig(
        run_dir=os.path.join(tmp, name), num_hosts=2, devices_per_host=2,
        steps=STEPS, global_batch=8, arch="uvit-nano", dp=2, pp=2,
        microbatches=4, wire_dtype="float32", lr=1e-3, ckpt_every=4,
        faults=faults, stall_timeout=12.0, miss_budget=2, poll=0.2,
        backoff_base=0.2, log_every=4)
    res = Supervisor(cfg).run()
    if not res.ok or res.restarts != 1:
        raise RuntimeError(f"recovery drill {name} did not recover "
                           f"cleanly: {res.outcome}/{res.restarts}")
    return read_events(res.events_path)


def run(json_sink: dict | None = None) -> list[str]:
    tmp = tempfile.mkdtemp(prefix="repro_rec_")
    rows = []
    sink = {} if json_sink is None else json_sink.setdefault("recovery", {})
    try:
        events = _drill("hostdown", "hostdown@8:1", tmp)
        mttr, _ = _mttr(events, "hostdown")
        rows.append(f"recovery.hostdown.mttr_s,{mttr:.1f},"
                    "exit-detected -> shrunk cluster training (cold jit)")
        sink["hostdown"] = {"mttr_s": round(mttr, 2)}

        events = _drill("hang", "hang@6", tmp)
        mttr, detect = _mttr(events, "hang")
        rows.append(f"recovery.hang.mttr_s,{mttr:.1f},"
                    "watchdog-flagged -> shrunk cluster training "
                    "(warm jit)")
        rows.append(f"recovery.hang.detect_age_s,{detect['age']:.1f},"
                    "stall age at detection (~stall_timeout*miss_budget)")
        sink["hang"] = {"mttr_s": round(mttr, 2),
                        "detect_age_s": round(detect["age"], 2)}
    finally:
        shutil.rmtree(tmp, ignore_errors=True)
    return rows
