"""Readings that a cell's limits are set from, on the chip, in one process.

    python3 bench/calibrate.py --workload uvit_h8.r32.b32 \
        --seeds 1,2,3,4,5,6,7,8,9,10,11,12 --control-seeds 1,2,3 \
        --faults half_batch --out .bench/calib.json

For each of ``--seeds`` the program runs the harness's first
``CHECK_STEPS`` steps from that seed through the same compiled step as a
benchmark run, and its readings are compared with the plain
reference's: the lower readings.  For each of ``--control-seeds`` the reference computed with
float8 operands (the next precision below the configuration's bfloat16)
stands in the program's place, and so does the reference with each fault
of ``--faults`` planted (``half_batch``: the second half of every batch
replaced by the first; ``no_exchange``: nothing crosses the boundaries
between the pipeline's stages): the upper readings.  A step that returns
its state unchanged reads 1 on ``change_gap`` by construction and is not
run.  Writes every reading to ``--out`` as JSON and prints a summary.
"""
from __future__ import annotations

import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _seeds(text: str) -> list[int]:
    return [int(s) for s in text.split(",") if s]


def _raw(readings: dict) -> dict:
    """Losses and every leaf's gradient and change norm, flattened: the
    data any number of the comparison can be worked out from again."""
    from bench import harness

    return {"losses": readings["losses"],
            "grad": harness._flat(readings["grad"]),
            "change": harness._flat(readings["change"])}


def calibrate(root: str, workload: str, seeds, control_seeds, faults,
              *, require_tpu: bool = True, log=sys.stderr) -> dict:
    from bench import harness

    import jax

    _, cell, cfg = harness.load(root, workload)
    fam = harness.module(root, "families", cfg["family"])
    devices = harness.devices_for(cell, require_tpu)
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    checks = harness.CHECK_STEPS
    prog = harness.Program(fam, cfg, cell, devices, harness.Spans(), log)
    mine = {}
    for s in seeds:
        prog.start(s)
        mine[s] = prog.readings(checks)
        prog.state = None
        print(f"program seed {s}: losses {mine[s]['losses']}", file=log)
    prog.free()
    out = {"workload": workload, "program": {}, "control": {}, "faults": {},
           "readings": {"program": {s: _raw(r) for s, r in mine.items()},
                        "reference": {}, "control": {}}}
    refs = {}
    for s in sorted(set(seeds) | set(control_seeds)):
        t = time.perf_counter()
        refs[s] = fam.reference_run(cfg, cell, s, checks, devices)
        out["readings"]["reference"][s] = _raw(refs[s])
        print(f"reference seed {s}: {time.perf_counter() - t:.1f} s, losses "
              f"{refs[s]['losses']}", file=log)
        if s in mine:
            out["program"][s] = harness.compare(mine[s], refs[s])
            print(f"  program {out['program'][s]}", file=log)
    for s in control_seeds:
        ctl = fam.reference_run(cfg, cell, s, checks, devices,
                                operand="float8")
        out["control"][s] = harness.compare(ctl, refs[s])
        out["readings"]["control"][s] = _raw(ctl)
        print(f"control seed {s}: {out['control'][s]}", file=log)
        for f in faults:
            bad = fam.reference_run(cfg, cell, s, checks, devices, fault=f)
            out["faults"].setdefault(f, {})[s] = harness.compare(bad, refs[s])
            out["readings"].setdefault(f, {})[s] = _raw(bad)
            print(f"fault {f} seed {s}: {out['faults'][f][s]}", file=log)
    names = ("loss_gap", "grad_gap", "change_gap")
    summary = {}
    for group in ("program", "control"):
        vals = out[group].values()
        if vals:
            summary[group] = {n: [min(v[n] for v in vals),
                                  max(v[n] for v in vals)] for n in names}
    for f, per in out["faults"].items():
        summary[f] = {n: [min(v[n] for v in per.values()),
                          max(v[n] for v in per.values())] for n in names}
    summary["state_unchanged"] = {"change_gap": [1.0, 1.0]}
    out["summary"] = summary
    out["seconds"] = time.perf_counter() - T0
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=_seeds, required=True)
    ap.add_argument("--control-seeds", type=_seeds, default=[])
    ap.add_argument("--faults", default="",
                    type=lambda t: [f for f in t.split(",") if f])
    ap.add_argument("--out", required=True)
    args = ap.parse_args(argv)
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    from bench import harness

    try:
        out = calibrate(ROOT, args.workload, args.seeds, args.control_seeds,
                        args.faults)
    except harness.NoChip as e:
        print(f"bench/calibrate.py: {e}", file=sys.stderr)
        return 3
    os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
    with open(args.out, "w") as f:
        json.dump(out, f, indent=1)
    print(json.dumps(out["summary"]))
    return 0


if __name__ == "__main__":
    sys.exit(main())
