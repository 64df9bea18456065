"""The program's named scopes reach the compiled train step's ``op_name``
metadata, in the forward, recompute and backward passes, under the names
that the benchmark's trace reduction (``bench/scopes.py``) reads.

The one-device plan compiles in this process; the plans over several
devices run in a subprocess on virtual CPU devices."""
import json
import os
import subprocess
import sys

from bench import scopes as bs
from helpers.named_scopes import scopes_of, step_text
from repro.runtime import scopes

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ALL_PASSES = ["bwd", "fwd", "recompute"]
#: a wave plan on one device has no ring and no ZeRO gathers
ONE_DEVICE = set(bs.SCOPES) - {bs.HOP, bs.ZERO_GATHER}


def test_benchmark_reads_the_programs_scope_names():
    """``bench/scopes.py`` spells every scope as the program defines it,
    so a rename fails here, not in silence on the chip."""
    assert bs.SCOPES == scopes.ALL
    names = [n for n in vars(scopes) if n.isupper() and n != "ALL"]
    assert len(names) == len(scopes.ALL)
    for n in names:
        assert getattr(bs, n) == getattr(scopes, n), n


def test_one_device_step_names_every_scope_in_every_pass():
    found = scopes_of(step_text(1, 1, 0))
    assert ONE_DEVICE <= set(found), ONE_DEVICE - set(found)
    for s in (bs.STAGE_ENC, bs.STAGE_DEC, bs.ATTENTION, bs.MLP):
        assert found[s] == ALL_PASSES, (s, found[s])
    assert found[bs.OPTIMIZER] == ["fwd"]


def test_multi_device_steps_name_hops_and_zero_gathers():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, os.path.join(ROOT, "tests", "helpers",
                                      "named_scopes.py")],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-4000:]
    line = [ln for ln in proc.stdout.splitlines()
            if ln.startswith("SCOPES ")][-1]
    found = json.loads(line[len("SCOPES "):])
    wave = found["wave_p2"]
    assert ONE_DEVICE | {bs.HOP} <= set(wave), ONE_DEVICE - set(wave)
    # a hop rides forward, and its transpose is the backward hop
    assert {"bwd", "fwd"} <= set(wave[bs.HOP])
    for s in (bs.STAGE_ENC, bs.STAGE_DEC, bs.ATTENTION):
        assert wave[s] == ALL_PASSES, (s, wave[s])
    zero = found["wave_p2_dp2_zero2"]
    assert set(bs.SCOPES) <= set(zero), set(bs.SCOPES) - set(zero)
    linear = found["linear_p2"]
    assert {bs.EXECUTOR, bs.HOP, bs.RX_STORE, bs.STAGE_ENC, bs.EMBED,
            bs.HEAD, bs.LOSS_ALLREDUCE, bs.ATTENTION} <= set(linear), linear
    assert linear[bs.STAGE_ENC] == ALL_PASSES
