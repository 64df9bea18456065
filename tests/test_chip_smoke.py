"""chip_smoke.py: refuses a host without a TPU, and its phases hold at a
tiny size on the CPU.

Both run in subprocesses: the phases call the training driver, which turns
on the persistent compilation cache for the whole process.
"""
import os
import subprocess
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

PHASES = """
import chip_smoke as c
ref = c.reference_loss("uvit", None, 8)
for dp, pp, zero in ((1, 1, 0), (2, 2, 2)):
    res = c.train_plan("uvit", None, dp, pp, zero, 8, 4, 3)
    c.check_plan(res, ref, 1, must_fall=False)
    assert res.compile_s > 0 and len(res.step_s) == 3
print("PHASES OK")
"""


def _env(**extra):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **extra)
    env["PYTHONPATH"] = os.pathsep.join(
        [ROOT, os.path.join(ROOT, "src"), env.get("PYTHONPATH", "")])
    return env


def test_chip_smoke_refuses_a_host_without_tpu():
    proc = subprocess.run([sys.executable, "chip_smoke.py"], cwd=ROOT,
                          env=_env(), capture_output=True, text=True,
                          timeout=300)
    assert proc.returncode != 0
    assert "no TPU found" in proc.stderr
    assert '"ok"' not in proc.stdout


def test_chip_smoke_phases_on_cpu():
    """The one-chip plan and the P=2 x dp=2 ZeRO-2 plan of a small UViT
    train a few steps through the driver, and each step-0 pipelined loss
    agrees with the float32 single-device reference."""
    proc = subprocess.run(
        [sys.executable, "-c", PHASES], cwd=ROOT, capture_output=True,
        text=True, timeout=600,
        env=_env(XLA_FLAGS="--xla_force_host_platform_device_count=4"))
    assert proc.returncode == 0, proc.stdout[-3000:] + proc.stderr[-3000:]
    assert "PHASES OK" in proc.stdout
    assert proc.stdout.count("step-0 loss") == 2
