"""The comparison's control at a tiny size: the plain reference computed in
float8 (the next precision below the configuration's bfloat16), put in the
program's place under a full run of the harness, comes out ``correct:
false`` by the tiny cell's limits, while the same run with the program
comes out ``correct: true``."""
import pytest

import test_bench_dropin as d
from conftest import make_root, tiny_cell

CONTROL = """
_program_readings = harness.Program.readings
def _control_readings(self, steps):
    out = _program_readings(self, steps)
    ctl = self.fam.reference_run(self.cfg, self.cell, self.seed, steps,
                                 list(self.mesh.devices.flat),
                                 operand="float8")
    return {**out, **{n: ctl[n] for n in ("losses", "grad", "change")}}
harness.Program.readings = _control_readings
"""


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return make_root(tmp_path_factory.mktemp("control"), [tiny_cell()])


def test_float8_control_fails_and_the_program_passes(root, monkeypatch):
    res, err = d.run_cell(root, "uvit_tiny.r8.b8")
    assert res["correct"] is True, err[-3000:]
    monkeypatch.setattr(d, "RUN", d.RUN.replace(
        "from bench import harness\n", "from bench import harness\n"
        + CONTROL))
    res, err = d.run_cell(root, "uvit_tiny.r8.b8")
    assert res["correct"] is False, err[-3000:]
    assert any(c["value"] > c["limit"] for c in res["compared"].values())
