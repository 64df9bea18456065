"""A run of the harness with the timed path broken underneath comes out
``correct: false``, once for each fault a training cell can have; the same
run unbroken comes out ``correct: true``.  Each fault is planted in the
program (monkeypatched in the run's own process), at a tiny size on the
CPU; the harness's look for a chip is skipped."""
import pytest

from conftest import make_root, tiny_cell
from test_bench_dropin import RUN, run_cell

FAULTS = {
    # the step hands its state back unchanged
    "state_unchanged": """
import repro.optim as o
o.adamw_update = lambda params, grads, state, cfg, lr=None: (params, state)
""",
    # the second half of the batch left out, the mean over the first half
    "half_batch": """
import jax.numpy as jnp
import repro.runtime.adapters as ad
_orig = ad.make_diffusion_microbatches
def _half(*a, **k):
    mb, aux = _orig(*a, **k)
    h = mb["xt"].shape[0] // 2
    dup = lambda x: jnp.concatenate([x[:h], x[:h]])
    return {n: dup(x) for n, x in mb.items()}, {n: dup(x) for n, x in aux.items()}
ad.make_diffusion_microbatches = _half
""",
    # the ring hops between the chips carry nothing
    "no_exchange": """
import jax, jax.numpy as jnp
jax.lax.ppermute = lambda x, axis_name, perm: jax.tree.map(jnp.zeros_like, x)
""",
    # the loss altered where the step produces it
    "loss_altered": """
import jax
import repro.launch.train as tr
_orig = tr.pipeline_step
def _altered(*a, **k):
    step, sh = _orig(*a, **k)
    def f(*args):
        p, o, loss, fin, gn = step(*args)
        return p, o, loss * 1.05, fin, gn
    return jax.jit(f, donate_argnums=(0, 1), out_shardings=sh), sh
tr.pipeline_step = _altered
""",
}


def _planted(fault):
    return RUN.replace("from bench import harness",
                       FAULTS[fault] + "\nfrom bench import harness")


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return make_root(tmp_path_factory.mktemp("faults"), [
        tiny_cell(), tiny_cell("uvit_tiny.p2", pp=2, microbatches=4)])


@pytest.mark.parametrize("fault,cell", [
    ("state_unchanged", "uvit_tiny.r8.b8"),
    ("half_batch", "uvit_tiny.r8.b8"),
    ("no_exchange", "uvit_tiny.p2"),
    ("loss_altered", "uvit_tiny.r8.b8"),
])
def test_fault_is_not_correct(root, fault, cell, monkeypatch):
    import test_bench_dropin as d
    monkeypatch.setattr(d, "RUN", _planted(fault))
    res, err = d.run_cell(root, cell, devices=2)
    assert res["correct"] is False, err[-3000:]


def test_two_stage_cell_unbroken_is_correct(root):
    res, err = run_cell(root, "uvit_tiny.p2", devices=2)
    assert res["correct"] is True, err[-3000:]
