"""The table executors take each scan step's weight gradient as one row
(``runtime/schedule_exec.py::_SlotRows``).

A step's stage reads its slot's weights from the stacks; the gradient goes
through a zero-valued row space, ``[2V, pad, ...]`` for the leaves both
kinds of stage have, so the transposed scan adds one slot-sized row in
place into its accumulator.  Checked here: the step tables' rows, the
optimized HLO of the backward scan on a one-device plan, and loss and
gradients of one-device plans against the single-device model (the plans
over several devices, with idle steps, asymmetric pads, interleaving and
ZeRO-2, are the differentials of ``tests/helpers/auto_pipeline_equiv.py``).
"""
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.models.diffusion import (SkipViTConfig, UViTConfig,
                                    skipvit_apply, skipvit_pipeline_graph,
                                    uvit_apply, uvit_pipeline_graph)
from repro.models.layers import AttnConfig
from repro.models.lm import LMConfig, lm_pipeline_graph
from repro.runtime.adapters import (diffusion_model_fns, lm_model_fns,
                                    make_diffusion_microbatches,
                                    skipvit_model_fns)
from repro.runtime.compile import auto_pipeline
from repro.runtime.schedule_exec import IDLE, RUN_DEC, RUN_ENC

KEY = jax.random.PRNGKey(0)


# ---------------------------------------------------------------------------
# the fetch-row table
# ---------------------------------------------------------------------------

def _uvit(n_layers=8, d_ff=64):
    return UViTConfig("t", img_size=8, in_ch=4, patch=2, d_model=32,
                      n_layers=n_layers, n_heads=4, d_ff=d_ff, n_classes=10)


def _plan(kind):
    if kind == "wave-d2":
        cfg = _uvit()
        return auto_pipeline(uvit_pipeline_graph(cfg),
                             diffusion_model_fns(cfg, "uvit"), 2,
                             pipeline_devices=2, microbatches=4, lam=0.0)
    if kind == "wave-p4-m8":
        # the plan of the four-chip benchmark cell, at a tiny width
        cfg = _uvit(n_layers=32)
        return auto_pipeline(uvit_pipeline_graph(cfg),
                             diffusion_model_fns(cfg, "uvit"), 4,
                             pipeline_devices=4, microbatches=8)
    if kind == "wave-asym":
        cfg = SkipViTConfig("t", n_enc=3, n_mid=2, n_dec=3)
        g = skipvit_pipeline_graph(cfg, fwd_times=[1, 1, 4, .5, .5, .5, 1, 1])
        return auto_pipeline(g, skipvit_model_fns(cfg), 2,
                             pipeline_devices=2, microbatches=4, lam=0.0)
    if kind == "wave-interleaved":
        cfg = SkipViTConfig("t", n_enc=4, n_mid=2, n_dec=4)
        g = skipvit_pipeline_graph(
            cfg, fwd_times=[1, 1, 2, 4, 0.5, 0.5, 0.5, 1, 1, 2])
        return auto_pipeline(g, skipvit_model_fns(cfg), 2,
                             pipeline_devices=2, microbatches=4, lam=0.0,
                             interleave=2)
    cfg = LMConfig(name="t", vocab=64, d_model=32, n_layers=8,
                   attn=AttnConfig(32, 4, 2, 8), d_ff=64,
                   tied_embeddings=True)
    return auto_pipeline(lm_pipeline_graph(cfg), lm_model_fns(cfg), 2,
                         pipeline_devices=2, microbatches=4, lam=0.0,
                         interleave=2 if kind == "linear-interleaved" else 1)


@pytest.mark.parametrize("kind", ["wave-d2", "wave-p4-m8", "wave-asym",
                                  "wave-interleaved", "linear-interleaved"])
def test_fetch_rows_address_the_running_slot(kind):
    """Encoder (and linear) steps fetch row ``slot``, decoder steps row
    ``V + slot``, every row lies in the ``[2V]`` row space, and
    ``describe()`` counts each device's running steps."""
    cp = _plan(kind)
    tabs = cp.step_tables()
    V, row = tabs.V, tabs.fetch_row
    assert row.shape == tabs.sel.shape and row.dtype == np.int32
    enc, dec = tabs.sel == RUN_ENC, tabs.sel == RUN_DEC
    np.testing.assert_array_equal(row[enc], tabs.slot[enc])
    np.testing.assert_array_equal(row[dec], V + tabs.slot[dec])
    assert ((0 <= row) & (row < (2 * V if cp.folded else V))).all()
    assert dec.any() == cp.folded
    np.testing.assert_array_equal(tabs.running_steps,
                                  (tabs.sel != IDLE).sum(axis=1))
    # a fold runs each of its 2V stage slots once a microbatch
    tasks = (2 if cp.folded else 1) * V * cp.pcfg.num_microbatches
    assert (tabs.running_steps == tasks).all()
    line = (f"running steps per device "
            f"{','.join(str(tasks) for _ in range(tabs.D))} of "
            f"{tabs.num_steps}")
    assert line in cp.describe()
    if kind == "wave-p4-m8":
        # 16 tasks a device in 2M + 2(D-1) = 22 ticks; the schedule's F
        # placements sit on 28 of its steps
        assert tabs.num_steps == 22 and tasks == 16
        assert ("forward program: 22 ticks (28 schedule steps with a "
                "forward placement), idle ticks per device 6,6,6,6"
                in cp.describe())


# ---------------------------------------------------------------------------
# the backward scan's optimized HLO
# ---------------------------------------------------------------------------

_INSTR = re.compile(r"^\s*(?:ROOT )?%(\S+) = (.+?) ([\w\-]+)\(")
_HEAD = re.compile(r"^(?:ENTRY )?%?(\S+) \(.*\) -> .* \{$")


def _computations(text):
    """``{computation: [(name, shape, opcode, line, is_root)]}``."""
    comps, cur = {}, None
    for line in text.splitlines():
        m = _HEAD.match(line)
        if m:
            cur = comps.setdefault(m.group(1), [])
        elif line.startswith("}"):
            cur = None
        elif cur is not None and (m := _INSTR.match(line)):
            cur.append((m.group(1), m.group(2).split("{")[0], m.group(3),
                        line, line.lstrip().startswith("ROOT")))
    return comps


def _called(line, fusions):
    """Computations an instruction calls: loop bodies and conditions,
    branches, applied reductions; ``fusions`` adds fused computations."""
    keys = "calls|to_apply|body|condition" if fusions else \
        "to_apply|body|condition"
    out = re.findall(rf"\b(?:{keys})=%([\w.\-]+)", line)
    for grp in re.findall(r"branch_computations=\{([^}]*)\}", line):
        out += re.findall(r"%([\w.\-]+)", grp)
    return out


def _reach(comps, root, fusions):
    seen, todo = set(), [root]
    while todo:
        c = todo.pop()
        if c in seen or c not in comps:
            continue
        seen.add(c)
        for ins in comps[c]:
            todo += _called(ins[3], fusions)
    return seen


def _backward_loop(comps, entry):
    """The body of the backward tick loop: of the entry's transposed
    loops, the one holding the most instructions (the other is the loss
    head's, split off by XLA)."""
    bodies = [re.search(r"body=%([\w.\-]+)", ins[3]).group(1)
              for ins in comps[entry]
              if ins[2] == "while" and "transpose(" in ins[3]]
    return max(bodies, key=lambda b: sum(
        len(comps[c]) for c in _reach(comps, b, True)))


def _effective(comps, ins):
    """An instruction's opcode; a fusion's is its fused root's."""
    if ins[2] != "fusion":
        return ins[2]
    fused = re.search(r"calls=%([\w.\-]+)", ins[3]).group(1)
    return next(i[2] for i in comps[fused] if i[4])


def test_backward_adds_weight_rows_in_place():
    """One device, V=1, M=2, float32 parameters (no bf16 converts on the
    CPU): in the backward tick loop no instruction is a ``broadcast`` or an
    ``add`` the shape of a stage stack ``[V, pad, ...]`` or of the shared
    row space ``[2V, pad, ...]``, and every shared leaf's gradient reaches
    the loop's carry through a ``dynamic-update-slice`` of its row space.

    Checked for the leaves both kinds have: at V=1 a leaf only one kind has
    (the decoder's skip projection) is one slot, so the add of its row is
    as large as its stack.  ``d_ff`` differs from ``2 * d_model`` so the
    skip projection's shape is no MLP weight's."""
    cfg = _uvit(n_layers=6, d_ff=96)
    M, B = 2, 4
    cp = auto_pipeline(uvit_pipeline_graph(cfg, batch=B // M),
                       diffusion_model_fns(cfg, "uvit"), 1,
                       pipeline_devices=1, microbatches=M,
                       wire_dtype="float32")
    assert cp.layout.V == 1 and cp.layout.enc_pad == cp.layout.dec_pad == 3
    state = cp.split_params(cp.model_fns.init_fn(KEY))
    enc, dec = state[0]
    dec_at = dict(jax.tree_util.tree_flatten_with_path(dec)[0])
    shared = [leaf for path, leaf in
              jax.tree_util.tree_flatten_with_path(enc)[0]
              if path in dec_at and dec_at[path].shape == leaf.shape]
    assert len(shared) >= 8             # attention, MLP and norms

    def hlo(shape):                     # [D=1, V, pad, ...] -> "f32[...]"
        return f"f32[{','.join(map(str, shape))}]"

    stack = {hlo(leaf.shape[1:]) for leaf in shared}
    rows = {hlo((2 * leaf.shape[1],) + leaf.shape[2:]) for leaf in shared}
    assert not stack & rows

    batch = {"latents": jax.random.normal(KEY, (B, 8, 8, 4)),
             "labels": jnp.arange(B) % 10}
    mb, aux = make_diffusion_microbatches(batch, KEY, M, cfg, "uvit")
    loss = cp.bind(jax.make_mesh((1, 1), ("data", "model")))
    text = jax.jit(jax.grad(loss)).lower(state, mb, aux).compile().as_text()
    comps = _computations(text)
    entry = re.search(r"^ENTRY %?(\S+) ", text, re.M).group(1)
    body = _backward_loop(comps, entry)
    instrs = [ins for c in _reach(comps, body, False) for ins in comps[c]]
    whole = [(ins[0], ins[1], _effective(comps, ins)) for ins in instrs
             if ins[1] in stack | rows
             and _effective(comps, ins) in ("add", "broadcast")]
    assert not whole, whole[:8]
    updated = {ins[1] for ins in instrs
               if _effective(comps, ins) == "dynamic-update-slice"}
    assert rows <= updated, rows - updated


# ---------------------------------------------------------------------------
# loss and gradients of one-device plans
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("arch", ["uvit", "skipvit"])
def test_one_device_gradients_match_the_model(arch):
    """A one-device fold (V=1, M=2, float32 wire) against the
    single-device model: loss and every leaf's gradient at rtol 1e-4.  The
    UViT plan fetches a decoder-only leaf beside the shared rows; SkipViT's
    two kinds share every leaf."""
    M, B = 2, 4
    if arch == "uvit":
        cfg = _uvit(n_layers=6, d_ff=96)
        graph, fns, apply = (uvit_pipeline_graph(cfg, batch=B // M),
                             diffusion_model_fns(cfg, "uvit"), uvit_apply)
    else:
        cfg = SkipViTConfig("t", n_enc=3, n_mid=2, n_dec=3)
        graph, fns, apply = (skipvit_pipeline_graph(cfg, batch=B // M),
                             skipvit_model_fns(cfg), skipvit_apply)
    cp = auto_pipeline(graph, fns, 1, pipeline_devices=1, microbatches=M,
                       wire_dtype="float32")
    assert cp.folded and cp.layout.V == 1
    params = cp.model_fns.init_fn(KEY)
    batch = {"latents": jax.random.normal(KEY, (B, 8, 8, 4)),
             "labels": jnp.arange(B) % 10}
    mb, aux = make_diffusion_microbatches(batch, KEY, M, cfg, "uvit")
    loss = cp.bind(jax.make_mesh((1, 1), ("data", "model")))
    lp, gp = jax.jit(jax.value_and_grad(loss))(cp.split_params(params),
                                               mb, aux)

    def ref(p):
        return jnp.mean(jnp.asarray([jnp.mean(jnp.square(
            apply(p, mb["xt"][m], aux["t"][m], {"labels": mb["labels"][m]},
                  cfg) - mb["noise"][m])) for m in range(M)]))

    with jax.default_matmul_precision("highest"):
        lr, gr = jax.jit(jax.value_and_grad(ref))(params)
    np.testing.assert_allclose(float(lp), float(lr), rtol=1e-4)
    got = cp.merge_params(*gp)
    for (path, a), b in zip(jax.tree_util.tree_flatten_with_path(got)[0],
                            jax.tree.leaves(gr)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-4,
                                   atol=1e-6, err_msg=str(path))
