"""Pipeline-parallel executors (shard_map + ppermute over the 'model' axis).

These are the *closed-form* executors: their scan dataflow realizes the
wave/1F1B template orders through index arithmetic (``my_mb = t - d``,
``skip_row = t2 - (D-1) + 2d``).  The auto-compile path now lowers the
synthesized schedule itself via per-device step tables
(``runtime.schedule_exec``); the executors here remain the hand-written
differential references (and the memory-lean fast path for the template
orders they encode).

Three executors, all *differentiable end-to-end* — backward pipelines are
generated by JAX autodiff (ppermute transposes to the reversed ring), giving
the mirror-image backward schedule of the paper's Figs. 8/9 for free:

- ``wave_pipeline``: PULSE's folded schedule.  Device d owns encoder stage d
  and decoder stage 2D-1-d.  Phase 1 pipelines microbatches *down* the ring
  (enc stages), stashing skip activations and the turnaround stream as
  ``lax.scan`` outputs (local buffers — the paper's skip cache; zero
  inter-device skip traffic).  Phase 2 pipelines *up* the ring (dec stages),
  each device consuming its own stashed skips.  Per-microbatch boundary
  traffic: 2(D-1) activations, matching §V-B.

- ``linear_pipeline``: S = D sequential stages (classic 1F1B dataflow) for
  skip-free models; the degenerate case PULSE recovers when C = ∅.

- ``skip_carry_pipeline``: the paper's *baseline* semantics (Hanayo/1F1B on
  a UNet): sequential partition where skip tensors are stacked into the
  boundary payload, transferred hop-by-hop, and popped at the consumer.
  Exists to measure the skip-induced communication volume from compiled HLO.

Conventions
-----------
- Stage parameters are *stacked*: every leaf has leading dim S (or D),
  sharded over the 'model' mesh axis; inside shard_map each device sees its
  slice ``[1, ...]``.
- Microbatch inputs are stacked ``[M, b, ...]`` pytrees, sharded over
  'data' (+'pod') on the batch dim, replicated over 'model'.
- The returned loss is psum-reduced over every mesh axis (the 'data' psum
  transpose IS the DP gradient all-reduce).
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.runtime import scopes
from repro.runtime.compat import shard_map

Pytree = Any

# Wire formats the lowered executors may put on the ring.  bf16 halves the
# P2P bytes of every boundary hop (fwd AND the transposed backward hops —
# the cast's transpose converts cotangents through the same dtype);
# float32 is the exact-wire escape hatch the strict differential tests
# pin.  The closed-form executors below always run fp32 wire — they are
# the hand-written references the table executors are diffed against.
WIRE_DTYPES = ("bfloat16", "float32")


def ring_perms(D: int, *, wrap: bool = False
               ) -> tuple[list[tuple[int, int]], list[tuple[int, int]]]:
    """(down, up) ppermute pairs for a D-device pipeline ring.

    ``wrap=True`` closes the ring (D-1 -> 0 down, 0 -> D-1 up): the
    table-driven executors use the closed ring so interleaved (V > 1)
    plans can hand an activation from the last device's slot back to the
    first device's next slot.  Receivers only *store* arrivals their step
    tables mark, so the extra edge is inert for V = 1 schedules.  The
    closed-form executors keep the open ring (their index arithmetic
    assumes no wraparound).
    """
    if D <= 1:
        return [], []
    if wrap:
        return ([(i, (i + 1) % D) for i in range(D)],
                [(i, (i - 1) % D) for i in range(D)])
    down = [(i, i + 1) for i in range(D - 1)]
    up = [(i, i - 1) for i in range(1, D)]
    return down, up


def _wrap_remat(fn, cfg: "PipelineConfig"):
    if not cfg.remat:
        return fn
    if cfg.remat_policy == "dots":
        return jax.checkpoint(
            fn, policy=jax.checkpoint_policies.checkpoint_dots)
    if cfg.remat_policy == "dots_nb":
        return jax.checkpoint(
            fn, policy=jax.checkpoint_policies.dots_with_no_batch_dims_saveable)
    return jax.checkpoint(fn)


def tree_index(tree: Pytree, i) -> Pytree:
    """Dynamic index into leading dim of every leaf (clamped by lax)."""
    return jax.tree.map(
        lambda x: jax.lax.dynamic_index_in_dim(x, i, 0, keepdims=False), tree)


def tree_local(tree: Pytree) -> Pytree:
    """Drop the singleton device dim shard_map leaves on stacked params."""
    return jax.tree.map(lambda x: x[0], tree)


@jax.named_scope(scopes.ZERO_GATHER)
def zero_all_gather(tree: Pytree, gather_dims: Pytree,
                    data_axes: tuple[str, ...]) -> Pytree:
    """All-gather ZeRO-2 rest-sharded stage params on use.

    ``gather_dims`` mirrors ``tree`` with one int per leaf: the dim to
    gather over ``data_axes`` (``-1`` = replicated leaf, passed through)
    — the slot-view dims ``runtime.sharding.zero_stack_specs`` derived
    next to the stack in_specs.  Called inside the executors' remat'd
    stage closures, so backward re-gathers instead of saving the full
    leaf, and the gather's transpose (``psum_scatter``) delivers the
    reduce-scattered gradient shard for free.
    """
    def g(t, dim):
        if dim < 0:
            return t
        return jax.lax.all_gather(t, data_axes, axis=dim, tiled=True)
    return jax.tree.map(g, tree, gather_dims)


def _zeros_like_struct(tree: Pytree) -> Pytree:
    return jax.tree.map(jnp.zeros_like, tree)


# ===========================================================================
# Masked block scans (uneven-partition stages over padded stacks)
# ===========================================================================
#
# The auto-compile path (runtime.compile) pads every stage's block-parameter
# stack to a common row count and records the true per-device count; these
# scans run a whole (possibly padded) stage as one lax.scan, applying the
# block only on valid rows.  Padded rows execute on zero parameters and are
# masked out of both the activation and (via the where-transpose) the
# gradient, so uneven DP partitions reuse the same SPMD executor program.

def _stack_rows(stage_p: Pytree) -> int:
    return jax.tree.leaves(stage_p)[0].shape[0]


def scan_blocks(block_fn: Callable, stage_p: Pytree, x, count, *args):
    """Apply ``block_fn(bp, x, *args) -> x`` to rows ``[0, count)``."""
    def body(x, inp):
        i, bp = inp
        y = block_fn(bp, x, *args)
        return jnp.where(i < count, y, x), None

    x, _ = jax.lax.scan(body, x, (jnp.arange(_stack_rows(stage_p)), stage_p))
    return x


def scan_blocks_emit(block_fn: Callable, stage_p: Pytree, x, count, *args):
    """``block_fn -> (x, skip)``; returns (x, skips stacked [rows, ...]).

    Rows >= count stash garbage skips; consumers must not read them
    (scan_blocks_consume never does).
    """
    def body(x, inp):
        i, bp = inp
        y, skip = block_fn(bp, x, *args)
        return jnp.where(i < count, y, x), skip

    x, skips = jax.lax.scan(
        body, x, (jnp.arange(_stack_rows(stage_p)), stage_p))
    return x, skips


def scan_blocks_consume(block_fn: Callable, stage_p: Pytree, skips: Pytree,
                        x, count, skip_rows, *args):
    """``block_fn(bp, x, skip, *args)``; row i consumes stash row
    ``skip_rows[i]`` (``-1`` = no skip: a zeroed stash entry is passed).

    ``skip_rows`` is the producer/consumer pairing the layout derived from
    the partition's actual skip edges (``StageLayout.skip_rows``).  For a
    mirror-symmetric fold of a fully-paired graph it equals the classic
    LIFO pairing ``count-1-i``; for asymmetric folds (mid-block
    bottlenecks, sparse skips) it maps each consumer row to the stash row
    its collocated encoder stage actually wrote, and rows without a skip
    edge see zeros — models whose blocks consume additively (or ignore the
    skip) are unaffected on those rows.
    """
    rows = _stack_rows(stage_p)
    stash_rows = max(
        [leaf.shape[0] for leaf in jax.tree.leaves(skips)], default=1)

    def body(x, inp):
        i, bp, sr = inp
        skip = tree_index(skips, jnp.clip(sr, 0, stash_rows - 1))
        skip = jax.tree.map(
            lambda s: jnp.where(sr >= 0, s, jnp.zeros_like(s)), skip)
        y = block_fn(bp, x, skip, *args)
        return jnp.where(i < count, y, x), None

    x, _ = jax.lax.scan(
        body, x, (jnp.arange(rows), stage_p, jnp.asarray(skip_rows)))
    return x


@dataclasses.dataclass(frozen=True)
class PipelineConfig:
    num_devices: int            # D = size of the 'model' mesh axis
    num_microbatches: int       # M
    axis: str = "model"
    data_axes: tuple[str, ...] = ("data",)   # axes to psum the loss over too
    dp_size: int = 1            # product of data_axes sizes (static)
    remat: bool = True
    remat_policy: str | None = None   # None=full remat; "dots"=save matmuls
    wire_dtype: str = "bfloat16"      # boundary-hop dtype of the lowered
    #   table executors (see WIRE_DTYPES); "float32" = exact-wire escape
    #   hatch.  Closed-form executors ignore it (always fp32 references).
    overlap: bool = True              # table executors: issue each ring hop
    #   at the TOP of the next step's scan body (double-buffered pending
    #   payload) so the collective overlaps the following step's compute;
    #   False = synchronous lowering (hop at the bottom of the producing
    #   step), the bit-identical differential reference.  Values, arrival
    #   steps and liveness windows are the same either way — only the
    #   hop's position relative to compute changes.  Closed-form
    #   executors ignore it (always synchronous references).
    zero_stage: int = 0               # ZeRO sharding over the data axes:
    #   0 = replicated param/optimizer state per DP replica; 1 = only the
    #   optimizer state is sharded (train.steps applies the leaf-wise
    #   specs — the executors are untouched, grads still all-reduce via
    #   the psum transpose); 2 = the stage stacks are sharded AT REST
    #   over data_axes (runtime.sharding.zero_stack_specs in_specs), the
    #   table executors all-gather each leaf on use inside the remat'd
    #   scan body, and the gather's transpose reduce-scatters the grads.
    #   Closed-form executors reject stage 2 (no gather in their bodies).


# ===========================================================================
# Wave pipeline (PULSE)
# ===========================================================================

def make_wave_pipeline(
    cfg: PipelineConfig,
    *,
    embed_fn: Callable,       # (edge_p, mb, aux) -> tokens (b, n, d)
    enc_stage_fn: Callable,   # (stage_p, x, aux) -> (x_out, skips)
    dec_stage_fn: Callable,   # (stage_p, x, skips, aux) -> x_out
    loss_fn: Callable,        # (edge_p, x_final, mb, aux) -> scalar
) -> Callable:
    """Returns ``fn(enc_stack, dec_stack, edge_p, mbs, aux) -> loss``
    to be run under shard_map (see :func:`shard_pipeline`).

    - ``enc_stack``/``dec_stack``: stacked per-device stage params.
      ``dec_stack`` is ordered so index d = decoder stage 2D-1-d (the stage
      collocated with encoder stage d).
    - ``mbs``: stacked [M, ...] microbatch inputs; ``aux``: stacked [M, ...]
      per-microbatch conditioning seen by every stage (may be ``{}``).
    """
    D, M, axis = cfg.num_devices, cfg.num_microbatches, cfg.axis
    if M < D:
        # phase2's closed-form turn/skip row arithmetic (t2 + D - 1,
        # t2 - (D-1) + 2d) relies on jnp.clip staying within rows that were
        # actually produced, which only holds for M >= D; with fewer
        # microbatches the clip silently reads stale rows.
        raise ValueError(
            f"closed-form wave executor requires M >= D (got M={M}, "
            f"D={D}): its skip/turn row arithmetic reads stale rows for "
            "short iterations.  Lower through the table-driven executor "
            "(runtime.schedule_exec / auto_pipeline(executor='table')) or "
            "raise num_microbatches.")
    T = M + D - 1
    down_perm, up_perm = ring_perms(D)

    enc_stage = _wrap_remat(enc_stage_fn, cfg)
    dec_stage = _wrap_remat(dec_stage_fn, cfg)

    def fn(enc_stack, dec_stack, edge_p, mbs, aux):
        d = jax.lax.axis_index(axis)
        enc_p = tree_local(enc_stack)
        dec_p = tree_local(dec_stack)

        mb0 = tree_index(mbs, 0)
        aux0 = tree_index(aux, 0)
        x_proto = jax.eval_shape(embed_fn, edge_p, mb0, aux0)
        zero_x = jnp.zeros(x_proto.shape, x_proto.dtype)

        # ---------------- phase 1: encoder half (down-stream) ----------
        def phase1(down_in, t):
            src = jnp.clip(t, 0, M - 1)
            x0 = jax.lax.cond(
                d == 0,
                lambda: embed_fn(edge_p, tree_index(mbs, src),
                                 tree_index(aux, src)),
                lambda: zero_x)
            x_in = jnp.where(d == 0, x0, down_in)
            my_mb = jnp.clip(t - d, 0, M - 1)
            x_out, skips = enc_stage(enc_p, x_in, tree_index(aux, my_mb))
            down_next = jax.lax.ppermute(x_out, axis, down_perm)
            return down_next, (skips, x_out)

        _, (skip_ys, turn_ys) = jax.lax.scan(phase1, zero_x, jnp.arange(T))

        # ---------------- phase 2: decoder half (up-stream) ------------
        def phase2(up_in, t2):
            # device d runs its decoder stage for microbatch m = t2-(D-1-d);
            # its stashed skip row is (m + d) = t2 - (D-1) + 2d.
            turn = tree_index(turn_ys, jnp.clip(t2 + D - 1, 0, T - 1))
            x_in = jnp.where(d == D - 1, turn, up_in)
            skip_row = jnp.clip(t2 - (D - 1) + 2 * d, 0, T - 1)
            skips = tree_index(skip_ys, skip_row)
            my_mb = jnp.clip(t2 - (D - 1 - d), 0, M - 1)
            x_out = dec_stage(dec_p, x_in, skips, tree_index(aux, my_mb))
            up_next = jax.lax.ppermute(x_out, axis, up_perm)
            m = t2 - (D - 1)
            mc = jnp.clip(m, 0, M - 1)
            loss = jax.lax.cond(
                (d == 0) & (m >= 0),
                lambda: loss_fn(edge_p, x_out, tree_index(mbs, mc),
                                tree_index(aux, mc)),
                lambda: jnp.zeros((), jnp.float32))
            return up_next, loss

        _, losses = jax.lax.scan(phase2, zero_x, jnp.arange(T))
        total = jnp.sum(losses) / M
        return jax.lax.psum(total, (axis, *cfg.data_axes)) / cfg.dp_size

    return fn


# ===========================================================================
# Linear pipeline (1F1B dataflow; skip-free models)
# ===========================================================================

def make_linear_pipeline(
    cfg: PipelineConfig,
    *,
    embed_fn: Callable,       # (edge_p, mb) -> x (b, s, d)
    stage_fn: Callable,       # (stage_p, x) -> x
    loss_fn: Callable,        # (edge_p, x_final, mb) -> scalar
) -> Callable:
    """``fn(stack, edge_p, mbs) -> loss``.  S = D stages; embedding on
    device 0, head/loss on device D-1."""
    D, M, axis = cfg.num_devices, cfg.num_microbatches, cfg.axis
    T = M + D - 1
    down_perm, _ = ring_perms(D)
    stage = _wrap_remat(stage_fn, cfg)

    def fn(stack, edge_p, mbs):
        d = jax.lax.axis_index(axis)
        my_p = tree_local(stack)
        mb0 = tree_index(mbs, 0)
        x_proto = jax.eval_shape(embed_fn, edge_p, mb0)
        zero_x = jnp.zeros(x_proto.shape, x_proto.dtype)

        def tick(h_in, t):
            src = jnp.clip(t, 0, M - 1)
            x0 = jax.lax.cond(
                d == 0,
                lambda: embed_fn(edge_p, tree_index(mbs, src)),
                lambda: zero_x)
            x_in = jnp.where(d == 0, x0, h_in)
            x_out = stage(my_p, x_in)
            m = t - (D - 1)
            mc = jnp.clip(m, 0, M - 1)
            loss = jax.lax.cond(
                (d == D - 1) & (m >= 0),
                lambda: loss_fn(edge_p, x_out, tree_index(mbs, mc)),
                lambda: jnp.zeros((), jnp.float32))
            h_next = jax.lax.ppermute(x_out, axis, down_perm)
            return h_next, loss

        _, losses = jax.lax.scan(tick, zero_x, jnp.arange(T))
        total = jnp.sum(losses) / M
        return jax.lax.psum(total, (axis, *cfg.data_axes)) / cfg.dp_size

    return fn


# ===========================================================================
# Baseline: sequential partition with skip-carry payload (paper baseline)
# ===========================================================================

def make_skip_carry_pipeline(
    cfg: PipelineConfig,
    *,
    n_skip_slots: int,        # total skip tensors riding the payload
    embed_fn: Callable,
    enc_stage_fn: Callable,   # (stage_p, x, aux) -> (x, skips [k,...])
    dec_stage_fn: Callable,   # (stage_p, x, skips, aux) -> x
    loss_fn: Callable,
    skips_per_stage: int,
) -> Callable:
    """Sequential block-wise partition of a skip model over D devices:
    first D/2 devices run encoder stages, last D/2 run decoder stages, and
    every skip activation is carried in the ppermute payload across all
    intermediate boundaries (stacked / transferred / popped — §VII
    baselines).  Used to measure the paper's Fig. 3 communication blow-up.

    ``fn(enc_stack, dec_stack, edge_p, mbs, aux) -> loss``; both stacks are
    padded to D rows (enc rows valid on devices < D/2, dec rows on the rest).
    """
    D, M, axis = cfg.num_devices, cfg.num_microbatches, cfg.axis
    assert D % 2 == 0, "skip-carry baseline assumes half enc / half dec"
    T = M + D - 1
    down_perm, _ = ring_perms(D)
    k = skips_per_stage
    enc_stage = _wrap_remat(enc_stage_fn, cfg)
    dec_stage = _wrap_remat(dec_stage_fn, cfg)

    def fn(enc_stack, dec_stack, edge_p, mbs, aux):
        d = jax.lax.axis_index(axis)
        enc_p = tree_local(enc_stack)
        dec_p = tree_local(dec_stack)
        mb0 = tree_index(mbs, 0)
        aux0 = tree_index(aux, 0)
        x_proto = jax.eval_shape(embed_fn, edge_p, mb0, aux0)
        zero_x = jnp.zeros(x_proto.shape, x_proto.dtype)
        zero_stack = jnp.zeros((n_skip_slots,) + x_proto.shape, x_proto.dtype)

        def tick(carry, t):
            h_in, stack_in = carry
            src = jnp.clip(t, 0, M - 1)
            x0 = jax.lax.cond(
                d == 0,
                lambda: embed_fn(edge_p, tree_index(mbs, src),
                                 tree_index(aux, src)),
                lambda: zero_x)
            x_in = jnp.where(d == 0, x0, h_in)
            my_mb = jnp.clip(t - d, 0, M - 1)
            a = tree_index(aux, my_mb)

            # encoder branch: run enc stage, push k skips at rows d*k..
            def enc_branch():
                x_out, skips = enc_stage(enc_p, x_in, a)
                st = jax.lax.dynamic_update_slice_in_dim(
                    stack_in, skips.astype(stack_in.dtype), d * k, axis=0)
                return x_out, st

            # decoder branch: pop k skips for this stage (reverse order)
            def dec_branch():
                row = (D - 1 - d) * k
                skips = jax.lax.dynamic_slice_in_dim(stack_in, row, k, axis=0)
                x_out = dec_stage(dec_p, x_in, skips, a)
                return x_out, stack_in

            x_out, stack_out = jax.lax.cond(d < D // 2, enc_branch, dec_branch)
            m = t - (D - 1)
            mc = jnp.clip(m, 0, M - 1)
            loss = jax.lax.cond(
                (d == D - 1) & (m >= 0),
                lambda: loss_fn(edge_p, x_out, tree_index(mbs, mc),
                                tree_index(aux, mc)),
                lambda: jnp.zeros((), jnp.float32))
            # the whole (activation, skip-stack) payload crosses the boundary
            h_next = jax.lax.ppermute(x_out, axis, down_perm)
            stack_next = jax.lax.ppermute(stack_out, axis, down_perm)
            return (h_next, stack_next), loss

        _, losses = jax.lax.scan(tick, (zero_x, zero_stack), jnp.arange(T))
        total = jnp.sum(losses) / M
        return jax.lax.psum(total, (axis, *cfg.data_axes)) / cfg.dp_size

    return fn


# ===========================================================================
# shard_map wrapper
# ===========================================================================

def shard_pipeline(fn: Callable, mesh, *, stacked_args: int,
                   axis: str = "model",
                   batch_specs: Pytree | None = None,
                   stacked_specs: "tuple | None" = None) -> Callable:
    """Wrap a pipeline fn in shard_map.

    The first ``stacked_args`` arguments are stage stacks (sharded over
    ``axis`` on dim 0, replicated elsewhere); remaining args are replicated
    over ``axis``; ``batch_specs`` optionally gives full PartitionSpecs for
    the trailing (mbs, aux, ...) args so their batch dims shard over 'data'.
    ``stacked_specs`` (one spec pytree per stacked arg, ``None`` entries
    keep the blanket ``P(axis)``) overrides the stack in_specs — the
    ZeRO-2 path passes ``runtime.sharding.zero_stack_specs`` here so the
    stage stacks live sharded over the data axes at rest.  Output is a
    replicated scalar.
    """
    def specs_for(args):
        in_specs = []
        for i, a in enumerate(args):
            if i < stacked_args:
                over = (stacked_specs[i]
                        if stacked_specs is not None
                        and i < len(stacked_specs) else None)
                in_specs.append(over if over is not None
                                else jax.tree.map(lambda _: P(axis), a))
            elif batch_specs is not None and i - stacked_args < len(batch_specs):
                in_specs.append(batch_specs[i - stacked_args])
            else:
                in_specs.append(jax.tree.map(lambda _: P(), a))
        return tuple(in_specs)

    def wrapped(*args):
        in_specs = specs_for(args)
        return shard_map(fn, mesh=mesh, in_specs=in_specs, out_specs=P(),
                         check_vma=False)(*args)

    return wrapped
