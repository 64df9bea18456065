"""PULSE auto-pipeline compile path: graph -> partition -> schedule -> executor.

This is the paper's end-to-end story wired together.  :func:`auto_pipeline`
takes a :class:`~repro.core.graph.BlockGraph`, a block-level model
description (:class:`PipelineModelFns`) and a device budget, then

1. **plans**: runs the hybrid tuner (§VI) — or a pinned partitioner call —
   to pick (P, G, b) and the skip-aware partition (§IV, Algorithm 1);
2. **schedules**: synthesizes the pipeline schedule from the partition's
   stage->device mapping (§V: wave / 1F1B templates via the greedy
   synthesizer, optionally the exact ILP) and validates every constraint
   family before anything executes;
3. **lowers**: builds a shard_map executor for the partition.  Unlike the
   hand-written executors' hard-wired S=D / S=2D even splits, stages here
   carry *padded block stacks* plus true per-device block counts — with
   independent encoder-/decoder-half counts and a skip-stash pairing
   derived from the graph's actual skip edges, so the uneven and
   mirror-asymmetric stage boundaries the DP partitioner emits for
   partially-skipped graphs run unchanged
   (masked block scans; see runtime.pipeline).  The execution *order* is
   lowered from the validated schedule itself: per-device step tables
   extracted by ``runtime.schedule_exec`` drive the scan body, so a
   different synthesized schedule (e.g. an ILP improvement) changes what
   runs.  ``executor="closed_form"`` selects the closed-form wave/1F1B
   executors instead — kept as differential references.

The returned :class:`CompiledPipeline` is adapter-compatible (``build`` /
``split_params`` / ``merge_params`` / ``init_pipeline_params``) so the
training step builders in ``train.steps`` drive it directly, and carries
the planning artefacts (choice, partition, schedule) for inspection.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Any, Callable, Sequence

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.core.graph import BlockGraph
from repro.core.hw import Hardware, TPU_V5E
from repro.core.partition import Partition, partition as partition_graph
from repro.core.schedule import Schedule, schedule_for_partition
from repro.core.tuner import TunerChoice, tune
from repro.runtime.compat import tree_to_host
from repro.runtime.pipeline import (PipelineConfig, make_linear_pipeline,
                                    make_wave_pipeline, scan_blocks,
                                    scan_blocks_consume, scan_blocks_emit,
                                    shard_pipeline)
from repro.runtime.schedule_exec import (make_linear_pipeline_from_schedule,
                                         make_wave_pipeline_from_schedule)

Pytree = Any


# ===========================================================================
# Model description consumed by the compiler
# ===========================================================================

@dataclasses.dataclass(frozen=True)
class PipelineModelFns:
    """Block-level callables + parameter layout for one model family.

    The graph handed to :func:`auto_pipeline` must have exactly one block
    per row of the model's stacked block parameters (edge params — embed,
    head, norms — live outside the graph and are replicated).

    ``split_blocks(params) -> (stacks, edge)`` where ``stacks`` is a
    1-tuple ``(blocks,)`` for a homogeneous stack (rows 0..n-1 in graph
    order) or a 2-tuple ``(enc_blocks, dec_blocks)`` when encoder and
    decoder blocks have different parameter structures (UNet/UViT).
    ``merge_blocks`` is the exact inverse.
    """

    init_fn: Callable                      # key -> params
    embed_fn: Callable                     # (edge_p, mb, aux) -> x
    loss_fn: Callable                      # (edge_p, x, mb, aux) -> scalar
    split_blocks: Callable                 # params -> (stacks, edge)
    merge_blocks: Callable                 # (stacks, edge) -> params
    block_fn: Callable | None = None       # (block_p, x, aux) -> x
    enc_block_fn: Callable | None = None   # (block_p, x, aux) -> (x, skip)
    dec_block_fn: Callable | None = None   # (block_p, x, skip, aux) -> x
    num_param_stacks: int = 1              # len(split_blocks(params)[0])


# ===========================================================================
# Stage layout: partition cuts -> padded per-device stacks
# ===========================================================================

@dataclasses.dataclass(frozen=True)
class StageLayout:
    """Mapping between a model's flat block stack and per-device stage-slot
    stacks for a (possibly uneven, mirror-asymmetric, interleaved)
    partition.

    Device ``d`` runs ``V`` encoder-half (prefix) stage slots and — for
    folded partitions — ``V`` decoder-half (suffix) slots;
    ``enc_slots[d][v]`` / ``dec_slots[d][v]`` name the pipeline stages in
    slot order (ascending stage id == the order the forward chain visits
    the device) and ``enc_counts[d][v]`` / ``dec_counts[d][v]`` their true
    block counts.  V == 1 recovers the classic one-(enc, dec)-pair-per-
    device fold; V > 1 is the interleaved (virtual-stage) layout that
    shrinks pipeline bubbles at the price of V padded weight shards per
    device.  All slots pad to ``enc_pad`` / ``dec_pad`` rows so one SPMD
    program covers every (device, slot).

    ``skip_rows[d][v][i]`` is the *flat* stash row device d's decoder slot
    v consumes at its row ``i``: ``src_slot * enc_pad + src_row`` into the
    device's ``[V * enc_pad]`` skip stash — derived from the partition's
    actual skip edges, not a mirror closed form; ``-1`` marks rows without
    a skip (they receive zeros).  Linear partitions use only
    ``enc_slots``/``enc_counts``/``enc_pad``.
    """

    partition: Partition
    enc_slots: tuple[tuple[int, ...], ...]
    dec_slots: tuple[tuple[int, ...], ...]
    enc_counts: tuple[tuple[int, ...], ...]
    dec_counts: tuple[tuple[int, ...], ...]
    enc_pad: int
    dec_pad: int
    skip_rows: tuple[tuple[tuple[int, ...], ...], ...] = ()

    # ---- legacy aliases (planning tests / describe output) -------------
    @property
    def V(self) -> int:
        """Interleave degree: stage slots per device and kind."""
        return len(self.enc_slots[0])

    @property
    def counts(self) -> tuple[int, ...]:
        """Per-device encoder-half block totals (legacy flat view)."""
        return tuple(sum(c) for c in self.enc_counts)

    @property
    def pad(self) -> int:
        return self.enc_pad

    @classmethod
    def from_partition(cls, part: Partition,
                       graph: BlockGraph | None = None) -> "StageLayout":
        """Lay out ``part``; ``graph`` supplies the skip edges that define
        the stash pairing.  Without a graph, folded layouts fall back to
        the LIFO mirror pairing (which requires V = 1 mirror-symmetric
        cuts — the only pairing derivable without edges); ``auto_pipeline``
        always passes the graph.
        """
        D = part.num_devices
        sizes = part.stage_sizes()
        if not part.folded:
            slots: list[list[int]] = [[] for _ in range(D)]
            for s in range(part.num_stages):
                slots[part.device_of_stage(s)].append(s)
            V = len(slots[0])
            if any(len(ss) != V for ss in slots):
                raise ValueError(
                    "linear partition is not an even interleave: devices "
                    f"hold {[len(ss) for ss in slots]} stage slots")
            enc_slots = tuple(map(tuple, slots))
            enc_counts = tuple(tuple(sizes[s] for s in ss)
                               for ss in enc_slots)
            pad = max(c for cs in enc_counts for c in cs)
            return cls(part, enc_slots, ((),) * D, enc_counts, ((),) * D,
                       pad, 0)
        S = part.num_stages
        half = S // 2
        enc: list[list[int]] = [[] for _ in range(D)]
        dec: list[list[int]] = [[] for _ in range(D)]
        for s in range(S):
            (enc if s < half else dec)[part.device_of_stage(s)].append(s)
        V = len(enc[0])
        if any(len(ss) != V for ss in enc) or any(len(ss) != V
                                                  for ss in dec) or V == 0:
            raise ValueError(
                "folded partition is not an even interleave: devices hold "
                f"{[(len(e), len(c)) for e, c in zip(enc, dec)]} "
                "(prefix, suffix)-half stage slots; the wave layout needs "
                "V of each per device")
        enc_slots = tuple(map(tuple, enc))
        dec_slots = tuple(map(tuple, dec))
        enc_counts = tuple(tuple(sizes[s] for s in ss) for ss in enc_slots)
        dec_counts = tuple(tuple(sizes[s] for s in ss) for ss in dec_slots)
        enc_pad = max(c for cs in enc_counts for c in cs)
        dec_pad = max(c for cs in dec_counts for c in cs)
        if graph is not None:
            skip_rows = cls._pair_skips(part, graph, enc_slots, dec_slots,
                                        enc_pad, dec_pad)
        else:
            if V != 1 or not part.mirror_symmetric():
                raise ValueError(
                    "mirror-asymmetric or interleaved folds need the block "
                    "graph to derive their skip pairing; call "
                    "StageLayout.from_partition(part, graph)")
            skip_rows = tuple(
                (tuple(enc_counts[d][0] - 1 - i if i < dec_counts[d][0]
                       else -1 for i in range(dec_pad)),)
                for d in range(D))
        return cls(part, enc_slots, dec_slots, enc_counts, dec_counts,
                   enc_pad, dec_pad, skip_rows)

    @staticmethod
    def _pair_skips(part: Partition, graph: BlockGraph,
                    enc_slots: tuple[tuple[int, ...], ...],
                    dec_slots: tuple[tuple[int, ...], ...],
                    enc_pad: int, dec_pad: int
                    ) -> tuple[tuple[tuple[int, ...], ...], ...]:
        """Per (device, dec slot): decoder row -> flat encoder stash row
        (``src_slot * enc_pad + src_row``), from the graph's skip edges."""
        D, cuts = part.num_devices, part.cuts
        V = len(enc_slots[0])
        rows = [[[-1] * dec_pad for _ in range(V)] for _ in range(D)]
        for e in graph.skips:
            s_src = part.stage_of_block(e.src)
            s_dst = part.stage_of_block(e.dst)
            d = part.device_of_stage(s_src)
            if part.device_of_stage(s_dst) != d:
                raise ValueError(
                    f"skip {e.src}->{e.dst} spans devices "
                    f"{d} and {part.device_of_stage(s_dst)}: the partition "
                    "violates collocation (validate_collocation)")
            if s_src not in enc_slots[d] or s_dst not in dec_slots[d]:
                raise ValueError(
                    f"skip {e.src}->{e.dst} is not encoder-half -> "
                    f"decoder-half on device {d} (stages {s_src}->{s_dst}): "
                    "the stash executors cache skips across the fold only")
            src_slot = enc_slots[d].index(s_src)
            dst_slot = dec_slots[d].index(s_dst)
            dec_row = e.dst - cuts[s_dst]
            enc_row = e.src - cuts[s_src]
            if rows[d][dst_slot][dec_row] != -1:
                raise ValueError(
                    f"block {e.dst} consumes two skips; one stash slot per "
                    "decoder row")
            rows[d][dst_slot][dec_row] = src_slot * enc_pad + enc_row
        return tuple(tuple(map(tuple, dev_rows)) for dev_rows in rows)

    def skip_consumers(self) -> tuple[tuple[tuple[int, ...], ...], ...]:
        """Per (device, dec slot): the encoder slots whose stash entries
        the decoder slot actually consumes (from ``skip_rows``).  Feeds
        the lowering's skip-liveness analysis: entries no decoder slot
        names are dead stores and their stash lifetime ends at the last
        *naming* decoder task, not the device's last decoder task."""
        return tuple(
            tuple(tuple(sorted({r // self.enc_pad for r in rows if r >= 0}))
                  for rows in dev)
            for dev in self.skip_rows)

    # ---- (device, slot) -> block-row ranges ----------------------------
    def enc_ranges(self) -> list[list[tuple[int, int]]]:
        cuts = self.partition.cuts
        return [[(cuts[s], cuts[s + 1]) for s in ss]
                for ss in self.enc_slots]

    def dec_ranges(self) -> list[list[tuple[int, int]]]:
        """Rows into the decoder-half stack (block index minus mid cut)."""
        part, cuts = self.partition, self.partition.cuts
        mid = cuts[part.num_stages // 2]
        return [[(cuts[s] - mid, cuts[s + 1] - mid) for s in ss]
                for ss in self.dec_slots]

    # ---- padded stacking (host-level; runs outside jit) ----------------
    def _stack(self, blocks: Pytree,
               ranges: Sequence[Sequence[tuple[int, int]]],
               pad: int) -> Pytree:
        def f(x):
            devs = []
            for dev_ranges in ranges:
                rows = []
                for lo, hi in dev_ranges:
                    r = x[lo:hi]
                    if hi - lo < pad:
                        z = jnp.zeros((pad - (hi - lo),) + r.shape[1:],
                                      r.dtype)
                        r = jnp.concatenate([r, z], 0)
                    rows.append(r)
                devs.append(jnp.stack(rows))
            return jnp.stack(devs)          # [D, V, pad, ...]

        return jax.tree.map(f, blocks)

    def _unstack(self, stacked: Pytree,
                 ranges: Sequence[Sequence[tuple[int, int]]]) -> Pytree:
        stacked = tree_to_host(stacked)   # sharded stacks: see tree_to_host
        order = sorted(
            ((d, v) for d in range(len(ranges))
             for v in range(len(ranges[d]))),
            key=lambda dv: ranges[dv[0]][dv[1]][0])

        def f(x):
            parts = [x[d, v, : ranges[d][v][1] - ranges[d][v][0]]
                     for d, v in order]
            return jnp.concatenate(parts, 0)

        return jax.tree.map(f, stacked)

    def split(self, stacks: tuple) -> tuple:
        """Model block stacks -> per-(device, slot) padded stage stacks."""
        part = self.partition
        if not part.folded:
            if len(stacks) != 1:
                raise ValueError("linear pipeline needs one block stack")
            return (self._stack(stacks[0], self.enc_ranges(), self.enc_pad),)
        mid = part.cuts[part.num_stages // 2]
        if len(stacks) == 1:
            enc_b = jax.tree.map(lambda x: x[:mid], stacks[0])
            dec_b = jax.tree.map(lambda x: x[mid:], stacks[0])
        else:
            enc_b, dec_b = stacks
            enc_rows = jax.tree.leaves(enc_b)[0].shape[0]
            if enc_rows != mid:
                # with two param structures the fold's turnaround must sit
                # exactly on the model's own enc/dec boundary; a fully
                # paired skip graph forces this, a sparse one may not
                raise ValueError(
                    f"partition turnaround cut at block {mid} but the "
                    f"model's encoder stack has {enc_rows} rows; two-stack "
                    "models need the mid cut on the stack boundary (pin it "
                    "with BlockGraph.turnaround, or use a homogeneous "
                    "stack)")
        return (self._stack(enc_b, self.enc_ranges(), self.enc_pad),
                self._stack(dec_b, self.dec_ranges(), self.dec_pad))

    def merge(self, stage_stacks: tuple, n_model_stacks: int) -> tuple:
        """Inverse of :meth:`split` (also correct for gradients)."""
        part = self.partition
        if not part.folded:
            return (self._unstack(stage_stacks[0], self.enc_ranges()),)
        enc_b = self._unstack(stage_stacks[0], self.enc_ranges())
        dec_b = self._unstack(stage_stacks[1], self.dec_ranges())
        if n_model_stacks == 1:
            return (jax.tree.map(
                lambda a, b: jnp.concatenate([a, b], 0), enc_b, dec_b),)
        return (enc_b, dec_b)


# ===========================================================================
# Compiled pipeline
# ===========================================================================

@dataclasses.dataclass(frozen=True)
class CompiledPipeline:
    """Planner output lowered to a runnable shard_map pipeline."""

    graph: BlockGraph
    partition: Partition
    schedule: Schedule
    layout: StageLayout
    pcfg: PipelineConfig
    model_fns: PipelineModelFns
    choice: TunerChoice | None = None      # set when the tuner drove the plan
    executor: str = "table"                # "table" | "closed_form"

    @property
    def folded(self) -> bool:
        return self.partition.folded

    # ---- parameter plumbing (adapter-compatible) -----------------------
    def split_params(self, params: Pytree) -> tuple:
        stacks, edge = self.model_fns.split_blocks(params)
        return self.layout.split(tuple(stacks)), edge

    def merge_params(self, stage_stacks: tuple, edge: Pytree) -> Pytree:
        stacks = self.layout.merge(tuple(stage_stacks),
                                   self.model_fns.num_param_stacks)
        return self.model_fns.merge_blocks(stacks, edge)

    def init_pipeline_params(self, key) -> tuple:
        return self.split_params(self.model_fns.init_fn(key))

    # ---- lowering artefacts --------------------------------------------
    def step_tables(self):
        """The lowered :class:`~repro.runtime.schedule_exec.StepTables`
        (memoized): step programs, channel activity masks and the proven
        liveness windows (W_down/W_up/W_turn/W_skip) the executors size
        their rotating buffers by."""
        from repro.runtime.schedule_exec import StepTables
        if not self.folded:
            return StepTables.from_schedule(
                self.schedule, folded=False,
                devices=self.partition.devices)
        return StepTables.from_schedule(
            self.schedule, folded=True, devices=self.partition.devices,
            skip_consumers=self.layout.skip_consumers())

    def state_spec(self) -> dict:
        """JSON-serializable spec of how this plan lays out training
        state at rest: partition cuts, stage->device map, the layout's
        slot/count/pad tables, (dp, zero_stage, V, M, wire_dtype) — what
        ``checkpoint.store`` records in every manifest and
        ``runtime.resilience`` de-stacks saved state through when the
        restore-time plan differs."""
        from repro.runtime.resilience import compiled_state_spec
        return compiled_state_spec(self)

    def fingerprint(self) -> str:
        """Digest of the state-layout-relevant subset of
        :meth:`state_spec` — equal fingerprints mean a checkpoint loads
        directly; different ones route through the elastic
        de-stack/re-stack path."""
        from repro.runtime.resilience import plan_fingerprint
        return plan_fingerprint(self.state_spec())

    def certify(self, *, name: str | None = None):
        """Statically verify the lowered plan and return the
        :class:`~repro.analysis.certificate.PlanCertificate`.

        Abstractly interprets the step tables (no execution): race- and
        deadlock-freedom of the ring hops, store/read matching on every
        rotating buffer, wire-dtype flow, and the liveness-window bounds
        — the proof ``python -m repro.analysis.verify`` re-checks
        offline.  Raises nothing on failure; inspect ``cert.ok`` /
        ``cert.violations`` (a freshly planned pipeline always
        certifies clean — a FAIL here means a planner/lowering bug).
        """
        from repro.analysis.certificate import certify_plan
        return certify_plan(self, name=name)

    # ---- ZeRO-2 stack sharding -----------------------------------------
    def _zero_layout(self) -> tuple:
        """(stacked_specs, gather_dims) for ZeRO-2 rest-sharded stage
        stacks, or ``(None, None)`` below stage 2 / without a dp axis.

        One entry per param stack: the ``P(axis, None, None, ...,
        "data", ...)`` in_specs :func:`runtime.sharding.zero_stack_specs`
        derives (``bind`` hands them to ``shard_pipeline``) and the
        matching slot-view gather dims the table executors all-gather on
        use.  Stack shapes come from ``eval_shape`` of the model's own
        init — no parameters are materialized.
        """
        if self.pcfg.zero_stage < 2 or self.pcfg.dp_size <= 1:
            return None, None
        from repro.runtime.sharding import zero_stack_specs
        stacks, _ = jax.eval_shape(
            lambda k: self.split_params(self.model_fns.init_fn(k)),
            jax.random.PRNGKey(0))
        specs, dims = [], []
        for st in stacks:
            sp, dm = zero_stack_specs(st, dp=self.pcfg.dp_size,
                                      axis=self.pcfg.axis,
                                      data_axes=self.pcfg.data_axes)
            specs.append(sp)
            dims.append(dm)
        return tuple(specs), tuple(dims)

    def param_shardings(self, mesh) -> tuple:
        """``NamedSharding`` pytree of ``(stage_stacks, edge)`` on
        ``mesh``: the layout the executor :meth:`bind` returns reads —
        each stack over the pipeline axis (ZeRO-2: one block dim over the
        data axes too), edge params replicated.  State placed with these
        is consumed without a reshard."""
        stacks, edge = jax.eval_shape(self.init_pipeline_params,
                                      jax.random.PRNGKey(0))
        specs, _ = self._zero_layout()
        if specs is None:
            specs = tuple(jax.tree.map(lambda _: P(self.pcfg.axis), st)
                          for st in stacks)
        named = lambda spec: NamedSharding(mesh, spec)
        return (tuple(jax.tree.map(named, sp,
                                   is_leaf=lambda x: isinstance(x, P))
                      for sp in specs),
                jax.tree.map(lambda _: named(P()), edge))

    # ---- executor ------------------------------------------------------
    def build(self) -> Callable:
        """Lower to an executor.

        ``executor="table"`` (default) lowers the *validated schedule
        itself*: per-device step tables extracted from ``self.schedule``
        drive the scan body (runtime.schedule_exec), so greedy and ILP
        schedules alike execute exactly as synthesized.
        ``executor="closed_form"`` selects the hand-written wave/1F1B
        executors whose scan dataflow realizes the template orders
        implicitly — kept as differential references.

        Folded: ``fn(enc_stack, dec_stack, edge, mbs, aux) -> loss``.
        Linear: ``fn(stack, edge, mbs) -> loss``.
        """
        if self.executor not in ("table", "closed_form"):
            raise ValueError(
                f"unknown executor {self.executor!r}; expected 'table' or "
                "'closed_form'")
        fns, pcfg, layout = self.model_fns, self.pcfg, self.layout
        axis = pcfg.axis
        if self.executor == "closed_form" and layout.V > 1:
            raise ValueError(
                f"closed-form executors realize one (enc, dec) stage slot "
                f"pair per device; this plan interleaves V={layout.V} "
                "slots — lower through executor='table'")
        if self.executor == "closed_form" and pcfg.zero_stage >= 2 \
                and pcfg.dp_size > 1:
            raise ValueError(
                "closed-form executors keep stage stacks replicated over "
                f"the data axes; zero_stage={pcfg.zero_stage} shards them "
                "at rest — lower through executor='table'")
        _, zero_dims = self._zero_layout()

        def my(table):
            # device-local lookup into a per-device host constant table
            return jnp.asarray(table, jnp.int32)[jax.lax.axis_index(axis)]

        def squeeze_slot(stage_p):
            # closed-form executors predate the slot axis: drop the V=1 dim
            return jax.tree.map(lambda t: t[0], stage_p)

        if self.folded:
            if fns.block_fn is None and (fns.enc_block_fn is None
                                         or fns.dec_block_fn is None):
                raise ValueError(
                    "folded pipeline needs model_fns.block_fn or both "
                    "enc_block_fn and dec_block_fn")
            enc_block = fns.enc_block_fn or (
                lambda bp, x, aux: (fns.block_fn(bp, x, aux), {}))
            dec_block = fns.dec_block_fn or (
                lambda bp, x, skip, aux: fns.block_fn(bp, x, aux))

            if self.executor == "table":
                # every slot carries its own count (asymmetric and
                # interleaved folds) and the stash pairing comes from the
                # partition's skip edges, resolved per (device, slot)
                def enc_stage_fn(stage_p, x, aux, slot):
                    return scan_blocks_emit(enc_block, stage_p, x,
                                            my(layout.enc_counts)[slot],
                                            aux)

                def dec_stage_fn(stage_p, x, skips, aux, slot):
                    return scan_blocks_consume(
                        dec_block, stage_p, skips, x,
                        my(layout.dec_counts)[slot],
                        my(layout.skip_rows)[slot], aux)

                return make_wave_pipeline_from_schedule(
                    pcfg, self.schedule, embed_fn=fns.embed_fn,
                    enc_stage_fn=enc_stage_fn, dec_stage_fn=dec_stage_fn,
                    loss_fn=fns.loss_fn,
                    devices=self.partition.devices,
                    skip_consumers=layout.skip_consumers(),
                    zero_dims=zero_dims)

            flat_enc = tuple(c[0] for c in layout.enc_counts)
            flat_dec = tuple(c[0] for c in layout.dec_counts)
            flat_rows = tuple(r[0] for r in layout.skip_rows)

            def enc_stage_cf(stage_p, x, aux):
                return scan_blocks_emit(enc_block, squeeze_slot(stage_p), x,
                                        my(flat_enc), aux)

            def dec_stage_cf(stage_p, x, skips, aux):
                return scan_blocks_consume(
                    dec_block, squeeze_slot(stage_p), skips, x,
                    my(flat_dec), my(flat_rows), aux)

            return make_wave_pipeline(
                pcfg, embed_fn=fns.embed_fn, enc_stage_fn=enc_stage_cf,
                dec_stage_fn=dec_stage_cf, loss_fn=fns.loss_fn)

        if fns.block_fn is None:
            raise ValueError("linear pipeline needs model_fns.block_fn")

        embed = lambda e, mb: fns.embed_fn(e, mb, None)
        loss = lambda e, x, mb: fns.loss_fn(e, x, mb, None)
        if self.executor == "table":
            def stage_fn(stage_p, x, slot):
                return scan_blocks(fns.block_fn, stage_p, x,
                                   my(layout.enc_counts)[slot], None)

            return make_linear_pipeline_from_schedule(
                pcfg, self.schedule, embed_fn=embed, stage_fn=stage_fn,
                loss_fn=loss,
                devices=self.partition.devices,
                zero_dims=zero_dims[0] if zero_dims is not None else None)

        def stage_cf(stage_p, x):
            return scan_blocks(fns.block_fn, squeeze_slot(stage_p), x,
                               my(tuple(c[0] for c in layout.enc_counts)),
                               None)

        return make_linear_pipeline(
            pcfg, embed_fn=embed, stage_fn=stage_cf, loss_fn=loss)

    def bind(self, mesh) -> Callable:
        """``loss(params, mbs[, aux])`` with params = (stage_stacks, edge),
        ready for jit/grad on a multi-device mesh."""
        fn = self.build()
        pcfg = self.pcfg
        axis, data = pcfg.axis, pcfg.data_axes
        sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
        missing = [a for a in (axis, *data) if a not in sizes]
        if missing:
            # the lowered executor psums over every configured axis; a mesh
            # without them would fail mid-trace with an unbound-axis error
            raise ValueError(
                f"mesh axes {mesh.axis_names} missing {missing} required by "
                "this plan (pass matching data_axes to auto_pipeline)")
        dp = math.prod(sizes[a] for a in data)
        if sizes[axis] != pcfg.num_devices or dp != pcfg.dp_size:
            # a size mismatch would not raise — it would silently mis-scale
            # the loss (dp) or gather clamped stage counts (model axis)
            raise ValueError(
                f"mesh sizes {sizes} do not match the plan "
                f"(model={pcfg.num_devices}, dp={pcfg.dp_size}); rebuild "
                f"with auto_pipeline(..., dp_size={dp})")

        def batch_spec(t):
            return jax.tree.map(
                lambda x: P(None, data)
                if data and getattr(x, "ndim", 0) >= 2 else P(), t)

        stacked_specs, _ = self._zero_layout()

        def wrap(edge, *batch_args):
            return shard_pipeline(
                fn, mesh, stacked_args=2 if self.folded else 1, axis=axis,
                batch_specs=(jax.tree.map(lambda _: P(), edge),
                             *(batch_spec(a) for a in batch_args)),
                stacked_specs=stacked_specs)

        if self.folded:
            def loss(params, mbs, aux):
                stacks, edge = params
                return wrap(edge, mbs, aux)(stacks[0], stacks[1], edge,
                                            mbs, aux)
        else:
            def loss(params, mbs):
                stacks, edge = params
                return wrap(edge, mbs)(stacks[0], edge, mbs)
        return loss

    def _fold_lines(self) -> list[str]:
        """The fold's turnaround block (pinned by the graph or chosen by
        the partitioner) and the blocks no skip edge touches."""
        part, graph = self.partition, self.graph
        if not part.folded:
            return []
        mid = part.cuts[part.num_stages // 2]
        how = ("pinned to the stack boundary" if graph.turnaround is not None
               else "chosen by the partitioner")
        ends = {b for e in graph.skips for b in (e.src, e.dst)}
        free = [b for b in range(graph.n) if b not in ends]
        return [f"  fold: turnaround at block {mid} ({how}); blocks without "
                f"a skip partner: {', '.join(map(str, free)) or 'none'}"]

    def describe(self) -> str:
        part, sched = self.partition, self.schedule
        V = self.layout.V
        kind = "folded wave" if part.folded else "linear 1F1B"
        if V > 1:
            kind += f", interleaved V={V}"
        lines = [
            f"auto_pipeline: S={part.num_stages} stages over "
            f"D={part.num_devices} devices ({kind}), "
            f"M={self.pcfg.num_microbatches} microbatches",
            f"  cuts={part.cuts} stage sizes={part.stage_sizes()}",
            (f"  layout: enc counts={self.layout.enc_counts} "
             f"dec counts={self.layout.dec_counts}"
             + ("" if part.mirror_symmetric() else " (asymmetric fold)")
             if part.folded else
             f"  layout: stage counts={self.layout.enc_counts}"),
            *self._fold_lines(),
            f"  schedule: makespan={sched.makespan} slots, "
            f"bubble={sched.bubble_ratio():.2f}",
            f"  executor: {self.executor}",
        ]
        if self.executor == "table":
            tabs = self.step_tables()
            live_d, live_u = tabs.live_hops
            mode = "overlapped" if self.pcfg.overlap else "synchronous"
            lines.append(
                f"  wire: {self.pcfg.wire_dtype}, live hops "
                f"{live_d}+{live_u}/{tabs.dense_hops} (down+up/dense), "
                f"windows W_down={tabs.W_down} W_up={tabs.W_up} "
                f"W_turn={tabs.W_turn} W_skip={tabs.W_skip} (M={sched.M})")
            lines.append(
                f"  comm: {mode}, exposed hops {tabs.exposed_hops} / "
                f"hidden {tabs.hidden_hops} (of {live_d + live_u} live)")
            lines.append(
                "  weight rows: running steps per device "
                f"{','.join(map(str, tabs.running_steps))} of "
                f"{tabs.num_steps} (each adds its weight gradient into "
                "one row)")
        if self.pcfg.dp_size > 1 or self.pcfg.zero_stage > 0:
            lines.append(
                f"  hybrid: dp={self.pcfg.dp_size} over "
                f"{self.pcfg.data_axes}, zero_stage={self.pcfg.zero_stage}")
        if self.choice is not None:
            c = self.choice
            lines.append(f"  tuner: P={c.P} G={c.G} b={c.b} M={c.M} "
                         f"zero={c.zero_stage} "
                         f"t/sample={c.t_sample*1e3:.3f} ms")
        return "\n".join(lines)


# ===========================================================================
# Entry point
# ===========================================================================

def auto_pipeline(
    graph: BlockGraph,
    model_fns: PipelineModelFns,
    N: int,
    hw: Hardware = TPU_V5E,
    *,
    microbatches: int | None = None,
    lam: float = 1.0,
    force_wave: bool | None = None,
    pipeline_devices: int | None = None,
    interleave: int | None = None,
    data_axes: tuple[str, ...] = ("data",),
    dp_size: int | None = None,
    zero_stage: int | None = None,
    remat: bool = True,
    remat_policy: str | None = None,
    use_ilp: bool = False,
    executor: str = "table",
    wire_dtype: str = "bfloat16",
    overlap: bool = True,
) -> CompiledPipeline:
    """Plan, schedule, and lower a pipeline for ``graph`` on ``N`` devices.

    By default the hybrid tuner (§VI) picks (P, G, b) — and, for wave
    plans, the interleave degree V — and supplies its partition;
    ``microbatches`` then defaults to the M the tuner's iteration-time
    score assumed (``TunerChoice.M``), and ``dp_size`` to the chosen G —
    the executed iteration matches the scored one.  Pass
    ``pipeline_devices`` to pin the pipeline degree and call the
    partitioner directly (deterministic; used by tests and the training
    driver, which already knows its mesh shape — ``dp_size`` defaults to 1
    there, ``microbatches`` to 2D folded / max(D, 2) linear).
    ``interleave`` pins V the same way (V stage slot pairs per device,
    S = 2VD folded / VD linear); with the tuner driving, pinning
    ``interleave`` restricts its search to that V.

    ``executor`` selects the lowering: ``"table"`` (default) executes the
    validated schedule via per-device step tables (runtime.schedule_exec);
    ``"closed_form"`` uses the hand-written wave/1F1B executors as
    differential references (these require M >= D and V = 1 for folded
    plans).

    ``wire_dtype`` sets the boundary-hop dtype of the table executors
    (default bf16 — cast-on-send, fp32 compute; backward hops ride the
    same dtype through the cast transposes).  ``"float32"`` is the
    exact-wire escape hatch the strict differential tests pin; closed-form
    executors are always fp32-wire references.

    ``overlap`` (default True) double-buffers the table executors' ring
    hops: each step's sends are issued at the top of the next step's scan
    body, before that step's compute, so XLA's latency-hiding scheduler
    can run the collective-permute concurrently with independent compute.
    Values, arrival steps, and liveness windows are identical either way
    — ``overlap=False`` is the synchronous reference lowering the
    differential tests compare against.  The tuner scores candidates with
    the matching comm term (hidden steady-state hops cost
    ``max(0, t_p2p - t_f)``, exposed ramp hops full ``t_p2p``).

    ``zero_stage`` selects ZeRO sharding over the data axes of the
    ``("data", "model")`` mesh: 0 replicates everything per DP rank, 1
    shards only optimizer state (train.steps applies the leaf-wise specs;
    executors are untouched), 2 additionally shards the stage parameter
    stacks at rest — the table executors all-gather each slot row on use
    inside the remat region, and the gather's transpose reduce-scatters
    the parameter gradients over ``data``.  With the tuner driving,
    ``None`` (default) searches stages {0, 1, 2} and ``peak_memory``
    charges each candidate its sharded param/optimizer bytes; pinning
    restricts the search.  With ``pipeline_devices`` pinned, ``None``
    means 0.
    """
    if zero_stage is not None and zero_stage not in (0, 1, 2):
        raise ValueError(f"zero_stage must be in (0, 1, 2), got {zero_stage}")
    choice: TunerChoice | None = None
    if pipeline_devices is not None:
        part = partition_graph(graph, pipeline_devices, hw=hw, lam=lam,
                               force_wave=force_wave,
                               interleave=interleave or 1)
        if graph.skips and not part.folded:
            raise ValueError(
                "graph has skip edges but the plan is linear: the linear "
                "executor has no skip transport, so skips would be "
                "silently dropped — skip graphs need a folded plan")
    else:
        if force_wave is not None:
            raise ValueError(
                "force_wave requires pipeline_devices: the tuner derives "
                "wave vs linear from graph.skips and would ignore it")
        drops: list[str] = []
        choices = tune(graph, N, hw=hw, lam=lam, drops=drops,
                       zero_stages=((zero_stage,) if zero_stage is not None
                                    else (0, 1, 2)),
                       interleave_options=(
                           (interleave,) if interleave is not None
                           else None),
                       overlap=overlap)
        pure_dp = sorted({(c.P, c.G, c.zero_stage) for c in choices
                          if c.partition is None or c.P <= 1})
        drops += [f"P={p} G={g}" + (f" zero{z}" if z else "")
                  + ": pure data parallelism "
                  "(P=1 plans carry no pipeline to lower)"
                  for p, g, z in pure_dp]
        keep = [c for c in choices if c.partition is not None and c.P > 1]
        if not keep:
            # every per-candidate drop reason the tuner and the P>1 filter
            # collected, in full — truncating this list hides the memory /
            # network constraint that actually killed the plan
            detail = "\n  ".join(drops) or "tuner enumerated no candidates"
            raise ValueError(
                f"tuner found no feasible pipeline plan for N={N}; "
                f"candidates considered:\n  {detail}")
        choice = keep[0]
        part = choice.partition

    D = part.num_devices
    if microbatches is not None:
        M = microbatches
    elif choice is not None:
        # execute the M the tuner scored (Eq. 15 assumed M = P) — the
        # planner and the executor must agree on the iteration shape
        M = choice.M
    else:
        M = 2 * D if part.folded else max(D, 2)
    if dp_size is None:
        dp_size = choice.G if choice is not None else 1
    if choice is not None and zero_stage is None:
        zero_stage = choice.zero_stage
    zero_stage = zero_stage or 0
    if zero_stage > 0 and dp_size <= 1:
        # nothing to shard over — a stage-1/2 request on a single replica
        # is the replicated plan; record it as such
        zero_stage = 0
    # Schedule synthesis + full constraint validation happens here; an
    # invalid plan raises before any executor is built.
    sched = schedule_for_partition(part, M, use_ilp=use_ilp)

    pcfg = PipelineConfig(num_devices=D, num_microbatches=M,
                          data_axes=data_axes, dp_size=dp_size,
                          zero_stage=zero_stage,
                          remat=remat, remat_policy=remat_policy,
                          wire_dtype=wire_dtype, overlap=overlap)
    layout = StageLayout.from_partition(part, graph)
    return CompiledPipeline(graph=graph, partition=part, schedule=sched,
                            layout=layout, pcfg=pcfg, model_fns=model_fns,
                            choice=choice, executor=executor)
