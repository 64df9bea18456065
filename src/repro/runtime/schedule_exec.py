"""Table-driven pipeline executors lowered from a validated Schedule.

The closed-form executors in ``runtime.pipeline`` realize the wave / 1F1B
orders through index arithmetic baked into the scan body (``my_mb = t - d``,
``skip_row = t2 - (D-1) + 2d``).  A synthesized
:class:`~repro.core.schedule.Schedule` — greedy *or* ILP — therefore never
changed what actually ran, and planner/executor disagreements stayed
invisible.  This module makes the Schedule the single source of truth:

1. :class:`StepTables` extracts, per device, a dense *forward step program*
   from the schedule's F placements: which task (encoder/decoder selector
   and *stage slot* — a device runs V slots per kind under an interleaved
   S = 2VD plan) runs at each step, on which microbatch, which receive
   slot the incoming boundary activation lands in, whether the slot
   embeds / reads / writes the turnaround buffer, and when to emit the
   loss.  Every cross-device dependency is checked against the
   synchronous-scan dataflow at lowering time — a schedule the executor
   could not realize raises ``ValueError`` here instead of silently
   computing garbage.  Pass the stage->device mapping as a ``devices``
   tuple to memoize the lowering per (schedule, partition).

2. :func:`make_wave_pipeline_from_schedule` /
   :func:`make_linear_pipeline_from_schedule` lower those tables into
   shard_map executors.  The scan body reads its (selector, slot,
   microbatch, receive slot, loss mask) from the precomputed per-device
   arrays; parameters carry a leading ``[V, pad, ...]`` slot axis indexed
   per step and the rings wrap so interleaved slot boundaries cross
   device D-1 -> 0.  Any *valid* schedule — including ILP schedules whose
   step timing differs from the greedy templates, and interleaved V > 1
   plans — executes exactly as synthesized.

Backward placements (virtual stage >= S) are realized by JAX autodiff as
the transposed scan, mirroring the forward order — the same convention as
the closed-form executors (paper Figs. 8/9 backward halves).

Communication & memory lowering: the step tables are the source of truth
for *what moves and what is resident*, not just execution order.
``StepTables.from_schedule`` additionally runs a per-step, per-ring
**channel activity analysis** (``down_send`` / ``up_send``: which
(device, step) hops actually carry a message) and a **liveness-window
analysis** (first-fit interval coloring of every message / turnaround /
skip-stash lifetime).  The executors lower these directly:

- quiescent hops are zero-masked (a dead step's payload — and, via the
  ``where`` transpose, its backward cotangent — is all-zeros), and a ring
  no schedule message ever rides is elided from the scan body entirely;
- receive / turnaround / skip-stash buffers are *rotating* buffers sized
  by the proven windows ``W_down`` / ``W_up`` / ``W_turn`` / ``W_skip``
  (the max simultaneously-live entries per channel) instead of
  microbatch-indexed ``O(M)`` arrays, with store/read slots precomputed
  per step; skip-stash entries no decoder row ever consumes are dead
  stores and are never written;
- boundary activations cross the wire in ``PipelineConfig.wire_dtype``
  (default bf16; compute stays fp32 — cast-on-send, upcast-on-read).
  The transposed scan converts cotangents through the same casts, so
  backward hops ride the wire dtype symmetrically.  ``wire_dtype=
  "float32"`` is the escape hatch the exact differential tests pin
  (see README "Wire format & buffer liveness" for tolerance guidance).

Comm/compute overlap: with ``PipelineConfig.overlap`` (the default) the
executors *double-buffer* the ring hops — step t's payload rides the
``ppermute`` issued at the top of step t+1's scan body, before that
step's compute, instead of at the bottom of step t.  The store tables
prove the target receive slot is dead until the arrival's consumer runs,
so prefetching into it is safe; values, arrival steps and windows are
identical to the synchronous lowering (``overlap=False``, the
differential reference), but the collective and the next step's
independent compute now sit in the same scan iteration with no data
dependency between them, so XLA's latency-hiding scheduler can overlap
them.  The analysis classifies each hop as **exposed** (its consumer
runs on the very next forward step — the dependency forces the
collective onto the critical path; cost ``t_p2p``) or **hidden**
(intervening compute covers it; cost ``max(0, t_p2p - t_f)``) —
``exposed_hops`` / ``hidden_hops`` here, mirrored by the planner's
``core.schedule.comm_stats`` and priced by
``core.comm_model.overlap_accounting`` and the tuner's Eq. 15
generalization, so the planner and the executor are held to the same
split the way ``lowered_comm_volume`` already holds the live-hop bytes.

The closed-form executors remain fp32-wire, O(1)-register differential
references via ``auto_pipeline(..., executor="closed_form")``;
``core.comm_model.lowered_comm_volume`` prices exactly the live hops and
wire bytes lowered here, and the tuner's ``peak_memory`` consumes the
same windows.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.schedule import (Schedule, placement_bounds_error,
                                 slot_maps)
from repro.runtime import scopes
from repro.runtime.pipeline import (WIRE_DTYPES, PipelineConfig,
                                    _wrap_remat, ring_perms, tree_index,
                                    tree_local, zero_all_gather)

Pytree = Any

IDLE, RUN_ENC, RUN_DEC = 0, 1, 2


class PlanError(ValueError):
    """A plan the lowering cannot realize, with structured context.

    Every rejection carries the name of the violated check plus the
    (device, step, slot) coordinates where the lowering noticed it, so
    callers — and the mutation-soundness suite — can dispatch on
    ``err.check`` instead of grepping message strings.  Subclasses
    ``ValueError``: every pre-existing ``except ValueError`` /
    ``pytest.raises(ValueError, match=...)`` site keeps working, and the
    original message text is preserved verbatim inside the formatted
    string.  ``python -m repro.analysis.verify`` replays the same plan
    through the full static dataflow proof for the complete report.
    """

    POINTER = ("see `python -m repro.analysis.verify` for the full "
               "diagnostic report")

    def __init__(self, message: str, *, check: str,
                 device: int | None = None, step: int | None = None,
                 slot: int | None = None):
        self.check = check
        self.device = device
        self.step = step
        self.slot = slot
        where = ", ".join(
            f"{k}={v}" for k, v in (("device", device), ("step", step),
                                    ("slot", slot)) if v is not None)
        super().__init__(
            f"[{check}{'; ' + where if where else ''}] {message} "
            f"({self.POINTER})")


def _color_intervals(ivs) -> tuple[dict[tuple[int, int], int], int]:
    """First-fit interval coloring by start step.

    ``ivs`` is a list of closed ``(start, end)`` step intervals on ONE
    device's channel; a slot is reusable only *strictly after* its last
    read (stores happen before reads within a step, so an entry arriving
    at the step its slot was last read would clobber it).  First-fit on
    start-sorted intervals is optimal for interval graphs, so the slot
    count equals the max number of simultaneously-live entries — the
    liveness window W the property tests cross-check against an
    event-driven replay.
    """
    ends: list[int] = []                 # slot -> last occupied step
    out: dict[tuple[int, int], int] = {}
    for s, e in sorted(ivs):
        for i, last in enumerate(ends):
            if last < s:
                ends[i] = e
                out[(s, e)] = i
                break
        else:
            out[(s, e)] = len(ends)
            ends.append(e)
    return out, len(ends)


# ===========================================================================
# Step-table extraction (host-side, numpy)
# ===========================================================================

@dataclasses.dataclass(frozen=True)
class StepTables:
    """Per-device forward step programs + message routing for one Schedule.

    All arrays are ``[D, num_steps]`` over the *compressed forward step
    axis*: the schedule's global steps that contain at least one forward
    placement, in order (``forward_steps`` maps compressed index -> global
    step).  Compression preserves the relative order of every placement, so
    the synchronous scan (one ppermute hop per step) realizes the same
    partial order the schedule was validated against.

    - ``sel``: ``IDLE`` / ``RUN_ENC`` / ``RUN_DEC`` (linear pipelines only
      use ``IDLE`` / ``RUN_ENC``).
    - ``slot``: which of the device's V same-kind stage slots the task
      runs (0 for classic V=1 plans; interleaved plans index the [V, pad]
      parameter stacks and per-slot count/pairing tables with it).
    - ``mb``: microbatch of the slot (0 when idle — never read).
    - ``down_mb`` / ``down_valid``: arrival on the down-ring channel at the
      *start* of the step (what the upstream device sent last step), with
      the microbatch for introspection; ``up_mb`` / ``up_valid`` the same
      for the up ring.  ``down_slot`` / ``up_slot`` give the rotating
      receive-buffer slot the arrival is stored into, and ``rx_slot`` the
      slot the step's *running* task reads its input from (undefined — 0 —
      on embed / turnaround-read / idle steps, where the buffers are not
      consulted).
    - ``down_send`` / ``up_send``: this device's hop on the ring actually
      carries a message this step (the channel activity analysis); on
      quiescent steps the executors send zeros and the transposed scan
      carries zero cotangents.
    - ``loss``: slot computes the final-stage output and emits the loss.
    - ``embed`` / ``turn_rd`` / ``turn_wr``: the slot runs stage 0 (embeds
      its input), the first decoder-half stage (reads the local turn
      buffer) or the last encoder-half stage (writes it).  With V > 1 a
      device runs several enc/dec slots, so these are per-(device, step)
      facts, not per-device ones — ``embed_device`` / ``turn_device`` stay
      as informational summaries.  ``turn_wr_slot`` / ``turn_rd_slot``
      give the rotating turn-buffer slot written / read.
    - ``skip_wr`` / ``skip_wr_slot``: the encoder slot's skip stash is
      live (some decoder row consumes it — dead stores are elided) and
      where it goes; ``skip_rd_slot[d, t, v]`` is the stash slot holding
      encoder-slot ``v``'s entry for the decoder task's microbatch
      (gathered into the ``[V * enc_pad]`` flat view
      ``StageLayout.skip_rows`` addresses).
    - ``W_down`` / ``W_up`` / ``W_turn`` / ``W_skip``: the proven liveness
      windows — max simultaneously-live entries per channel across
      devices; the executors allocate exactly these many buffer slots.
    """

    D: int
    M: int
    V: int
    rings: int                     # 2 folded (down + up), 1 linear
    forward_steps: tuple[int, ...]
    sel: np.ndarray
    slot: np.ndarray
    mb: np.ndarray
    down_mb: np.ndarray
    down_valid: np.ndarray
    up_mb: np.ndarray
    up_valid: np.ndarray
    loss: np.ndarray
    embed: np.ndarray
    turn_rd: np.ndarray
    turn_wr: np.ndarray
    # ---- channel activity + liveness lowering --------------------------
    down_send: np.ndarray
    up_send: np.ndarray
    down_slot: np.ndarray
    up_slot: np.ndarray
    rx_slot: np.ndarray
    turn_wr_slot: np.ndarray
    turn_rd_slot: np.ndarray
    skip_wr: np.ndarray
    skip_wr_slot: np.ndarray
    skip_rd_slot: np.ndarray
    W_down: int
    W_up: int
    W_turn: int
    W_skip: int
    # hops whose consumer runs on the very next forward step: the arrival's
    # dependency serializes the collective against compute even in the
    # overlapped lowering (the rest are hidden under intervening steps)
    exposed_down: int
    exposed_up: int
    embed_device: int = 0
    turn_device: int = -1

    @property
    def num_steps(self) -> int:
        return self.sel.shape[1]

    @property
    def fetch_row(self) -> np.ndarray:
        """``[D, num_steps]`` row of the ``[2V, pad, ...]`` gradient row
        space (``_SlotRows``) each step fetches: ``slot`` on encoder (and
        linear) steps, ``V + slot`` on decoder steps, 0 on idle steps
        (whose weight cotangent is zero)."""
        return np.where(self.sel == RUN_DEC, self.V + self.slot,
                        self.slot).astype(np.int32)

    @property
    def running_steps(self) -> np.ndarray:
        """``[D]`` steps on which each device runs a stage, each adding
        its weight gradient into one row."""
        return (self.sel != IDLE).sum(axis=1)

    @property
    def live_hops(self) -> tuple[int, int]:
        """(down, up) hops that actually carry a message (fwd pass)."""
        return int(self.down_send.sum()), int(self.up_send.sum())

    @property
    def dense_hops(self) -> int:
        """Hops the pre-liveness lowering paid: every ring, every step."""
        return self.rings * self.D * self.num_steps

    @property
    def exposed_hops(self) -> int:
        """Live hops whose consumer runs one step after the producer —
        the overlapped executor cannot hide these under compute."""
        return self.exposed_down + self.exposed_up

    @property
    def hidden_hops(self) -> int:
        """Live hops with at least one intervening step before their
        consumer: the overlapped lowering prefetches them under compute."""
        down, up = self.live_hops
        return down + up - self.exposed_hops

    @classmethod
    def from_schedule(cls, sched: Schedule, *, folded: bool,
                      device_of_stage=None,
                      devices: tuple[int, ...] | None = None,
                      skip_consumers=None) -> "StepTables":
        """Lower a schedule's forward placements to step tables.

        ``device_of_stage`` is the partition's *explicit* stage->device
        mapping; when omitted the canonical placements (mirror fold /
        identity, or their V-fold interleaved generalization) are assumed.
        Pass the mapping as a ``devices`` *tuple* instead to memoize the
        lowering per (schedule, folded, devices, skip_consumers) — the
        tuner's candidate loop and repeated ``auto_pipeline`` calls then
        reuse the O(S*M*steps) extraction.

        ``skip_consumers[d][dec_slot]`` optionally lists the encoder slots
        whose stash entries device ``d``'s decoder slot actually consumes
        (``StageLayout`` derives this from the graph's skip edges — see
        ``runtime.compile``).  Without it the analysis is conservative:
        every decoder slot may read every encoder slot, so stash entries
        stay live until the device's last decoder task of the microbatch.
        With it, unconsumed entries become dead stores (never written) and
        the skip window shrinks on sparse graphs.  Must be nested tuples
        when combined with ``devices`` (the memoization key).

        Raises ``ValueError`` on any shape the synchronous scan cannot
        realize (malformed placements, a stage mapped off the ring
        neighbourhood its messages need, double-booked channels, a
        consumer scheduled before its input can arrive) — the
        planner/executor mismatches the closed forms used to hide surface
        here.
        """
        if devices is not None:
            if device_of_stage is not None:
                raise ValueError("pass device_of_stage or devices, not both")
            return _tables_cached(sched, folded, tuple(devices),
                                  skip_consumers)
        return cls._build(sched, folded, device_of_stage, skip_consumers)

    @classmethod
    def _build(cls, sched: Schedule, folded: bool,
               device_of_stage, skip_consumers=None) -> "StepTables":
        S, M, D = sched.S, sched.M, sched.D
        if (S % (2 * D) if folded else S % D) != 0:
            raise PlanError(
                f"schedule has S={S} stages but a "
                f"{'folded' if folded else 'linear'} executor over D={D} "
                f"devices lowers S = {'2*V*D' if folded else 'V*D'} "
                "(an integer number of stage slots per device)",
                check="program-shape")
        half = S // 2 if folded else S
        if device_of_stage is None:
            if folded:
                device_of_stage = (
                    lambda s: (s % D) if s < half else (S - 1 - s) % D)
            else:
                device_of_stage = lambda s: s % D
        V, enc_slot, dec_slot = slot_maps(S, D, folded, device_of_stage)
        if skip_consumers is not None:
            if len(skip_consumers) != D or any(
                    len(dev) != V for dev in skip_consumers):
                raise PlanError(
                    f"skip_consumers must list every (device, dec slot): "
                    f"expected [{D}][{V}], got "
                    f"{[len(dev) for dev in skip_consumers]}",
                    check="program-shape")
        fwd = sorted((p for p in sched.placements if p.virtual < S),
                     key=lambda p: (p.step, p.device))
        steps = sorted({p.step for p in fwd})
        k_of_step = {t: k for k, t in enumerate(steps)}
        T = len(steps)

        sel = np.zeros((D, T), dtype=np.int32)
        slot = np.zeros((D, T), dtype=np.int32)
        mb = np.zeros((D, T), dtype=np.int32)
        down_mb = np.zeros((D, T), dtype=np.int32)
        down_valid = np.zeros((D, T), dtype=bool)
        up_mb = np.zeros((D, T), dtype=np.int32)
        up_valid = np.zeros((D, T), dtype=bool)
        loss = np.zeros((D, T), dtype=bool)
        embed = np.zeros((D, T), dtype=bool)
        turn_rd = np.zeros((D, T), dtype=bool)
        turn_wr = np.zeros((D, T), dtype=bool)

        def mark_rx(tab, ok, dev, k, m, chan):
            if k >= T:
                raise PlanError(
                    f"message for m={m} sent on the last forward step has "
                    "no consumer step — run validate_schedule",
                    check="no-lost-message", device=dev)
            if ok[dev, k]:
                raise PlanError(
                    f"two messages on the {chan} channel of device {dev} "
                    f"at forward step {k} — run validate_schedule",
                    check="send-recv-pairing", device=dev, step=k)
            tab[dev, k] = m
            ok[dev, k] = True

        # message / buffer-lifetime event logs for the liveness analysis
        msgs_down: list[tuple[int, int, int, int, int]] = []
        msgs_up: list[tuple[int, int, int, int, int]] = []
        turn_writes: dict[tuple[int, int], int] = {}   # (dev, m) -> step
        turn_reads: dict[tuple[int, int], int] = {}
        enc_runs: list[tuple[int, int, int, int]] = []  # (dev, k, m, vslot)
        dec_runs: list[tuple[int, int, int, int]] = []

        k_of_task: dict[tuple[int, int], int] = {}
        for p in fwd:
            v, m, dev = p.virtual, p.microbatch, p.device
            err = placement_bounds_error(p, S, M, D)
            if err is not None:
                raise PlanError(
                    f"placement v={v} m={m}: {err}; run validate_schedule",
                    check="placement-bounds")
            # The stage layout pins each stage to the partition's device
            # mapping; routing below assumes it.  A schedule with a
            # permuted device mapping (e.g. an ILP free-mapping solve) is
            # *valid* but not realizable on this layout — reject it here
            # rather than run the wrong stage's parameters silently.
            canon = device_of_stage(v)
            if dev != canon:
                raise PlanError(
                    f"placement v={v} m={m} on device {dev}, but this "
                    f"executor's stage layout pins stage {v} to device "
                    f"{canon} (slot "
                    f"{enc_slot.get(v, dec_slot.get(v))}); re-synthesize "
                    "the schedule with the partition's device_of_stage",
                    check="stage-routing", device=dev)
            k = k_of_step[p.step]
            if sel[dev, k] != IDLE:
                raise PlanError(
                    f"device {dev} double-booked at step {p.step} — run "
                    "validate_schedule",
                    check="program-shape", device=dev, step=k)
            k_of_task[(v, m)] = k
            mb[dev, k] = m
            is_enc = v < half
            sel[dev, k] = RUN_ENC if is_enc else RUN_DEC
            slot[dev, k] = enc_slot[v] if is_enc else dec_slot[v]
            (enc_runs if is_enc else dec_runs).append(
                (dev, k, m, int(slot[dev, k])))
            if v == 0:
                embed[dev, k] = True
            if folded and v == half:
                turn_rd[dev, k] = True
                turn_reads[(dev, m)] = k
            if folded and v == half - 1:
                # turnaround — consumed locally from the turn buffer by
                # stage S/2, which must share the device; no send.
                turn_wr[dev, k] = True
                turn_writes[(dev, m)] = k
                if device_of_stage(half) != dev:
                    raise PlanError(
                        f"turnaround stages {half - 1},{half} on devices "
                        f"{dev},{device_of_stage(half)}: the fold "
                        "collocates them (constraint (9))",
                        check="stage-routing", device=dev)
            elif v < S - 1:
                # enc -> enc rides the down ring, dec -> dec the up ring
                # (both wrap: interleaved slot boundaries cross D-1 -> 0);
                # the consumer must be the matching ring neighbour.
                nd = device_of_stage(v + 1)
                want = (dev + 1) % D if is_enc else (dev - 1) % D
                if nd != want:
                    raise PlanError(
                        f"stage {v} on device {dev} (slot "
                        f"{slot[dev, k]}) feeds stage {v + 1} on device "
                        f"{nd}, but the ring executors only deliver to "
                        f"device {want}",
                        check="stage-routing", device=dev, step=k,
                        slot=int(slot[dev, k]))
                if is_enc:
                    mark_rx(down_mb, down_valid, nd, k + 1, m, "down")
                    msgs_down.append((dev, nd, k, v, m))
                else:
                    mark_rx(up_mb, up_valid, nd, k + 1, m, "up")
                    msgs_up.append((dev, nd, k, v, m))
            if v == S - 1:
                loss[dev, k] = True

        # Dataflow feasibility: each forward task's input must have been
        # produced at an earlier compressed step (so it arrived — one
        # ppermute hop — at or before the consumer's step).
        for p in fwd:
            if p.virtual == 0:
                continue
            dep = (p.virtual - 1, p.microbatch)
            if dep not in k_of_task:
                raise PlanError(
                    f"task v={p.virtual} m={p.microbatch} has no scheduled "
                    "predecessor — run validate_schedule",
                    check="matched-store-read", device=p.device)
            if k_of_task[(p.virtual, p.microbatch)] < k_of_task[dep] + 1:
                raise PlanError(
                    f"task v={p.virtual} m={p.microbatch} runs before its "
                    "input can arrive (constraint (10)) — run "
                    "validate_schedule",
                    check="matched-store-read", device=p.device,
                    step=k_of_task[(p.virtual, p.microbatch)])

        # ---- channel activity + liveness windows -----------------------
        down_send = np.zeros((D, T), dtype=bool)
        up_send = np.zeros((D, T), dtype=bool)
        down_slot = np.zeros((D, T), dtype=np.int32)
        up_slot = np.zeros((D, T), dtype=np.int32)
        rx_slot = np.zeros((D, T), dtype=np.int32)
        windows = {}
        exposed = {}
        for name, msgs, send_tab, slot_tab in (
                ("down", msgs_down, down_send, down_slot),
                ("up", msgs_up, up_send, up_slot)):
            by_dev: dict[int, list[tuple[int, int]]] = {}
            n_exposed = 0
            for src, dst, k_prod, v, m in msgs:
                send_tab[src, k_prod] = True
                # in flight in the receiver's buffer from arrival (start
                # of k_prod + 1) until its consumer runs
                k_cons = k_of_task[(v + 1, m)]
                by_dev.setdefault(dst, []).append((k_prod + 1, k_cons))
                if k_cons == k_prod + 1:
                    n_exposed += 1
            exposed[name] = n_exposed
            W = 0
            for dst, ivs in by_dev.items():
                assign, w = _color_intervals(ivs)
                W = max(W, w)
                for (k_arr, k_cons), sl in assign.items():
                    slot_tab[dst, k_arr] = sl
                    rx_slot[dst, k_cons] = sl
            windows[name] = W

        turn_wr_slot = np.zeros((D, T), dtype=np.int32)
        turn_rd_slot = np.zeros((D, T), dtype=np.int32)
        by_dev = {}
        for (dev, m), kw in turn_writes.items():
            kr = turn_reads.get((dev, m))
            if kr is None:
                turn_wr[dev, kw] = False    # dead store: no reader
                continue
            by_dev.setdefault(dev, []).append((kw, kr))
        W_turn = 0
        for dev, ivs in by_dev.items():
            assign, w = _color_intervals(ivs)
            W_turn = max(W_turn, w)
            for (kw, kr), sl in assign.items():
                turn_wr_slot[dev, kw] = sl
                turn_rd_slot[dev, kr] = sl

        # Skip stash: entry (device, microbatch, enc slot) is written when
        # the encoder slot runs and stays live until the last decoder task
        # whose slot consumes it.  Without skip_consumers every decoder
        # slot is assumed to read every encoder slot (conservative).
        skip_wr = np.zeros((D, T), dtype=bool)
        skip_wr_slot = np.zeros((D, T), dtype=np.int32)
        skip_rd_slot = np.zeros((D, T, V), dtype=np.int32)
        last_read: dict[tuple[int, int, int], int] = {}
        for dev, k2, m, dv in dec_runs:
            evs = (range(V) if skip_consumers is None
                   else skip_consumers[dev][dv])
            for ev in evs:
                if not 0 <= ev < V:
                    raise PlanError(
                        f"skip_consumers names enc slot {ev} on device "
                        f"{dev}, but the layout has V={V} slots",
                        check="program-shape", device=dev, slot=ev)
                key = (dev, m, ev)
                if last_read.get(key, -1) < k2:
                    last_read[key] = k2
        per_dev: dict[int, list[tuple[int, int]]] = {}
        entry_of: dict[tuple[int, int, int], tuple[int, int]] = {}
        for dev, k, m, vslot in enc_runs:
            if not folded:
                continue
            end = last_read.get((dev, m, vslot))
            if end is None:
                continue                    # dead store: never consumed
            skip_wr[dev, k] = True
            per_dev.setdefault(dev, []).append((k, end))
            entry_of[(dev, m, vslot)] = (k, end)
        W_skip = 0
        entry_slot: dict[tuple[int, int, int], int] = {}
        for dev, ivs in per_dev.items():
            assign, w = _color_intervals(ivs)
            W_skip = max(W_skip, w)
            for key, iv in entry_of.items():
                if key[0] == dev:
                    entry_slot[key] = assign[iv]
        for dev, k2, m, dv in dec_runs:
            for ev in range(V):
                skip_rd_slot[dev, k2, ev] = entry_slot.get((dev, m, ev), 0)
        for dev, k, m, vslot in enc_runs:
            if skip_wr[dev, k]:
                skip_wr_slot[dev, k] = entry_slot[(dev, m, vslot)]

        return cls(D=D, M=M, V=V, rings=2 if folded else 1,
                   forward_steps=tuple(steps), sel=sel,
                   slot=slot, mb=mb,
                   down_mb=down_mb, down_valid=down_valid, up_mb=up_mb,
                   up_valid=up_valid, loss=loss, embed=embed,
                   turn_rd=turn_rd, turn_wr=turn_wr,
                   down_send=down_send, up_send=up_send,
                   down_slot=down_slot, up_slot=up_slot, rx_slot=rx_slot,
                   turn_wr_slot=turn_wr_slot, turn_rd_slot=turn_rd_slot,
                   skip_wr=skip_wr, skip_wr_slot=skip_wr_slot,
                   skip_rd_slot=skip_rd_slot,
                   W_down=windows["down"], W_up=windows["up"],
                   W_turn=W_turn, W_skip=W_skip,
                   exposed_down=exposed["down"], exposed_up=exposed["up"],
                   embed_device=device_of_stage(0),
                   turn_device=device_of_stage(half - 1) if folded else -1)


@functools.lru_cache(maxsize=256)
def _tables_cached(sched: Schedule, folded: bool,
                   devices: tuple[int, ...],
                   skip_consumers) -> StepTables:
    return StepTables._build(sched, folded, lambda s: devices[s],
                             skip_consumers)


# ===========================================================================
# Rotating scan buffers (slot-indexed; sized by the liveness windows)
# ===========================================================================

def _zeros_buffer(proto: Pytree, W: int, dtype=None) -> Pytree:
    """``[W, ...]`` zero buffer per leaf (proto may be concrete or structs)."""
    return jax.tree.map(
        lambda t: jnp.zeros((W,) + tuple(t.shape), dtype or t.dtype), proto)


def _buf_store(buf: Pytree, i, val: Pytree, pred) -> Pytree:
    """``buf[i] = val`` where ``pred`` (scalar bool), identity otherwise."""
    return jax.tree.map(
        lambda b, v: jnp.where(
            pred, jax.lax.dynamic_update_index_in_dim(
                b, v.astype(b.dtype), i, 0), b),
        buf, val)


def _gather_rows(buf: Pytree, rows) -> Pytree:
    """``buf[rows]`` flattened over the gathered axis: ``[W, pad, ...]``
    leaves gathered with a ``[V]`` slot vector -> ``[V * pad, ...]`` (the
    flat stash view ``StageLayout.skip_rows`` addresses)."""
    return jax.tree.map(
        lambda b: jnp.take(b, rows, axis=0).reshape(
            (rows.shape[0] * b.shape[1],) + b.shape[2:]), buf)


def _wire_dtype(cfg: PipelineConfig):
    if cfg.wire_dtype not in WIRE_DTYPES:
        raise PlanError(
            f"unknown wire_dtype {cfg.wire_dtype!r}; expected one of "
            f"{WIRE_DTYPES} (float32 is the exact-differential escape "
            "hatch)",
            check="wire-dtype-flow")
    return jnp.dtype(cfg.wire_dtype)


# ===========================================================================
# Per-step weight gradients, one row a step
# ===========================================================================
#
# A scan step reads its stage's weights from the slot stacks inside the
# stage switch, but takes their gradient through zero-valued row spaces
# (``_SlotRows.sinks``) whose rows are operands of the switch.  The
# transposed switch then returns one slot-sized row cotangent a step,
# which XLA adds in place into the scan's gradient accumulator.  Had the
# stacks themselves carried the gradient, they would be operands of the
# switch: every branch would return a stack-sized cotangent for each
# (zeros where it did not run), and the transposed scan would add them
# all every step.  Leaves both kinds have share one ``[2V, pad, ...]``
# row space, so a step adds into one row of it whichever kind it runs.


@jax.custom_vjp
def _tap(w, z):
    """``w``, whose cotangent goes to ``z`` (a zero row of ``w``'s shape)
    and not to ``w``."""
    return w


_tap.defvjp(lambda w, z: (w, None), lambda _, g: (None, g))


def _index(leaves: list, i) -> list:
    return [jax.lax.dynamic_index_in_dim(t, i, 0, keepdims=False)
            for t in leaves]


@dataclasses.dataclass(frozen=True)
class _SlotRows:
    """The gradient row spaces of the encoder and decoder slot stacks.

    A leaf both kinds have at the same path, shape, dtype and ZeRO-2
    gather dim has one ``[2V, pad, ...]`` row space: encoder slots at rows
    ``0..V-1``, decoder slots at ``V..2V-1``, addressed by the step's
    ``StepTables.fetch_row``.  Any other leaf (a decoder-only skip
    projection, or stacks whose pads differ) has a ``[V, pad, ...]`` row
    space of its own kind, addressed by the slot.
    """

    enc_def: Any
    dec_def: Any
    shared: tuple[tuple[int, int], ...]   # (enc leaf, dec leaf) pairs
    enc_own: tuple[int, ...]
    dec_own: tuple[int, ...]

    @classmethod
    def of(cls, enc_p: Pytree, dec_p: Pytree, dims=None) -> "_SlotRows":
        enc_kv, enc_def = jax.tree_util.tree_flatten_with_path(enc_p)
        dec_kv, dec_def = jax.tree_util.tree_flatten_with_path(dec_p)
        enc_dims, dec_dims = ((jax.tree.leaves(dims[0]),
                               jax.tree.leaves(dims[1]))
                              if dims is not None else
                              ([-1] * len(enc_kv), [-1] * len(dec_kv)))
        dec_at = {path: j for j, (path, _) in enumerate(dec_kv)}

        def key(leaf, dim):
            return tuple(leaf.shape), jnp.dtype(leaf.dtype), dim

        shared = []
        for i, (path, leaf) in enumerate(enc_kv):
            j = dec_at.get(path)
            if j is not None and (key(leaf, enc_dims[i])
                                  == key(dec_kv[j][1], dec_dims[j])):
                shared.append((i, j))
        enc_sh = {i for i, _ in shared}
        dec_sh = {j for _, j in shared}
        return cls(enc_def, dec_def, tuple(shared),
                   tuple(i for i in range(len(enc_kv)) if i not in enc_sh),
                   tuple(j for j in range(len(dec_kv)) if j not in dec_sh))

    def sinks(self, enc_p: Pytree, dec_p: Pytree) -> tuple:
        """Zero row spaces: (shared ``[2V, ...]``, encoder-own,
        decoder-own) leaf lists."""
        enc, dec = jax.tree.leaves(enc_p), jax.tree.leaves(dec_p)
        zeros = lambda t, n: jnp.zeros((n * t.shape[0],) + t.shape[1:],
                                       t.dtype)
        return ([zeros(enc[i], 2) for i, _ in self.shared],
                [zeros(enc[i], 1) for i in self.enc_own],
                [zeros(dec[j], 1) for j in self.dec_own])

    def rows(self, sinks: tuple, row, slot) -> tuple:
        """A step's zero rows: the shared row spaces at ``row``, each
        kind's own at ``slot``."""
        shared, enc_own, dec_own = sinks
        return _index(shared, row), _index(enc_own, slot), _index(dec_own,
                                                                  slot)

    def enc(self, enc_p: Pytree, z: tuple, slot) -> Pytree:
        """The encoder slot's weights, their gradient into the rows ``z``
        of :meth:`rows`."""
        return self._slot(enc_p, z[0], z[1], [i for i, _ in self.shared],
                          self.enc_own, slot)

    def dec(self, dec_p: Pytree, z: tuple, slot) -> Pytree:
        return self._slot(dec_p, z[0], z[2], [j for _, j in self.shared],
                          self.dec_own, slot)

    @staticmethod
    def _slot(stack, shared, own, shared_at, own_at, slot) -> Pytree:
        leaves, treedef = jax.tree.flatten(stack)
        z = [None] * len(leaves)
        for k, i in enumerate(shared_at):
            z[i] = shared[k]
        for k, i in enumerate(own_at):
            z[i] = own[k]
        return jax.tree.unflatten(treedef, [
            _tap(w, zi) for w, zi in zip(_index(leaves, slot), z)])

    def grads(self, g: tuple) -> tuple:
        """The stacks' gradients from the row spaces' gradients ``g``."""
        shared, enc_own, dec_own = g
        enc = [None] * (len(self.shared) + len(self.enc_own))
        dec = [None] * (len(self.shared) + len(self.dec_own))
        for (i, j), gs in zip(self.shared, shared):
            V = gs.shape[0] // 2
            enc[i], dec[j] = gs[:V], gs[V:]
        for i, gi in zip(self.enc_own, enc_own):
            enc[i] = gi
        for j, gj in zip(self.dec_own, dec_own):
            dec[j] = gj
        return (jax.tree.unflatten(self.enc_def, enc),
                jax.tree.unflatten(self.dec_def, dec))


def _grads_through_rows(run: Callable, rows: _SlotRows, enc_p, dec_p,
                        *args):
    """``run(enc_p, dec_p, sinks, *args)``, differentiated with respect to
    the stacks through the row spaces ``sinks`` (the stacks are read as
    constants); ``args`` are differentiated as usual."""
    @jax.custom_vjp
    def f(enc_p, dec_p, args):
        return run(enc_p, dec_p, rows.sinks(enc_p, dec_p), *args)

    def f_fwd(enc_p, dec_p, args):
        out, vjp = jax.vjp(lambda z, a: run(enc_p, dec_p, z, *a),
                           rows.sinks(enc_p, dec_p), args)
        return out, vjp

    def f_bwd(vjp, g):
        g_sinks, g_args = vjp(g)
        return (*rows.grads(g_sinks), g_args)

    f.defvjp(f_fwd, f_bwd)
    return f(enc_p, dec_p, args)


# ===========================================================================
# Folded wave executor from tables
# ===========================================================================

def make_wave_pipeline_from_schedule(
    cfg: PipelineConfig,
    sched: Schedule,
    *,
    embed_fn: Callable,       # (edge_p, mb, aux) -> tokens
    enc_stage_fn: Callable,   # (stage_p, x, aux, slot) -> (x_out, skips)
    dec_stage_fn: Callable,   # (stage_p, x, skips, aux, slot) -> x_out
    loss_fn: Callable,        # (edge_p, x_final, mb, aux) -> scalar
    device_of_stage=None,     # partition's explicit stage->device mapping
    devices=None,             # ...same, as a tuple (memoized lowering)
    skip_consumers=None,      # layout-derived (device, dec slot) -> enc slots
    zero_dims=None,           # (enc_dims, dec_dims): ZeRO-2 slot-view
    #   gather dims per stack leaf (runtime.sharding.zero_stack_specs);
    #   None = unsharded stacks
) -> Callable:
    """Lower a folded S=2VD schedule to ``fn(enc_stack, dec_stack, edge_p,
    mbs, aux) -> loss`` (same call signature as ``make_wave_pipeline``, but
    the stage stacks carry a leading slot axis: ``[D, V, pad, ...]``).

    With ``zero_dims`` the stacks arrive ZeRO-2 rest-sharded over
    ``cfg.data_axes`` (their shard_map in_specs carry the matching
    ``P("data", ...)``-suffixed entries): each stage invocation
    all-gathers its slot's leaves on use *inside* the remat region, so
    backward re-gathers instead of retaining the full params and the
    gather's transpose reduce-scatters the gradient over the data axis.

    Each scan step consults the schedule-derived tables: arrivals are
    stored into rotating receive buffers sized by the proven windows, the
    selected stage slot runs on the slot's microbatch with its own
    parameter rows (``stack[d, slot]``; their gradient comes back one row
    a step, see ``_SlotRows``), encoder slots stash their skips
    under the precomputed stash slot — and the turnaround slot the
    activation under its turn slot — so each decoder slot reads exactly
    the skips its collocated encoder slot produced.  Boundary activations
    cross the rings in ``cfg.wire_dtype`` (zero-masked on quiescent
    steps); compute stays in the model dtype.  Correct for any valid
    schedule, including ``M < D`` and interleaved V > 1 plans; the rings
    wrap (interleaved slot boundaries cross device D-1 -> 0).

    ``enc_stage_fn`` / ``dec_stage_fn`` receive the *slot index* as their
    last argument so callers can select per-slot block counts and skip
    pairings (see ``runtime.compile``).
    """
    D, M, axis = cfg.num_devices, cfg.num_microbatches, cfg.axis
    if sched.M != M or sched.D != D:
        raise PlanError(
            f"schedule (M={sched.M}, D={sched.D}) does not match the "
            f"pipeline config (M={M}, D={D})",
            check="program-shape")
    tables = StepTables.from_schedule(sched, folded=True,
                                      device_of_stage=device_of_stage,
                                      devices=devices,
                                      skip_consumers=skip_consumers)
    T, V = tables.num_steps, tables.V
    wire = _wire_dtype(cfg)
    down_perm, up_perm = ring_perms(D, wrap=True)
    # a ring no message ever rides is elided from the scan body entirely
    down_used = bool(tables.down_send.any())
    up_used = bool(tables.up_send.any())
    W_down = max(tables.W_down, 1)
    W_up = max(tables.W_up, 1)
    W_turn = max(tables.W_turn, 1)
    W_skip = max(tables.W_skip, 1)
    if zero_dims is not None:
        enc_dims, dec_dims = zero_dims
        enc_inner, dec_inner = enc_stage_fn, dec_stage_fn

        def enc_stage_fn(stage_p, x, aux_m, slot):  # noqa: F811
            stage_p = zero_all_gather(stage_p, enc_dims, cfg.data_axes)
            return enc_inner(stage_p, x, aux_m, slot)

        def dec_stage_fn(stage_p, x, skips, aux_m, slot):  # noqa: F811
            stage_p = zero_all_gather(stage_p, dec_dims, cfg.data_axes)
            return dec_inner(stage_p, x, skips, aux_m, slot)

    @jax.named_scope(scopes.EXECUTOR)
    def fn(enc_stack, dec_stack, edge_p, mbs, aux):
        enc_p = tree_local(enc_stack)       # [V, enc_pad, ...]
        dec_p = tree_local(dec_stack)       # [V, dec_pad, ...]
        rows = _SlotRows.of(enc_p, dec_p, zero_dims)
        total = _grads_through_rows(functools.partial(run, rows), rows,
                                    enc_p, dec_p, edge_p, mbs, aux)
        with jax.named_scope(scopes.LOSS_ALLREDUCE):
            total = jax.lax.psum(total, (axis, *cfg.data_axes))
        return total / cfg.dp_size

    def run(rows, enc_p, dec_p, sinks, edge_p, mbs, aux):
        d = jax.lax.axis_index(axis)

        mb0 = tree_index(mbs, 0)
        aux0 = tree_index(aux, 0)
        x_proto = jax.eval_shape(embed_fn, edge_p, mb0, aux0)
        zero_x = jnp.zeros(x_proto.shape, x_proto.dtype)
        zero_w = jnp.zeros(x_proto.shape, wire)
        skips_proto = jax.eval_shape(
            lambda p, x, a: enc_stage_fn(p, x, a, 0)[1],
            tree_index(enc_p, 0), zero_x, aux0)
        zero_skips = jax.tree.map(
            lambda t: jnp.zeros(t.shape, t.dtype), skips_proto)

        # This device's rows of every table (host constants -> jnp).
        sel_t = jnp.asarray(tables.sel)[d]
        slot_t = jnp.asarray(tables.slot)[d]
        row_t = jnp.asarray(tables.fetch_row)[d]
        mb_t = jnp.asarray(tables.mb)[d]
        dok_t = jnp.asarray(tables.down_valid)[d]
        uok_t = jnp.asarray(tables.up_valid)[d]
        dsl_t = jnp.asarray(tables.down_slot)[d]
        usl_t = jnp.asarray(tables.up_slot)[d]
        rx_t = jnp.asarray(tables.rx_slot)[d]
        dsnd_t = jnp.asarray(tables.down_send)[d]
        usnd_t = jnp.asarray(tables.up_send)[d]
        loss_t = jnp.asarray(tables.loss)[d]
        emb_t = jnp.asarray(tables.embed)[d]
        trd_t = jnp.asarray(tables.turn_rd)[d]
        twr_t = jnp.asarray(tables.turn_wr)[d]
        twrs_t = jnp.asarray(tables.turn_wr_slot)[d]
        trds_t = jnp.asarray(tables.turn_rd_slot)[d]
        swr_t = jnp.asarray(tables.skip_wr)[d]
        swrs_t = jnp.asarray(tables.skip_wr_slot)[d]
        srd_t = jnp.asarray(tables.skip_rd_slot)[d]     # [T, V]

        init = (
            zero_w,                              # down-ring register (wire)
            zero_w,                              # up-ring register (wire)
            _zeros_buffer(zero_x, W_down, wire),  # enc_rx[W_down]: arrivals
            _zeros_buffer(zero_x, W_up, wire),    # dec_rx[W_up]: arrivals
            _zeros_buffer(zero_x, W_turn),        # turn[W_turn]
            _zeros_buffer(zero_skips, W_skip),    # cache[W_skip]: skips
        )

        @jax.named_scope(scopes.HOP)
        def hop(down_pl, up_pl):
            down = (jax.lax.ppermute(down_pl, axis, down_perm)
                    if down_used else down_pl)
            up = (jax.lax.ppermute(up_pl, axis, up_perm)
                  if up_used else up_pl)
            return down, up

        def compute(enc_p, dec_p, sinks, edge_p, sel, row, vslot, emb,
                    x_rx_enc, x_in_dec, skips_m, mb_m, aux_m):
            def run_idle(_):
                return zero_x, zero_skips

            @jax.named_scope(scopes.EMBED)
            def embed():
                return embed_fn(edge_p, mb_m, aux_m)

            @jax.named_scope(scopes.STAGE_ENC)
            def run_enc(z):
                x0 = jax.lax.cond(emb, embed, lambda: zero_x)
                x_in = jnp.where(emb, x0, x_rx_enc)
                return enc_stage_fn(rows.enc(enc_p, z, vslot), x_in, aux_m,
                                    vslot)

            @jax.named_scope(scopes.STAGE_DEC)
            def run_dec(z):
                x_out = dec_stage_fn(rows.dec(dec_p, z, vslot), x_in_dec,
                                     skips_m, aux_m, vslot)
                return x_out, zero_skips

            # the step's gradient rows are the switch's operands (see
            # _SlotRows): its transpose returns one row, not the stacks
            return jax.lax.switch(sel, (run_idle, run_enc, run_dec),
                                  rows.rows(sinks, row, vslot))

        # One remat region per step, around the stage switch: its saved
        # inputs are the loop-invariant stacks (forwarded, not stacked
        # over the T scan steps) and this step's activations.  A region
        # inside the switch would save the stacks as per-step switch
        # outputs, T copies of every parameter.
        compute = _wrap_remat(compute, cfg)

        @jax.named_scope(scopes.HEAD)
        def head(x_out, mb_m, aux_m):
            return loss_fn(edge_p, x_out, mb_m, aux_m)

        # The scopes below keep the order in which the body traces its
        # operations, so the named program is the unnamed one.
        def body(down_in, up_in, enc_rx, dec_rx, turn, cache, t):
            with jax.named_scope(scopes.RX_STORE):
                enc_rx = _buf_store(enc_rx, dsl_t[t], down_in, dok_t[t])
                dec_rx = _buf_store(dec_rx, usl_t[t], up_in, uok_t[t])
            m = mb_t[t]
            mb_m = tree_index(mbs, m)
            aux_m = tree_index(aux, m)
            with jax.named_scope(scopes.RX_STORE):
                x_rx_enc = tree_index(enc_rx, rx_t[t]).astype(zero_x.dtype)
            turn_rd = trd_t[t]
            with jax.named_scope(scopes.STASH):
                x_turn = tree_index(turn, trds_t[t])
            with jax.named_scope(scopes.RX_STORE):
                x_rx_dec = tree_index(dec_rx, rx_t[t]).astype(zero_x.dtype)
            x_in_dec = jnp.where(turn_rd, x_turn, x_rx_dec)
            with jax.named_scope(scopes.STASH):
                # gather the stash slots holding this microbatch's V
                # encoder-slot entries -> the flat [V * enc_pad] view
                # consumers address via StageLayout.skip_rows
                skips_m = _gather_rows(cache, srd_t[t])
            x_out, skips = compute(enc_p, dec_p, sinks, edge_p, sel_t[t],
                                   row_t[t], slot_t[t], emb_t[t], x_rx_enc,
                                   x_in_dec, skips_m, mb_m, aux_m)
            # gated stores: only the turnaround slot's output is read back
            # from the turn buffer, and only stash entries some decoder
            # row consumes are written (dead stores are elided — the
            # liveness analysis cleared their flags)
            with jax.named_scope(scopes.STASH):
                turn = _buf_store(turn, twrs_t[t], x_out, twr_t[t])
                cache = _buf_store(cache, swrs_t[t], skips, swr_t[t])
            loss = jax.lax.cond(
                loss_t[t],
                lambda: head(x_out, mb_m, aux_m),
                lambda: jnp.zeros((), jnp.float32))
            # cast-on-send; quiescent hops carry zeros (the where
            # transpose zeroes their backward cotangents too)
            payload = x_out.astype(wire)
            down_pl = jnp.where(dsnd_t[t], payload, zero_w)
            up_pl = jnp.where(usnd_t[t], payload, zero_w)
            return down_pl, up_pl, enc_rx, dec_rx, turn, cache, loss

        if cfg.overlap:
            # Double-buffered hops: the carry holds step t-1's *unsent*
            # payload and its ppermute is issued at the top of body t,
            # before this step's compute.  The arrival still lands at the
            # same step as the synchronous lowering (values identical),
            # but the collective no longer depends on — nor is depended
            # on by — this step's compute unless the arrival's consumer
            # runs right now (an *exposed* hop), so XLA's latency-hiding
            # scheduler can run hop and compute concurrently.
            def step(carry, t):
                pend_down, pend_up, enc_rx, dec_rx, turn, cache = carry
                down_in, up_in = hop(pend_down, pend_up)
                down_pl, up_pl, enc_rx, dec_rx, turn, cache, loss = body(
                    down_in, up_in, enc_rx, dec_rx, turn, cache, t)
                return (down_pl, up_pl, enc_rx, dec_rx, turn, cache), loss
        else:
            # Synchronous reference: hop at the bottom of the producing
            # step; the carry holds the arrival.
            def step(carry, t):
                down_in, up_in, enc_rx, dec_rx, turn, cache = carry
                down_pl, up_pl, enc_rx, dec_rx, turn, cache, loss = body(
                    down_in, up_in, enc_rx, dec_rx, turn, cache, t)
                down_nx, up_nx = hop(down_pl, up_pl)
                return (down_nx, up_nx, enc_rx, dec_rx, turn, cache), loss

        _, losses = jax.lax.scan(step, init, jnp.arange(T))
        return jnp.sum(losses) / M

    return fn


# ===========================================================================
# Linear executor from tables
# ===========================================================================

def make_linear_pipeline_from_schedule(
    cfg: PipelineConfig,
    sched: Schedule,
    *,
    embed_fn: Callable,       # (edge_p, mb) -> x
    stage_fn: Callable,       # (stage_p, x, slot) -> x
    loss_fn: Callable,        # (edge_p, x_final, mb) -> scalar
    device_of_stage=None,     # partition's explicit stage->device mapping
    devices=None,             # ...same, as a tuple (memoized lowering)
    zero_dims=None,           # ZeRO-2 slot-view gather dims per stack leaf
) -> Callable:
    """Lower a linear S=VD schedule to ``fn(stack, edge_p, mbs) -> loss``
    (same call signature as ``make_linear_pipeline``; the stack carries a
    leading slot axis ``[D, V, pad, ...]`` and ``stage_fn`` receives the
    slot index).  The down ring wraps so interleaved (V > 1) plans cross
    the D-1 -> 0 slot boundary; arrivals land in a rotating ``W_down``
    receive buffer in ``cfg.wire_dtype`` and quiescent hops carry
    zeros.  ``zero_dims`` rest-shards the stack exactly as in
    :func:`make_wave_pipeline_from_schedule` (all-gather-on-use inside
    the remat region; grads reduce-scatter through the transpose)."""
    D, M, axis = cfg.num_devices, cfg.num_microbatches, cfg.axis
    if sched.M != M or sched.D != D:
        raise PlanError(
            f"schedule (M={sched.M}, D={sched.D}) does not match the "
            f"pipeline config (M={M}, D={D})",
            check="program-shape")
    tables = StepTables.from_schedule(sched, folded=False,
                                      device_of_stage=device_of_stage,
                                      devices=devices)
    T = tables.num_steps
    wire = _wire_dtype(cfg)
    down_perm, _ = ring_perms(D, wrap=True)
    down_used = bool(tables.down_send.any())
    W_down = max(tables.W_down, 1)
    if zero_dims is not None:
        stage_inner = stage_fn

        def stage_fn(stage_p, x, slot):  # noqa: F811
            stage_p = zero_all_gather(stage_p, zero_dims, cfg.data_axes)
            return stage_inner(stage_p, x, slot)

    @jax.named_scope(scopes.EXECUTOR)
    def fn(stack, edge_p, mbs):
        my_p = tree_local(stack)            # [V, pad, ...]
        # one kind of stage: every leaf's row space is its own [V] stack
        rows = _SlotRows.of(my_p, {}, None if zero_dims is None
                            else (zero_dims, {}))
        total = _grads_through_rows(functools.partial(run, rows), rows,
                                    my_p, {}, edge_p, mbs)
        with jax.named_scope(scopes.LOSS_ALLREDUCE):
            total = jax.lax.psum(total, (axis, *cfg.data_axes))
        return total / cfg.dp_size

    def run(rows, my_p, _, sinks, edge_p, mbs):
        d = jax.lax.axis_index(axis)
        mb0 = tree_index(mbs, 0)
        x_proto = jax.eval_shape(embed_fn, edge_p, mb0)
        zero_x = jnp.zeros(x_proto.shape, x_proto.dtype)
        zero_w = jnp.zeros(x_proto.shape, wire)

        sel_t = jnp.asarray(tables.sel)[d]
        slot_t = jnp.asarray(tables.slot)[d]
        mb_t = jnp.asarray(tables.mb)[d]
        dok_t = jnp.asarray(tables.down_valid)[d]
        dsl_t = jnp.asarray(tables.down_slot)[d]
        rx_t = jnp.asarray(tables.rx_slot)[d]
        dsnd_t = jnp.asarray(tables.down_send)[d]
        loss_t = jnp.asarray(tables.loss)[d]
        emb_t = jnp.asarray(tables.embed)[d]

        init = (zero_w, _zeros_buffer(zero_x, W_down, wire))

        @jax.named_scope(scopes.HOP)
        def hop(h_pl):
            return (jax.lax.ppermute(h_pl, axis, down_perm)
                    if down_used else h_pl)

        def compute(my_p, sinks, edge_p, sel, vslot, emb, x_rx, mb_m):
            def run_idle(_):
                return zero_x

            @jax.named_scope(scopes.EMBED)
            def embed():
                return embed_fn(edge_p, mb_m)

            @jax.named_scope(scopes.STAGE_ENC)
            def run_stage(z):
                x0 = jax.lax.cond(emb, embed, lambda: zero_x)
                x_in = jnp.where(emb, x0, x_rx)
                return stage_fn(rows.enc(my_p, z, vslot), x_in, vslot)

            # the step's gradient rows are the switch's operands (see
            # _SlotRows and the wave executor)
            return jax.lax.switch(sel, (run_idle, run_stage),
                                  rows.rows(sinks, vslot, vslot))

        # one remat region per step, around the switch (see the wave
        # executor: the stack stays a forwarded loop invariant)
        compute = _wrap_remat(compute, cfg)

        @jax.named_scope(scopes.HEAD)
        def head(x_out, mb_m):
            return loss_fn(edge_p, x_out, mb_m)

        def body(h_in, rx, t):
            with jax.named_scope(scopes.RX_STORE):
                rx = _buf_store(rx, dsl_t[t], h_in, dok_t[t])
            mb_m = tree_index(mbs, mb_t[t])
            with jax.named_scope(scopes.RX_STORE):
                x_rx = tree_index(rx, rx_t[t]).astype(zero_x.dtype)
            x_out = compute(my_p, sinks, edge_p, sel_t[t], slot_t[t],
                            emb_t[t], x_rx, mb_m)
            loss = jax.lax.cond(
                loss_t[t],
                lambda: head(x_out, mb_m),
                lambda: jnp.zeros((), jnp.float32))
            h_pl = jnp.where(dsnd_t[t], x_out.astype(wire), zero_w)
            return h_pl, rx, loss

        if cfg.overlap:
            # double-buffered hop: carry = pending payload, permuted at
            # the top of the next step's body (see the wave executor)
            def step(carry, t):
                pend, rx = carry
                h_pl, rx, loss = body(hop(pend), rx, t)
                return (h_pl, rx), loss
        else:
            def step(carry, t):
                h_in, rx = carry
                h_pl, rx, loss = body(h_in, rx, t)
                return (hop(h_pl), rx), loss

        _, losses = jax.lax.scan(step, init, jnp.arange(T))
        return jnp.sum(losses) / M

    return fn
