"""Attribution of a trace's device operations to the program's named scopes.

The program names its layers with ``jax.named_scope`` (the table in
``src/repro/runtime/scopes.py``; the names below are checked against it by
the tests).  Each HLO instruction carries the scopes it was traced in as
its ``op_name``, a path such as ``jit(step)/transpose(jvp())/
pulse.executor/while/body/.../pulse.stage_dec/attention/dot_general``.  A
fusion counts under the ``op_name`` XLA gives the fusion instruction, that
of one fused instruction (its root, or the matmul it is built around): a
block's MLP matmul fused with the masked scan's select counts under
``mlp`` though XLA names the fusion after its root (``add_select_fusion``).

Each inner operation of a device's ``XLA Ops`` line (``trace.leaves``),
clipped to the traced window, is attributed to

- a layer: ``stage`` where a stage, embedding, head or model-part scope
  (:data:`STAGE`) is on its path, else ``executor`` under
  ``pulse.executor``, else ``optimizer`` under ``pulse.optimizer``, else
  ``other``;
- its innermost scope of :data:`SCOPES` (``other`` for none);
- a pass: ``recompute`` where ``rematted_computation`` is on the path (the
  remat recompute runs inside the backward pass), else ``bwd`` where
  ``transpose(`` is, else ``fwd``;
- a kind: ``collective`` (``trace.COLLECTIVE``) or ``compute``.

Print the per-device, per-scope, per-pass table of a trace, for example
a ``jax.profiler`` trace of ``launch/train.py``, from the root of a
checkout::

    python -m bench.scopes <.xplane.pb, or a dir holding one> [--steps N]

Without ``--steps`` the steps are the host's ``train`` step annotations in
the window (``launch/train.py::run`` writes one a step), else one.
"""
from __future__ import annotations

from bench import trace as tr

EXECUTOR = "pulse.executor"
HOP = "pulse.hop"
RX_STORE = "pulse.rx_store"
STASH = "pulse.stash"
STAGE_ENC = "pulse.stage_enc"
STAGE_DEC = "pulse.stage_dec"
EMBED = "pulse.embed"
HEAD = "pulse.head"
LOSS_ALLREDUCE = "pulse.loss_allreduce"
ZERO_GATHER = "pulse.zero_gather"
OPTIMIZER = "pulse.optimizer"
ATTENTION = "attention"
MLP = "mlp"
SKIP_PROJ = "skip_proj"
#: every scope the table knows, as the program spells it
SCOPES = (EXECUTOR, HOP, RX_STORE, STASH, STAGE_ENC, STAGE_DEC, EMBED, HEAD,
          LOSS_ALLREDUCE, ZERO_GATHER, OPTIMIZER, ATTENTION, MLP, SKIP_PROJ)
#: scopes whose operations are the model's own work as the executor runs it
STAGE = (STAGE_ENC, STAGE_DEC, EMBED, HEAD, ATTENTION, MLP, SKIP_PROJ)
LAYERS = ("stage", "executor", "optimizer", "other")
PASSES = ("fwd", "recompute", "bwd")
#: the step annotation of ``launch/train.py::run``
STEP = "train"


def _protos():
    """Message classes for the parts of two protos read here: the
    profiler's ``XSpace`` (per plane, its event metadata with their stats,
    and its stat names) and XLA's ``HloProto`` (per instruction, its name,
    ``op_name`` and the computations it calls).  Other fields are skipped
    as unknown."""
    from google.protobuf import descriptor_pb2, descriptor_pool
    from google.protobuf import message_factory

    f = descriptor_pb2.FileDescriptorProto(name="bench_scopes.proto",
                                           package="bench_scopes")
    T = descriptor_pb2.FieldDescriptorProto

    def msg(name, *fields):
        m = f.message_type.add(name=name)
        for field, num, kind in fields:
            fd = m.field.add(name=field, number=num, label=(
                T.LABEL_REPEATED if kind.startswith("*") else
                T.LABEL_OPTIONAL))
            kind = kind.lstrip("*")
            if kind.isupper():
                fd.type = getattr(T, f"TYPE_{kind}")
            else:
                fd.type, fd.type_name = T.TYPE_MESSAGE, f".bench_scopes.{kind}"

    msg("Stat", ("metadata_id", 1, "INT64"), ("uint64_value", 3, "UINT64"),
        ("bytes_value", 6, "BYTES"))
    msg("EventMetadata", ("name", 2, "STRING"), ("stats", 5, "*Stat"))
    msg("StatMetadata", ("name", 2, "STRING"))
    # the proto's maps, read as their repeated key-value entries
    msg("EventEntry", ("key", 1, "INT64"), ("value", 2, "EventMetadata"))
    msg("StatEntry", ("key", 1, "INT64"), ("value", 2, "StatMetadata"))
    msg("Plane", ("name", 2, "STRING"), ("event_metadata", 4, "*EventEntry"),
        ("stat_metadata", 5, "*StatEntry"))
    msg("Space", ("planes", 1, "*Plane"))
    msg("OpMetadata", ("op_name", 2, "STRING"))
    msg("Instruction", ("name", 1, "STRING"), ("metadata", 7, "OpMetadata"),
        ("called_computation_ids", 38, "*INT64"))
    msg("Computation", ("instructions", 2, "*Instruction"),
        ("id", 5, "INT64"))
    msg("Module", ("computations", 3, "*Computation"))
    msg("Hlo", ("hlo_module", 1, "Module"))
    pool = descriptor_pool.DescriptorPool()
    pool.Add(f)
    return tuple(message_factory.GetMessageClass(pool.FindMessageTypeByName(
        f"bench_scopes.{n}")) for n in ("Space", "Hlo"))


def _module_op_names(module) -> dict:
    """``{instruction: op_name}`` of an HLO module.  An instruction with no
    ``op_name`` of its own, such as a copy the compiler put in, takes that
    of the instruction that calls its computation (a loop, a branch)."""
    own, comp_of, caller = {}, {}, {}
    for comp in module.computations:
        for ins in comp.instructions:
            own[ins.name] = ins.metadata.op_name
            comp_of[ins.name] = comp.id
            for cid in ins.called_computation_ids:
                caller.setdefault(cid, ins.name)

    def resolve(name):
        while name is not None and not own[name]:
            name = caller.get(comp_of[name])
        return "" if name is None else own[name]

    return {name: resolve(name) for name in own}


def op_names(path: str) -> dict:
    """``{device plane: {operation's HLO text: op_name}}`` from an
    ``.xplane.pb``.  Neither an event nor its name carries the
    ``op_name``: the trace's ``/host:metadata`` plane holds each program's
    HLO module, and each device operation's event metadata names its
    program (``program_id``) and its instruction (the HLO text's name)."""
    Space, Hlo = _protos()
    with open(path, "rb") as f:
        space = Space.FromString(f.read())
    modules, out = {}, {}
    for plane in space.planes:
        if plane.name == "/host:metadata":
            # each event metadata: "<module>(<program id>)", HloProto bytes
            for e in plane.event_metadata:
                pid = int(e.value.name.rpartition("(")[2].rstrip(")"))
                modules[pid] = _module_op_names(Hlo.FromString(
                    e.value.stats[0].bytes_value).hlo_module)
    for plane in space.planes:
        if not _is_device(plane.name):
            continue
        pid_stat = {e.key for e in plane.stat_metadata
                    if e.value.name == "program_id"}
        names = out[plane.name] = {}
        for e in plane.event_metadata:
            md = e.value
            pid = [s.uint64_value for s in md.stats
                   if s.metadata_id in pid_stat]
            instr = md.name.partition(" = ")[0].lstrip("%")
            op = modules.get(pid[0], {}).get(instr, "") if pid else ""
            if op:
                names.setdefault(md.name, op)
    return out


def _is_device(plane: str) -> bool:
    return plane.startswith("/device:") and "CPU" not in plane


def attribute(op_name: str) -> tuple[str, str, str]:
    """``(layer, innermost scope, pass)`` of an ``op_name``."""
    parts = op_name.split("/")
    known = [p for p in parts if p in SCOPES]
    if any(p in STAGE for p in known):
        layer = "stage"
    elif EXECUTOR in known:
        layer = "executor"
    elif OPTIMIZER in known:
        layer = "optimizer"
    else:
        layer = "other"
    if "rematted_computation" in parts:
        pas = "recompute"
    elif "transpose(" in op_name:
        pas = "bwd"
    else:
        pas = "fwd"
    return layer, known[-1] if known else "other", pas


def read(path: str) -> dict:
    """``{"devices": {name: [(op, start, end, op_name)]}, "host": [(span,
    start, end)]}`` from an ``.xplane.pb``, times in ns; the host holds the
    ``traced_window`` span and the ``train`` step annotations."""
    from jax.profiler import ProfileData

    names = op_names(path)
    devices, host = {}, []
    for plane in ProfileData.from_file(path).planes:
        if _is_device(plane.name):
            devices[plane.name] = [
                (n, s, e, names[plane.name].get(n, ""))
                for n, s, e in tr.events(plane, tr.OPS_LINE)]
        elif plane.name.startswith("/host:"):
            host += [e for e in tr.events(plane)
                     if e[0] in (tr.WINDOW, STEP)]
    return {"devices": devices, "host": host}


def table(raw: dict) -> dict | None:
    """Per device: device seconds of its inner operations in the window by
    ``(layer, scope, pass, kind)`` (``rows``); the union of its compute
    operations (``compute_s``) and of all (``busy_s``); and the time its
    hops ran with no compute beside them (``hop_exposed_s``).  None when
    the trace holds no device operation."""
    devs = {k: v for k, v in raw["devices"].items() if v}
    if not devs:
        return None
    wins = [(s, e) for n, s, e in raw["host"] if n == tr.WINDOW]
    if wins:
        lo, hi = wins[0]
    else:
        lo = min(op[1] for ops in devs.values() for op in ops)
        hi = max(op[2] for ops in devs.values() for op in ops)
    ns = 1e-9
    out = {}
    for dev, ops in sorted(devs.items()):
        inner = [(n, max(s, lo), min(e, hi)) for n, s, e in tr.leaves(
            ((name, opn), s, e) for name, s, e, opn in ops)
            if e > lo and s < hi]
        rows: dict[tuple, float] = {}
        compute, hops = [], []
        for (name, opn), s, e in inner:
            layer, scope, pas = attribute(opn)
            coll = bool(tr.COLLECTIVE.search(name))
            key = (layer, scope, pas, "collective" if coll else "compute")
            rows[key] = rows.get(key, 0.0) + (e - s) * ns
            if not coll:
                compute.append((s, e))
            elif scope == HOP:
                hops.append((s, e))
        compute = tr.union(compute)
        out[dev] = {
            "rows": [[*k, v] for k, v in sorted(rows.items())],
            "compute_s": tr.length(compute) * ns,
            "busy_s": tr.length(tr.union((s, e) for _, s, e in inner)) * ns,
            "hop_exposed_s": tr.length(tr.minus(tr.union(hops),
                                                compute)) * ns,
        }
    return {"window_s": (hi - lo) * ns, "devices": out,
            "steps": sum(1 for n, s, e in raw["host"]
                         if n == STEP and s >= lo and e <= hi)}


def per_step(tab: dict, steps: int) -> dict:
    """Milliseconds of device time per step, averaged over the devices:
    compute operations of the stages (``stage_ms``), of the executor
    outside them (``executor_ms``), of the optimizer (``optimizer_ms``)
    and of no scope (``other_ms``); of ``attention`` (``attention_ms``, a
    part of ``stage_ms``); the union of the compute operations
    (``compute_ms``); the hops' time with no compute beside them
    (``hop_exposed_ms``)."""
    devs = tab["devices"].values()
    scale = 1e3 / steps / len(devs)
    out = {f"{layer}_ms": scale * sum(
        v for d in devs for lay, _, _, kind, v in d["rows"]
        if lay == layer and kind == "compute") for layer in LAYERS}
    out["attention_ms"] = scale * sum(
        v for d in devs for _, scope, _, kind, v in d["rows"]
        if scope == ATTENTION and kind == "compute")
    out["compute_ms"] = scale * sum(d["compute_s"] for d in devs)
    out["hop_exposed_ms"] = scale * sum(d["hop_exposed_s"] for d in devs)
    return out


def format_table(tab: dict, steps: int) -> str:
    """The table as text: per device and scope, ms a step in each pass."""
    lines = [f"window {tab['window_s'] * 1e3:.3f} ms, {steps} steps; "
             "ms a step"]
    head = f"  {'layer':9} {'scope':22} {'kind':10}" + "".join(
        f" {p:>10}" for p in PASSES)
    for dev, d in tab["devices"].items():
        lines += [f"{dev}: compute {d['compute_s'] * 1e3 / steps:.3f}, "
                  f"busy {d['busy_s'] * 1e3 / steps:.3f}, hop exposed "
                  f"{d['hop_exposed_s'] * 1e3 / steps:.3f}", head]
        cells: dict[tuple, dict] = {}
        for layer, scope, pas, kind, v in d["rows"]:
            cells.setdefault((LAYERS.index(layer), scope, kind), {})[pas] = v
        for (li, scope, kind), by_pass in sorted(cells.items()):
            lines.append(f"  {LAYERS[li]:9} {scope:22} {kind:10}" + "".join(
                f" {by_pass.get(p, 0.0) * 1e3 / steps:10.3f}"
                for p in PASSES))
    lines.append("mean over devices: " + ", ".join(
        f"{k} {v:.3f}" for k, v in per_step(tab, steps).items()))
    return "\n".join(lines)


def main(argv=None) -> int:
    import argparse
    import os

    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("trace", help=".xplane.pb, or a dir holding one")
    ap.add_argument("--steps", type=int, default=None)
    args = ap.parse_args(argv)
    path = (tr.find(args.trace) if os.path.isdir(args.trace)
            else args.trace)
    tab = table(read(path))
    if tab is None:
        print(f"{path}: no device operation")
        return 1
    print(format_table(tab, args.steps or tab["steps"] or 1))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
