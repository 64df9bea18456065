"""One run of one benchmark cell: set-up, the measured window, the traced
steps, and the comparison with the plain reference that decides
``correct``.

Everything that belongs to one configuration, cell, traffic mix or metric
is found by its name in files of its own: ``bench/configs/<config>.json``,
``bench/workloads/<cell>.json`` (configuration, traffic, chips, pinned
plan, limits), ``bench/traffic/<traffic>.json`` (latent size, global
batch), ``bench/families/<family>.py`` (how the
program builds, feeds and counts that model family, and its reference)
and ``bench/metrics/<metric>.py`` (one ``read(run)`` each).  The list of
metrics a run reports comes from ``BENCHMARK.json``.

A run, in order:

1. set-up (``setup_s``, from process start): plan (``auto_pipeline``),
   mesh, step, parameters and optimizer state made on the device from the
   seed, the step compiled ahead of its first call (or loaded from the
   persistent cache), then the first ``CHECK_STEPS`` steps through the
   window's own call and feed, which warm every shape up and give the
   program's readings for ``correct``;
2. the window: steps back to back until ``--seconds`` have passed, each
   making its batch on the host from ``(seed, step)``, placing it and
   calling the step, with ``AHEAD_S`` seconds of steps dispatched ahead of
   the one whose ``finite`` and loss the host reads, so that a host that
   stands still for a moment does not leave the chip idle; when the time
   is up nothing more is sent, and the window closes once every step sent
   has been read;
3. with ``--trace 1``, a few more steps, driven alike, under the profiler;
4. the device's peak memory is read, the program's state freed, and the
   reference follows the same first steps from the same seed.
"""
from __future__ import annotations

import collections
import contextlib
import gc
import importlib.util
import json
import math
import os
import shutil
import statistics
import sys
import time
import types

#: steps that the program and the reference both follow for ``correct``;
#: every cell's limits were set from readings over this many steps
CHECK_STEPS = 3
#: seconds of steps the window keeps dispatched beyond the one it waits for
AHEAD_S = 4.0


class NoChip(RuntimeError):
    """The machine lacks the accelerator or the chips the cell asks for."""


# --------------------------------------------------------------------------
# the cell's files
# --------------------------------------------------------------------------

def _json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def load(root: str, workload: str) -> tuple[dict, dict, dict]:
    """``(manifest, cell, config)`` of a workload, by name; the cell
    carries its traffic's parameters (``bench/traffic/<traffic>.json``)."""
    manifest = _json(os.path.join(root, "BENCHMARK.json"))
    cell = _json(os.path.join(root, "bench", "workloads", f"{workload}.json"))
    cell = {**_json(os.path.join(root, "bench", "traffic",
                                 f"{cell['traffic']}.json")), **cell}
    cfg = _json(os.path.join(root, "bench", "configs",
                             f"{cell['config']}.json"))
    return manifest, cell, cfg


def module(root: str, kind: str, name: str):
    """``bench/<kind>/<name>.py`` under ``root``, imported from its file."""
    path = os.path.join(root, "bench", kind, f"{name}.py")
    spec = importlib.util.spec_from_file_location(
        f"bench_{kind}_{name}".replace(".", "_"), path)
    if spec is None or not os.path.exists(path):
        raise FileNotFoundError(f"no {kind} file {path}")
    mod = importlib.util.module_from_spec(spec)
    sys.modules[spec.name] = mod
    spec.loader.exec_module(mod)
    return mod


def metrics_of(manifest: dict, workload: str, trace: bool) -> list[dict]:
    """The manifest's metrics this run reports: end-to-end ones with
    ``--trace 0``, per-layer ones with ``--trace 1``."""
    group = manifest["per_layer" if trace else "end_to_end"]
    return [m for m in group
            if workload in m.get("workloads", [workload])]


# --------------------------------------------------------------------------
# host spans
# --------------------------------------------------------------------------

class Spans:
    """Host spans by name, on the host clock; each also written into the
    profiler's trace as a ``TraceAnnotation`` of the same name."""

    def __init__(self):
        self.times: dict[str, list[tuple[float, float]]] = {}

    @contextlib.contextmanager
    def __call__(self, name: str):
        import jax

        with jax.profiler.TraceAnnotation(name):
            t = time.perf_counter()
            try:
                yield
            finally:
                self.times.setdefault(name, []).append(
                    (t, time.perf_counter()))

    def total(self, name: str) -> float | None:
        spans = self.times.get(name)
        return sum(e - s for s, e in spans) if spans else None


class CompileCounter:
    """Counts XLA compilations while ``on`` (JAX's monitoring events)."""

    def __init__(self):
        import jax

        self.on, self.count = False, 0
        jax.monitoring.register_event_duration_secs_listener(self._event)

    def _event(self, event: str, duration: float, **_):
        if self.on and event.endswith("backend_compile_duration"):
            self.count += 1


# --------------------------------------------------------------------------
# the program under test
# --------------------------------------------------------------------------

def _row_norms(params):
    """Norm of each row of the stage stacks (``[D, V, pad, ...]`` ->
    ``[D, V, pad]``) and of each edge leaf, in float32."""
    import jax
    import jax.numpy as jnp

    stacks, edge = params
    sq = lambda x, axes: jnp.sqrt(jnp.sum(
        jnp.square(x.astype(jnp.float32)), axis=axes))
    return (tuple(jax.tree.map(lambda x: sq(x, tuple(range(3, x.ndim))), st)
                  for st in stacks),
            jax.tree.map(lambda x: sq(x, None), edge))


class Program:
    """The program's training path for one cell, built once; state made
    from a seed with :meth:`start`, stepped with :meth:`step` (one at a
    time) or :meth:`drive` (with steps in flight)."""

    def __init__(self, fam, cfg: dict, cell: dict, devices, spans: Spans,
                 log=sys.stderr):
        import jax

        self.fam, self.cfg, self.cell, self.spans = fam, cfg, cell, spans
        self.opt = cfg["optimizer"]
        with spans("plan"):
            self.model_cfg, self.compiled = fam.plan(cfg, cell)
        print("planner: " + self.compiled.describe().replace(
            "\n", "\nplanner: "), file=log)
        p = cell["plan"]
        self.mesh = jax.make_mesh((p["dp"], p["pp"]), ("data", "model"),
                                  devices=devices)
        self.jitted, shardings = fam.train_step(
            self.compiled, self.model_cfg, self.mesh, self.opt)
        self.p_shard, self.o_shard, self.rep = shardings[:3]
        # one executable makes the starting point, and makes it again for
        # the parameters' change: the chip's random-normal code need not
        # round alike when it is compiled into another program
        self.init = jax.jit(self.compiled.init_pipeline_params,
                            out_shardings=self.p_shard)
        self.exe = None
        self.state = None
        self.log = log

    def start(self, seed: int):
        """Parameters and AdamW state from ``seed``, made in place on the
        mesh; the seed's batches and step keys."""
        import jax
        import jax.numpy as jnp

        from repro.optim import adamw_init

        self.seed = seed
        self.key = jax.random.PRNGKey(seed)
        with self.spans("init"):
            params = self.init(self.key)
            opt = jax.jit(adamw_init, out_shardings=self.o_shard)(params)
            self.lr = jax.device_put(jnp.float32(self.opt["lr"]), self.rep)
            jax.block_until_ready(opt)
        self.state = [params, opt]
        self.data = self.fam.batches(self.cfg, self.cell, seed)
        if self.exe is None:
            batch, rng = self._inputs(0)
            with self.spans("compile"):
                self.exe = self.jitted.lower(params, opt, batch, rng,
                                             self.lr).compile()
            ma = self.exe.memory_analysis()
            if ma is not None:
                print(f"step program: arguments "
                      f"{ma.argument_size_in_bytes / 2**30:.3f} GiB, "
                      f"temporaries {ma.temp_size_in_bytes / 2**30:.3f} GiB"
                      f" per device (compiler's memory analysis)",
                      file=self.log)

    def _inputs(self, k: int):
        import jax

        with self.spans("batch"):
            raw = self.data(k)
        with self.spans("place"):
            return (jax.device_put(raw, self.rep),
                    jax.device_put(jax.random.fold_in(self.key, k),
                                   self.rep))

    def dispatch(self, k: int):
        """Step ``k``: batch, placement and the step's dispatch; its loss
        and ``finite`` are returned still on the device."""
        batch, rng = self._inputs(k)
        with self.spans("dispatch"):
            params, opt, loss, finite, _ = self.exe(
                self.state[0], self.state[1], batch, rng, self.lr)
            self.state = [params, opt]
        return loss, finite

    def read(self, out) -> tuple[float, bool]:
        """A dispatched step's loss and ``finite`` on the host."""
        with self.spans("loss_read"):
            return float(out[0]), bool(out[1])

    def step(self, k: int) -> tuple[float, bool]:
        """Step ``k`` and its loss on the host, nothing ahead of it."""
        return self.read(self.dispatch(k))

    def drive(self, k: int, ahead: int, more) -> tuple[int, int, float]:
        """Steps from ``k`` for as long as ``more(k)`` says, ``ahead`` of
        them dispatched beyond the one whose loss is read; then every step
        sent is read.  Returns the next step, the steps whose loss was not
        finite and the last loss."""
        pending, fails, loss = collections.deque(), 0, math.nan
        while more(k):
            pending.append(self.dispatch(k))
            k += 1
            if len(pending) > ahead:
                loss, ok = self.read(pending.popleft())
                fails += not (ok and math.isfinite(loss))
        while pending:
            loss, ok = self.read(pending.popleft())
            fails += not (ok and math.isfinite(loss))
        return k, fails, loss

    def _model_rows(self, rows) -> dict:
        import jax
        import numpy as np

        stacks, edge = jax.device_get(rows)
        merged = self.compiled.merge_params(stacks, edge)
        return jax.tree.map(lambda x: np.asarray(x, np.float64), merged)

    def grad_rows(self) -> dict:
        """Per-leaf norms of the first step's gradient as AdamW got it,
        from its first moment after one step (``m = (1 - b1) g``)."""
        import jax

        rows = jax.jit(_row_norms)(self.state[1]["m"])
        return jax.tree.map(lambda x: x / (1.0 - self.opt["b1"]),
                            self._model_rows(rows))

    def change_rows(self) -> dict:
        """Per-leaf norms of the parameters' change since the start, the
        start made again by the executable that first made it."""
        import jax
        import jax.numpy as jnp

        diff = jax.jit(lambda p, p0: _row_norms(jax.tree.map(
            lambda a, b: a.astype(jnp.float32) - b.astype(jnp.float32),
            p, p0)))
        rows = diff(self.state[0], self.init(self.key))
        return self._model_rows(rows)

    def readings(self, steps: int) -> dict:
        """Steps ``0 .. steps-1`` and the program's readings over them."""
        losses, failed, grad = [], 0, None
        for k in range(steps):
            t = time.perf_counter()
            loss, ok = self.step(k)
            #: the last check step's wall time, dispatch to loss
            self.step_s = time.perf_counter() - t
            losses.append(loss)
            failed += not (ok and math.isfinite(loss))
            if k == 0:
                grad = self.grad_rows()
        return {"losses": losses, "failed": failed, "grad": grad,
                "change": self.change_rows()}

    def free(self):
        """Drop the training state, every other array the run made and the
        compiled programs, which may keep device memory while loaded."""
        import jax

        self.state = self.exe = self.init = self.jitted = None
        jax.clear_caches()
        gc.collect()
        for a in jax.live_arrays():
            a.delete()


# --------------------------------------------------------------------------
# the comparison that decides ``correct``
# --------------------------------------------------------------------------

def _flat(tree: dict) -> dict:
    import jax
    import numpy as np

    out = {}
    for path, x in jax.tree_util.tree_flatten_with_path(tree)[0]:
        name = jax.tree_util.keystr(path)
        x = np.atleast_1d(np.asarray(x, np.float64))
        for i, v in enumerate(x):
            out[f"{name}[{i}]" if x.size > 1 else name] = float(v)
    return out


def worst_leaf(prog: dict, ref: dict, keep=None) -> tuple[float, str]:
    """Largest gap between the program's and the reference's norm of a
    leaf, over the larger of the reference's norm of that leaf and the
    median leaf's; ``keep`` restricts the leaves."""
    p, r = _flat(prog), _flat(ref)
    if set(p) != set(r):
        raise ValueError(f"leaves differ: {sorted(set(p) ^ set(r))[:8]}")
    names = [n for n in r if keep is None or n in keep]
    med = statistics.median(r[n] for n in names)
    gap, at = max((abs(p[n] - r[n]) / max(r[n], med), n) for n in names)
    return gap, at


def compare(prog: dict, ref: dict) -> dict:
    """The numbers compared: the worst relative gap of the step losses,
    the worst leaf of the first gradient, and the worst leaf of the
    parameters' change.  Leaves whose reference gradient is under a
    thousandth of the median leaf's move by round-off alone and are left
    out of the change."""
    loss = max(abs(a - b) / abs(b) for a, b in zip(prog["losses"],
                                                    ref["losses"]))
    grad, grad_at = worst_leaf(prog["grad"], ref["grad"])
    g = _flat(ref["grad"])
    floor = 1e-3 * statistics.median(g.values())
    keep = {n for n, v in g.items() if v >= floor}
    change, change_at = worst_leaf(prog["change"], ref["change"], keep)
    return {"loss_gap": loss, "grad_gap": grad, "change_gap": change,
            "at": {"grad_gap": grad_at, "change_gap": change_at},
            "left_out": sorted(set(g) - keep)}


# --------------------------------------------------------------------------
# one run
# --------------------------------------------------------------------------

def devices_for(cell: dict, require_tpu: bool):
    import jax

    devs = jax.devices()
    if require_tpu and devs[0].platform != "tpu":
        raise NoChip(f"no TPU: JAX's first device is {devs[0].platform!r}; "
                     "the benchmark measures the chip only")
    if len(devs) < cell["chips"]:
        raise NoChip(f"the cell needs {cell['chips']} chips, JAX finds "
                     f"{len(devs)}")
    return devs[:cell["chips"]]


def peak_of(root: str, kind: str) -> dict:
    peaks = _json(os.path.join(root, "bench", "peaks.json"))["devices"]
    if kind not in peaks:
        raise KeyError(f"device kind {kind!r} is not in bench/peaks.json")
    return peaks[kind]


def run(root: str, workload: str, seed: int, seconds: float, trace: bool,
        *, t0: float, require_tpu: bool = True, log=sys.stderr) -> dict:
    """One run of ``workload``; returns the result line as a dict."""
    manifest, cell, cfg = load(root, workload)
    fam = module(root, "families", cfg["family"])
    wanted = metrics_of(manifest, workload, trace)
    readers = {m["name"]: module(root, "metrics", m["name"]) for m in wanted}
    import jax

    from repro.launch.compile_cache import enable_compile_cache

    devices = devices_for(cell, require_tpu)
    kind = devices[0].device_kind
    if require_tpu:
        peak_of(root, kind)
    print(f"compile cache: {enable_compile_cache()}", file=log)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    counter = CompileCounter()
    spans = Spans()
    prog = Program(fam, cfg, cell, devices, spans, log)
    prog.start(seed)
    checks = CHECK_STEPS
    mine = prog.readings(checks)
    setup_s = time.perf_counter() - t0
    print(f"set-up: {setup_s:.3f} s (plan {spans.total('plan'):.3f} s, "
          f"init {spans.total('init'):.3f} s, compile "
          f"{spans.total('compile'):.3f} s)", file=log)

    # the window
    ahead = max(1, round(AHEAD_S / prog.step_s))
    marks = {n: len(spans.times[n]) for n in ("batch", "dispatch")}
    counter.on = True
    w0 = time.perf_counter()
    k, failed, loss = prog.drive(
        checks, ahead, lambda _: time.perf_counter() - w0 < seconds)
    window_s = time.perf_counter() - w0
    counter.on = False
    fails = mine["failed"] + failed
    steps = k - checks
    batch = spans.times["batch"][marks["batch"]:]
    disp = spans.times["dispatch"][marks["dispatch"]:]
    host_gaps = [d[1] - b[0] for b, d in zip(batch, disp)]
    print(f"window: {steps} steps in {window_s:.4f} s, {ahead} ahead, last "
          f"loss {loss:.6f}, {counter.count} compilations inside", file=log)

    reduced, traced = None, 0
    if trace:
        from bench import trace as tr

        traced = max(3, math.ceil(2.0 / prog.step_s))
        tdir = os.path.join(root, ".bench", "trace", workload)
        shutil.rmtree(tdir, ignore_errors=True)
        end = k + traced
        jax.profiler.start_trace(tdir)
        with jax.profiler.TraceAnnotation(tr.WINDOW):
            k, failed, _ = prog.drive(k, ahead, lambda i: i < end)
        jax.profiler.stop_trace()
        fails += failed
        reduced = tr.reduce(tr.read(tr.find(tdir)))
        shutil.rmtree(tdir, ignore_errors=True)
        if reduced is not None:
            print(f"trace: {traced} steps, busy {reduced['busy_s']:.4f} s of "
                  f"{reduced['window_s']:.4f} s per device, "
                  f"{reduced['collective_ops']} collective ops", file=log)

    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in devices)
    prog.free()
    stats = [d.memory_stats() or {} for d in devices]
    print(f"before the reference: "
          f"{max(m.get('bytes_in_use', 0) for m in stats) / 2**30:.3f} GiB "
          f"in use on the fullest device", file=log)
    t_ref = time.perf_counter()
    ref = fam.reference_run(cfg, cell, seed, checks, devices)
    print(f"reference: {checks} steps in {time.perf_counter() - t_ref:.3f} s"
          f", losses {ref['losses']} (program {mine['losses']})", file=log)
    gaps = compare(mine, ref)
    limits = cell["limits"]
    correct = fails == 0 and all(gaps[n] <= limits[n] for n in limits)

    run_ns = types.SimpleNamespace(
        cell=cell, cfg=cfg, workload=workload, chips=len(devices),
        setup_s=setup_s, spans=spans, window_steps=steps, window_s=window_s,
        samples=steps * cell["global_batch"], host_gaps=host_gaps,
        flops_per_step=fam.model_flops(cfg, cell),
        peak=lambda: peak_of(root, kind), trace=reduced, traced_steps=traced)
    metrics = {}
    for m in wanted:
        value = readers[m["name"]].read(run_ns)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    device = {"platform": devices[0].platform, "kind": kind,
              "count": len(jax.devices()), "memory_peak_bytes": peak}
    result = {"correct": bool(correct), "attempted": k, "failed": fails,
              "metrics": metrics, "device": device}
    if reduced is not None:
        device["busy_s"] = reduced["busy_s"]
        device["window_s"] = reduced["window_s"]
        result["breakdown"] = {"device_ops": reduced["device_ops"],
                               "idle_gaps": reduced["idle_gaps"]}
    result["compared"] = {n: {"value": gaps[n], "limit": limits[n]}
                          for n in limits}
    print(f"worst leaves: {gaps['at']}; left out of the change: "
          f"{gaps['left_out']}", file=log)
    for n in limits:
        print(f"compared {n} {gaps[n]!r} limit {limits[n]!r}", file=log)
    return result
