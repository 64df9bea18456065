"""Static shape / tiling / memory checks for the Pallas TPU kernels.

Each kernel in ``repro.kernels`` asserts its grid divisibility at trace
time, deep inside a jit; this module lifts those launch constraints (plus
the TPU tiling and VMEM-residency facts the kernel docstrings promise)
into plain-arithmetic checks that run with **no jax import** — usable
from the analysis CLI, CI, and the ops-layer ``*_supported`` fallbacks
that route unsupported shapes to the reference implementations instead
of tripping a trace-time assert.

The checked properties mirror the kernels exactly:

- ``flash_attention_fwd`` — grid ``(BH, S/bq)`` with a fori_loop over
  ``T/bk`` K/V tiles; K/V BlockSpecs are *whole rows* ``(T, D)`` resident
  in VMEM, so long-T shapes are bounded by the ~16 MB/core budget here,
  not by the grid.
- ``skip_concat_matmul_fwd`` — grid ``(M/bm, N/bn)``, K-loop over
  ``D/bk``; all block dims clamp to the operand (``min(block, dim)``)
  and the clamped block must tile the dim exactly.
- ``gated_linear_scan_fwd`` — grid ``(R*C/bc, T/bt)`` with the time
  dimension iterated sequentially against a ``(1, bc)`` f32 VMEM scratch
  carry; ``block_t`` is a *static unroll* factor, so oversized values
  explode compile time (flagged as a warning).

Tiling constants are the TPU v4/v5 facts from the Pallas guide: 128-wide
lanes, dtype-dependent sublane minimums (f32 8, bf16 16, int8/fp8 32),
128x128 MXU, ~16 MB VMEM per core.

Findings come in two levels: ``error`` — the launch would assert or
cannot fit — and ``warn`` — it runs but off the hardware's fast path
(sub-tile blocks, VMEM pressure near the ceiling, huge unrolls).
``*_supported`` booleans are errors-only, matching the historical
``skip_concat_matmul_supported`` contract.
"""
from __future__ import annotations

import dataclasses

LANE = 128
MXU = 128
VMEM_BYTES = 16 * 2 ** 20
# minimum second-minor (sublane) tile per dtype; also the itemsize table
SUBLANE = {"float32": 8, "bfloat16": 16, "float16": 16,
           "int8": 32, "float8_e4m3fn": 32, "float8_e5m2": 32}
ITEMSIZE = {"float32": 4, "bfloat16": 2, "float16": 2,
            "int8": 1, "float8_e4m3fn": 1, "float8_e5m2": 1}
# static unroll lengths past this compile pathologically (linear_scan
# emits block_t dependent vector ops per tile)
MAX_UNROLL = 512


@dataclasses.dataclass(frozen=True)
class KernelFinding:
    level: str                   # "error" | "warn"
    detail: str

    def __str__(self) -> str:
        return f"{self.level}: {self.detail}"


@dataclasses.dataclass(frozen=True)
class KernelCheckReport:
    kernel: str
    params: dict
    findings: tuple[KernelFinding, ...]

    @property
    def ok(self) -> bool:
        """No errors — the launch is statically sound (warnings allowed)."""
        return all(f.level != "error" for f in self.findings)

    def errors(self) -> tuple[KernelFinding, ...]:
        return tuple(f for f in self.findings if f.level == "error")

    def __str__(self) -> str:
        head = (f"{self.kernel}(" + ", ".join(
            f"{k}={v}" for k, v in self.params.items()) + "): "
            + ("OK" if self.ok else "UNSUPPORTED"))
        return "\n".join([head] + [f"  {f}" for f in self.findings])


class _Checker:
    def __init__(self, kernel: str, params: dict, dtype: str):
        self.kernel, self.params = kernel, params
        self.findings: list[KernelFinding] = []
        self.dtype = dtype
        if dtype not in SUBLANE:
            self.error(f"dtype {dtype!r} has no TPU tiling rule; expected "
                       f"one of {tuple(SUBLANE)}")
            self.dtype = "float32"   # keep arithmetic going

    def error(self, detail: str):
        self.findings.append(KernelFinding("error", detail))

    def warn(self, detail: str):
        self.findings.append(KernelFinding("warn", detail))

    def positive(self, **dims: int) -> bool:
        bad = [k for k, v in dims.items() if v <= 0]
        for k in bad:
            self.error(f"{k}={dims[k]} is degenerate (the grid would be "
                       "empty or the BlockSpec zero-sized)")
        return not bad

    def tiles(self, name: str, dim: int, block: int) -> int:
        """Clamped block size + exact-tiling check (the kernel assert)."""
        b = min(block, dim)
        if dim % b != 0:
            self.error(f"{name}={dim} is not a multiple of its clamped "
                       f"block {b} (kernel asserts {name} % {b} == 0)")
        return b

    def aligned(self, name: str, val: int, *, lane: bool):
        unit = LANE if lane else SUBLANE[self.dtype]
        axis = "lane" if lane else f"{self.dtype} sublane"
        if val % unit != 0:
            self.warn(f"{name}={val} is not a multiple of the {unit}-wide "
                      f"{axis} tile — the tile pads and the "
                      "MXU/VPU runs below peak")

    def vmem(self, tiles_f32_bytes: int, **tiles_elems: int):
        itemsize = ITEMSIZE[self.dtype]
        total = sum(tiles_elems.values()) * itemsize + tiles_f32_bytes
        if total > VMEM_BYTES:
            names = ", ".join(tiles_elems)
            self.error(
                f"VMEM-resident blocks ({names} + f32 accumulators) need "
                f"{total / 2**20:.1f} MiB > ~{VMEM_BYTES // 2**20} MiB/core")
        elif total > VMEM_BYTES // 2:
            self.warn(
                f"VMEM-resident blocks use {total / 2**20:.1f} MiB — over "
                "half the core budget leaves no room for double-buffered "
                "pipelining")

    def report(self) -> KernelCheckReport:
        return KernelCheckReport(self.kernel, self.params,
                                 tuple(self.findings))


def check_flash_attention(BH: int, S: int, T: int, D: int, *,
                          dtype: str = "float32", block_q: int = 128,
                          block_k: int = 128,
                          window: int | None = None) -> KernelCheckReport:
    """Static launch check for ``flash_attention_fwd`` (flattened layout:
    BH = batch*heads, q (BH, S, D), k/v (BH, T, D))."""
    c = _Checker("flash_attention",
                 {"BH": BH, "S": S, "T": T, "D": D, "dtype": dtype,
                  "block_q": block_q, "block_k": block_k}, dtype)
    if not c.positive(BH=BH, S=S, T=T, D=D):
        return c.report()
    bq = c.tiles("S", S, block_q)
    bk = c.tiles("T", T, block_k)
    c.aligned("D", D, lane=True)
    c.aligned("block_q", bq, lane=False)
    c.aligned("block_k", bk, lane=False)
    if window is not None and window <= 0:
        c.error(f"window={window} masks every key (must be positive)")
    # q/o tiles are (bq, D); K and V are whole (T, D) rows in VMEM;
    # f32: q copy, acc (bq, D), per-tile k/v casts and the (bq, bk) scores
    f32 = 4 * (2 * bq * D + 2 * bk * D + 2 * bq * bk + 2 * bq)
    c.vmem(f32, q=bq * D, k=T * D, v=T * D, o=bq * D)
    return c.report()


def check_skip_concat_matmul(rows: int, d: int, n: int, *,
                             dtype: str = "float32", block_m: int = 128,
                             block_n: int = 128,
                             block_k: int = 128) -> KernelCheckReport:
    """Static launch check for ``skip_concat_matmul_fwd``
    (h/s (rows, d), w (2d, n))."""
    c = _Checker("skip_concat_matmul",
                 {"rows": rows, "d": d, "n": n, "dtype": dtype,
                  "block_m": block_m, "block_n": block_n,
                  "block_k": block_k}, dtype)
    if not c.positive(rows=rows, d=d, n=n):
        return c.report()
    bm = c.tiles("rows", rows, block_m)
    bn = c.tiles("n", n, block_n)
    bk = c.tiles("d", d, block_k)
    c.aligned("block_m", bm, lane=False)
    c.aligned("block_n", bn, lane=True)
    c.aligned("block_k", bk, lane=True)
    # h/s tiles (bm, d), w1/w2 tiles (d, bn), out (bm, bn); f32 acc +
    # per-K-tile casts
    f32 = 4 * (bm * bn + 2 * bm * bk + 2 * bk * bn)
    c.vmem(f32, h=bm * d, s=bm * d, w1=d * bn, w2=d * bn, o=bm * bn)
    return c.report()


def check_gated_linear_scan(R: int, T: int, C: int, *,
                            dtype: str = "float32", block_t: int = 128,
                            block_c: int = 128) -> KernelCheckReport:
    """Static launch check for ``gated_linear_scan_fwd`` (a/x (R, T, C))."""
    c = _Checker("gated_linear_scan",
                 {"R": R, "T": T, "C": C, "dtype": dtype,
                  "block_t": block_t, "block_c": block_c}, dtype)
    if not c.positive(R=R, T=T, C=C):
        return c.report()
    bt = c.tiles("T", T, block_t)
    bc = c.tiles("C", C, block_c)
    c.aligned("block_c", bc, lane=True)
    c.aligned("block_t", bt, lane=False)
    if bt > MAX_UNROLL:
        c.warn(f"block_t={bt} statically unrolls {bt} vector ops per "
               f"tile — past ~{MAX_UNROLL} this dominates compile time")
    # a/x/o tiles (bt, bc) + (1, bc) f32 scratch + f32 casts of a/x/rows
    f32 = 4 * (bc + 3 * bt * bc)
    c.vmem(f32, a=bt * bc, x=bt * bc, o=bt * bc)
    return c.report()


# ---- ops-layer launch predicates (errors-only booleans) ------------------

def skip_concat_matmul_supported(rows: int, d: int, n: int,
                                 block: int = 128) -> bool:
    """Whether (rows, D) x (2D, N) operands tile the kernel's grid (the
    check ``models.diffusion._skip_project`` raises on)."""
    return check_skip_concat_matmul(rows, d, n, block_m=block,
                                    block_n=block, block_k=block).ok


def flash_attention_supported(S: int, T: int, D: int, *,
                              block_q: int = 128,
                              block_k: int = 128) -> bool:
    """Whether (S, T, D) attention shapes satisfy the kernel's grid
    asserts (per-head layout; BH does not affect supportability)."""
    return check_flash_attention(1, S, T, D, block_q=block_q,
                                 block_k=block_k).ok


def gated_linear_scan_supported(T: int, C: int, *, block_t: int = 128,
                                block_c: int = 128) -> bool:
    """Whether (T, C) scan shapes satisfy the kernel's grid asserts."""
    return check_gated_linear_scan(1, T, C, block_t=block_t,
                                   block_c=block_c).ok
