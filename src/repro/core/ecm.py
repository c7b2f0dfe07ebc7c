"""Execution-Cache-Memory (ECM) performance model — paper §2, adapted to TPU.

Two model families live here:

1. ``ecm_x86`` — a faithful implementation of the paper's model, including
   the machine descriptions of the four Xeons in Table 1 and the kernel
   descriptions of the naive / Kahan dot variants. We *reproduce the paper's
   own Table 2 and the predictions in §3* from first principles; tests pin
   the published numbers ({8|8|12|18.1+2.9} cy → {4.40|4.40|2.93|1.68} GUP/s
   on IVB, saturation points 4/11/6, ...).

2. ``ecm_tpu`` — the TPU adaptation. The memory hierarchy is
   VREG ← VMEM ← HBM; the unit of work is one VMEM block (BlockSpec tile)
   instead of one cache line. The central *assumption inversion*: on TPU
   the HBM→VMEM DMA overlaps with compute when the kernel is
   double-buffered, so

       T_db  = max(T_core, T_hbm)          (double-buffered, the default)
       T_sb  = T_core + T_hbm              (single-buffered, paper-style
                                            non-overlap — kept for comparison)
       T_core = max(T_comp, T_vmem)        (VPU ALU vs VPU load ports)

   Saturation: v5e has one TensorCore per chip with private HBM, so the
   paper's core-count saturation is reported as ``n_s_equiv`` =
   ceil(T_core / T_hbm): the number of concurrent instruction-bound streams
   that would be needed to saturate the chip's HBM — the quantity that
   decides whether "Kahan comes for free" (n_s_equiv == that of naive).

The kernel descriptions (instruction mixes) are NOT a parallel hardcoded
list: they derive from the compensation-scheme registry
(``repro.kernels.schemes``) via ``dot_kernel_for_scheme`` /
``tpu_block_for_scheme``. The registry owns adds/muls per scalar
iteration; this module only adds the machine axis (SIMD width, element
bytes, VMEM-block size). Registering a new scheme makes it predictable
here (``registry_dot_kernels`` / ``registry_tpu_blocks`` /
``ecm_tpu_for_scheme``) with no edits to this file. The named module
constants (``KAHAN_AVX_SP``, ``DOT2_TPU``, ...) are built lazily (PEP
562) from the same derivation, so importing this module stays light.

All cycle math is plain Python floats — jax is only reached through the
lazy registry import, and only for metadata (no arrays).
"""

from __future__ import annotations

import dataclasses
import math
from typing import Dict, Optional, Tuple, Union


# ===========================================================================
# Part 1: the paper's x86 model (validation target)
# ===========================================================================

@dataclasses.dataclass(frozen=True)
class X86Machine:
    """One row of paper Table 1 (per-socket)."""

    name: str
    clock_ghz: float
    cores: int
    simd_bytes: int                 # AVX register width
    avx_loads_per_cy: float         # AVX loads retired per cycle
    scalar_loads_per_cy: float
    add_per_cy: float               # ADD/SUB pipe throughput (SIMD or scalar insn)
    mul_per_cy: float
    l1l2_bytes_per_cy: float        # L2->L1 bus width
    l2l3_bytes_per_cy: float
    load_bw_gbs: float              # measured load-only memory bandwidth
    mem_penalty_cy_per_cl: float    # empirical latency penalty (paper §2/§3)
    l2l3_single_core_cy_per_cl: Optional[float] = None  # HSW uncore slowdown

    def t_l3mem_cy_per_cl(self) -> float:
        """Cycles to move one 64 B cache line from memory (no penalty)."""
        return 64.0 * self.clock_ghz / self.load_bw_gbs


# Paper Table 1 (load-only BW row), cache line = 64 B.
SNB = X86Machine("SNB", 2.7, 8, 32, 1.0, 2.0, 1.0, 1.0, 32.0, 32.0, 43.6, 5.1)
IVB = X86Machine("IVB", 2.2, 10, 32, 1.0, 2.0, 1.0, 1.0, 32.0, 32.0, 46.1, 2.9)
HSW = X86Machine("HSW", 2.3, 14, 32, 2.0, 2.0, 1.0, 2.0, 64.0, 32.0, 60.6, 11.1,
                 l2l3_single_core_cy_per_cl=2.77)
BDW = X86Machine("BDW", 1.8, 8, 32, 2.0, 2.0, 1.0, 2.0, 64.0, 32.0, 33.0, 1.0)

PAPER_MACHINES: Dict[str, X86Machine] = {m.name: m for m in (SNB, IVB, HSW, BDW)}


@dataclasses.dataclass(frozen=True)
class DotKernel:
    """Instruction mix of one *scalar iteration* of a dot-product loop."""

    name: str
    adds: int            # ADD/SUB ops per scalar iteration
    muls: int
    loads: int           # input streams (a[i], b[i])
    flops: int           # useful flops per iteration (for GUP accounting: 2)
    elem_bytes: int      # bytes per element (4 SP / 8 DP)
    simd: str            # 'scalar' | 'sse' | 'avx'


# The named kernels (NAIVE_SP, KAHAN_AVX_SP, ... ) are derived from the
# compensation-scheme registry — see ``dot_kernel_for_scheme`` and the
# module ``__getattr__`` at the bottom of this file.


@dataclasses.dataclass(frozen=True)
class ECMResult:
    """ECM model output for one (machine, kernel) pair.

    ``model_cy`` is the shorthand {T_OL || T_nOL | L1L2 | L2L3 | L3Mem} and
    ``pred_cy`` the per-level prediction {L1 | L2 | L3 | Mem}; both in cycles
    per unit of work. ``perf_gups`` is per-level GUP/s, ``n_s`` the predicted
    saturation core count, ``p_bw_gups`` the bandwidth roofline.
    """

    machine: str
    kernel: str
    unit_iters: int
    t_ol: float
    t_nol: float
    t_l1l2: float
    t_l2l3: float
    t_l3mem: float
    penalty: float
    pred_cy: Tuple[float, float, float, float]
    perf_gups: Tuple[float, float, float, float]
    n_s: int
    p_bw_gups: float

    def shorthand(self) -> str:
        return (f"{{{self.t_ol:g} || {self.t_nol:g} | {self.t_l1l2:g} | "
                f"{self.t_l2l3:g} | {self.t_l3mem:g}+{self.penalty:g}}} cy")

    def pred_shorthand(self) -> str:
        p = self.pred_cy
        return f"{{{p[0]:g} | {p[1]:g} | {p[2]:g} | {p[3]:g}}} cy"


def ecm_x86(machine: X86Machine, kernel: DotKernel) -> ECMResult:
    """Evaluate the paper's ECM model for a dot-family kernel."""
    # Unit of work: one cache line per stream = 64/elem_bytes scalar iters.
    unit_iters = 64 // kernel.elem_bytes
    if kernel.simd == "avx":
        width = machine.simd_bytes // kernel.elem_bytes
    elif kernel.simd == "sse":
        width = 16 // kernel.elem_bytes
    else:
        width = 1
    vec_iters = unit_iters / width

    # Core: ADD pipe vs MUL pipe (separate ports) — bottleneck is the max.
    t_add = vec_iters * kernel.adds / machine.add_per_cy
    t_mul = vec_iters * kernel.muls / machine.mul_per_cy
    t_ol = max(t_add, t_mul)

    # Loads are the non-overlapping part (paper model assumption (i)).
    loads = vec_iters * kernel.loads
    loads_per_cy = machine.scalar_loads_per_cy if kernel.simd == "scalar" \
        else machine.avx_loads_per_cy
    if kernel.simd == "sse":
        # SSE loads dual-issue on all four machines (2×16 B ports).
        loads_per_cy = 2.0
    t_nol = loads / loads_per_cy

    # Transfers: one CL per stream per unit of work.
    cls_per_unit = kernel.loads  # 2 streams -> 2 CLs
    t_l1l2 = cls_per_unit * 64.0 / machine.l1l2_bytes_per_cy
    if machine.l2l3_single_core_cy_per_cl is not None:
        t_l2l3 = cls_per_unit * machine.l2l3_single_core_cy_per_cl
    else:
        t_l2l3 = cls_per_unit * 64.0 / machine.l2l3_bytes_per_cy
    t_l3mem = cls_per_unit * machine.t_l3mem_cy_per_cl()
    # The paper quotes the latency penalty per 2-CL unit of work directly
    # (e.g. "+2.9" on IVB); keep their convention: once per unit of work.
    penalty = machine.mem_penalty_cy_per_cl

    def pred(upto: int) -> float:
        t_data = sum([t_l1l2, t_l2l3, t_l3mem + penalty][:upto])
        return max(t_nol + t_data, t_ol)

    pred_cy = (pred(0), pred(1), pred(2), pred(3))
    perf = tuple(unit_iters * machine.clock_ghz / p for p in pred_cy)

    # Saturation (divide by the *no-penalty* memory transfer time, paper §3).
    n_s = math.ceil(pred_cy[3] / t_l3mem)
    # Bandwidth roofline: one update per (2 * elem_bytes) transferred.
    p_bw = machine.load_bw_gbs / (kernel.loads * kernel.elem_bytes)

    return ECMResult(
        machine=machine.name, kernel=kernel.name, unit_iters=unit_iters,
        t_ol=t_ol, t_nol=t_nol, t_l1l2=t_l1l2, t_l2l3=t_l2l3,
        t_l3mem=round(t_l3mem, 2), penalty=penalty,
        pred_cy=tuple(round(p, 2) for p in pred_cy),
        perf_gups=tuple(round(p, 2) for p in perf),
        n_s=n_s, p_bw_gups=round(p_bw, 2),
    )


def multicore_scaling(machine: X86Machine, kernel: DotKernel, n: int) -> float:
    """P(n) = min(n * P_ECM_mem, I * b_S) in GUP/s (paper §2)."""
    r = ecm_x86(machine, kernel)
    return min(n * r.perf_gups[3], r.p_bw_gups)


# ===========================================================================
# Part 2: TPU adaptation
# ===========================================================================

@dataclasses.dataclass(frozen=True)
class TPUMachine:
    """Nominal single-chip TPU description (per TensorCore where relevant).

    Numbers are public-spec nominal values; v5e is the grading target
    (197 TF bf16 / 819 GB/s / ~50 GB/s/link per the task brief).
    """

    name: str
    clock_ghz: float
    mxu_bf16_tflops: float        # peak MXU throughput per chip
    vpu_fp32_flops_per_cy: float  # VPU: lanes * ALUs (8*128*2 default)
    vmem_load_bytes_per_cy: float # VMEM -> VREG per cycle (two 8x128 ports)
    vmem_bytes: int               # VMEM capacity
    hbm_gbs: float                # HBM bandwidth per chip
    hbm_gib: float                # HBM capacity per chip
    ici_gbs_per_link: float
    ici_links: int

    def hbm_bytes_per_cy(self) -> float:
        return self.hbm_gbs / self.clock_ghz  # (GB/s)/(Gcy/s) = B/cy


TPU_V4 = TPUMachine("v4", 1.05, 275.0, 8 * 128 * 2, 2 * 8 * 128 * 4, 128 * 2**20,
                    1228.0, 32.0, 50.0, 6)
TPU_V5E = TPUMachine("v5e", 0.94, 197.0, 8 * 128 * 2, 2 * 8 * 128 * 4, 128 * 2**20,
                     819.0, 16.0, 50.0, 3)
TPU_V5P = TPUMachine("v5p", 1.75, 459.0, 8 * 128 * 2, 2 * 8 * 128 * 4, 128 * 2**20,
                     2765.0, 95.0, 100.0, 6)

TPU_MACHINES: Dict[str, TPUMachine] = {m.name: m for m in (TPU_V4, TPU_V5E, TPU_V5P)}


@dataclasses.dataclass(frozen=True)
class TPUKernelBlock:
    """One VMEM block ("unit of work") of a streaming reduction kernel."""

    name: str
    elems: int           # elements per block per stream
    streams: int         # input streams (dot: 2, sum: 1)
    flops_per_elem: int  # executed VPU flops per element (kahan dot: 5)
    useful_flops: int    # flops counted as work (update = 2)
    elem_bytes: int
    sequential: bool = False  # fori_loop element-at-a-time ("scalar" analog)


def tpu_dot_block(name: str, elems: int, flops_per_elem: int,
                  elem_bytes: int = 4, streams: int = 2,
                  sequential: bool = False) -> TPUKernelBlock:
    return TPUKernelBlock(name, elems, streams, flops_per_elem, 2, elem_bytes,
                          sequential)


# KAHAN_DOT_TPU / NAIVE_DOT_TPU / KAHAN_DOT_SEQ_TPU / DOT2_TPU are
# registry-derived — see ``tpu_block_for_scheme`` and ``__getattr__``.


@dataclasses.dataclass(frozen=True)
class TPUECMResult:
    machine: str
    kernel: str
    elems: int
    t_comp_cy: float
    t_vmem_cy: float
    t_core_cy: float
    t_hbm_cy: float
    t_db_cy: float        # double-buffered: max(core, hbm)
    t_sb_cy: float        # single-buffered (paper-style): core + hbm
    perf_db_gups: float
    perf_sb_gups: float
    p_bw_gups: float      # bandwidth roofline
    n_s_equiv: float      # ceil(T_core / T_hbm) — free-ness indicator
    bound: str            # 'compute' | 'bandwidth'

    def shorthand(self) -> str:
        return (f"{{{self.t_comp_cy:.1f} (comp) | {self.t_vmem_cy:.1f} (vmem) "
                f"|| {self.t_hbm_cy:.1f} (hbm)}} cy/block")


def ecm_tpu(machine: TPUMachine, kernel: TPUKernelBlock) -> TPUECMResult:
    """Evaluate the TPU-adapted ECM model for one streaming-kernel block."""
    n = kernel.elems
    if kernel.sequential:
        # element-at-a-time: each flop chain is serialized; assume 1 elem/cy
        # per dependent add (latency-bound, like the paper's scalar variant
        # being ADD-pipe bound). ~flops_per_elem cycles per element.
        t_comp = float(n * kernel.flops_per_elem)
        t_vmem = float(n * kernel.streams * kernel.elem_bytes)  # scalar loads
    else:
        t_comp = n * kernel.flops_per_elem / machine.vpu_fp32_flops_per_cy
        t_vmem = n * kernel.streams * kernel.elem_bytes / machine.vmem_load_bytes_per_cy
    t_core = max(t_comp, t_vmem)
    bytes_hbm = n * kernel.streams * kernel.elem_bytes
    t_hbm = bytes_hbm / machine.hbm_bytes_per_cy()

    t_db = max(t_core, t_hbm)
    t_sb = t_core + t_hbm

    updates = float(n)  # one update per element pair
    perf_db = updates * machine.clock_ghz / t_db
    perf_sb = updates * machine.clock_ghz / t_sb
    p_bw = machine.hbm_gbs / (kernel.streams * kernel.elem_bytes)

    return TPUECMResult(
        machine=machine.name, kernel=kernel.name, elems=n,
        t_comp_cy=t_comp, t_vmem_cy=t_vmem, t_core_cy=t_core, t_hbm_cy=t_hbm,
        t_db_cy=t_db, t_sb_cy=t_sb,
        perf_db_gups=round(perf_db, 2), perf_sb_gups=round(perf_sb, 2),
        p_bw_gups=round(p_bw, 2),
        n_s_equiv=math.ceil(t_core / t_hbm) if t_hbm > 0 else float("inf"),
        bound="compute" if t_core > t_hbm else "bandwidth",
    )


# ===========================================================================
# Part 3: compensation-scheme registry bridge
# ===========================================================================
#
# The variant axis (naive / kahan / pairwise / dot2 / custom) is owned by
# ``repro.kernels.schemes``; this section turns a registered scheme's
# instruction mix into the model's kernel descriptions. The import is
# lazy and metadata-only (no jax arrays are created).

def _scheme(spec) -> "object":
    from repro.kernels import schemes as _schemes

    if isinstance(spec, str):
        return _schemes.get(spec)  # fail-fast: lists the registered menu
    return spec


#: bytes per element for each supported accumulate dtype — kept as a
#: plain name map so this module never imports jax for metadata.
_ELEM_BYTES = {"bfloat16": 2, "float32": 4, "float64": 8}


def elem_bytes_for_dtype(compute_dtype) -> int:
    """Element width of a supported ``Policy.compute_dtype`` (name, numpy
    dtype, numpy/jnp scalar type such as ``jnp.float32``). The machine
    axis of the precision trade space: halving/doubling the element width
    moves the bandwidth roofline while the scheme's instruction mix fixes
    the compute side."""
    # dtype instances carry .name; scalar TYPES (jnp.float32,
    # np.float64, ml_dtypes.bfloat16) carry __name__; strings are
    # themselves. No np.dtype()/jax import: 'bfloat16' only resolves
    # through numpy once ml_dtypes is registered, and this module stays
    # importable without jax.
    name = (getattr(compute_dtype, "name", None)
            or getattr(compute_dtype, "__name__", None)
            or str(compute_dtype))
    try:
        return _ELEM_BYTES[name]
    except KeyError:
        raise ValueError(
            f"compute_dtype must be one of {sorted(_ELEM_BYTES)}; "
            f"got {compute_dtype!r}") from None


def dot_kernel_for_scheme(scheme: Union[str, object], *, simd: str = "avx",
                          elem_bytes: int = 4, compute_dtype=None,
                          name: Optional[str] = None) -> DotKernel:
    """x86 kernel description for a registered scheme: the registry owns
    the adds/muls per scalar iteration, the caller picks the SIMD variant
    and element width (the machine axis the registry doesn't model) —
    either directly via ``elem_bytes`` or from a ``compute_dtype``."""
    sch = _scheme(scheme)
    mix = sch.instruction_mix
    if compute_dtype is not None:
        elem_bytes = elem_bytes_for_dtype(compute_dtype)
    return DotKernel(name or sch.name, adds=mix.adds, muls=mix.muls,
                     loads=2, flops=2, elem_bytes=elem_bytes, simd=simd)


def tpu_block_for_scheme(scheme: Union[str, object], *,
                         elems: int = 8 * 1024, elem_bytes: int = 4,
                         compute_dtype=None, streams: int = 2,
                         sequential: bool = False,
                         name: Optional[str] = None) -> TPUKernelBlock:
    """TPU VMEM-block description for a registered scheme (executed VPU
    flops per element = the scheme's instruction-mix total; element width
    from ``elem_bytes`` or a supported ``compute_dtype``)."""
    sch = _scheme(scheme)
    if compute_dtype is not None:
        elem_bytes = elem_bytes_for_dtype(compute_dtype)
    return tpu_dot_block(name or sch.name, elems,
                         sch.instruction_mix.flops, elem_bytes, streams,
                         sequential)


def registry_dot_kernels(*, simd: str = "avx", elem_bytes: int = 4,
                         compute_dtype=None) -> Dict[str, DotKernel]:
    """One x86 kernel description per *currently registered* scheme —
    newly registered schemes appear with no edits here."""
    from repro.kernels import schemes as _schemes

    return {n: dot_kernel_for_scheme(s, simd=simd, elem_bytes=elem_bytes,
                                     compute_dtype=compute_dtype)
            for n, s in _schemes.registered().items()}


def registry_tpu_blocks(*, elems: int = 8 * 1024, elem_bytes: int = 4,
                        compute_dtype=None) -> Dict[str, TPUKernelBlock]:
    """One TPU block description per *currently registered* scheme.

    Passing ``compute_dtype`` produces the table for that accumulate
    dtype (bf16 halves, f64 doubles the streamed bytes per element) —
    the model-side view of the ``Policy.compute_dtype`` axis."""
    from repro.kernels import schemes as _schemes

    return {n: tpu_block_for_scheme(s, elems=elems, elem_bytes=elem_bytes,
                                    compute_dtype=compute_dtype)
            for n, s in _schemes.registered().items()}


def ecm_tpu_for_scheme(machine: TPUMachine, scheme: Union[str, object],
                       **block_kwargs) -> TPUECMResult:
    """ECM-TPU prediction straight from a scheme name — the one-call path
    for anything in the registry (including schemes registered at runtime)."""
    return ecm_tpu(machine, tpu_block_for_scheme(scheme, **block_kwargs))


@dataclasses.dataclass(frozen=True)
class CostExpectation:
    """What the model EXPECTS a scheme's kernel body to cost, per element.

    This is the comparison record the cost auditor
    (``repro.analysis.costmodel``) checks traced jaxprs against: the
    per-element add/mul counts of the product path (``mul_update``; the
    dot kernel) and the sum path (``update``; asum and the matmul/flash
    fold sites) at their RAW traced accounting
    (``InstructionMix.traced_dot`` / ``traced_sum``), plus the streamed
    bytes per element at the resolved accumulate dtype. ``table_flops``
    is the canonical per-element flop total the ECM tables
    (``tpu_block_for_scheme``) are built from — for most schemes it
    equals ``dot_adds + dot_muls``; a deliberate canonical-vs-traced
    split (dot2's FMA accounting) is visible as a difference here and
    must carry a cost-rule exemption.
    """

    scheme: str
    dot_adds: int        # mul_update path, adds per element
    dot_muls: int        # mul_update path, muls per element
    sum_adds: int        # update path, adds per element (muls are 0)
    elem_bytes: int      # bytes per element at the accumulate dtype
    streams: int         # input streams (dot: 2, asum: 1)
    table_flops: int     # canonical flops/elem the ECM tables use

    @property
    def load_bytes_per_elem(self) -> int:
        return self.streams * self.elem_bytes

    @property
    def traced_flops(self) -> int:
        """Raw per-element VPU flops the traced dot body executes."""
        return self.dot_adds + self.dot_muls


def expected_cost(scheme: Union[str, object], *, compute_dtype=None,
                  elem_bytes: int = 4, streams: int = 2) -> CostExpectation:
    """The model-side cost expectation for one registered scheme.

    The single place the cost auditor (and anything else comparing traced
    kernels against the model) asks "what should this body cost?" —
    counts come from the scheme's ``instruction_mix`` declaration, bytes
    from ``elem_bytes_for_dtype``.
    """
    sch = _scheme(scheme)
    if compute_dtype is not None:
        elem_bytes = elem_bytes_for_dtype(compute_dtype)
    dot_adds, dot_muls = sch.instruction_mix.traced_dot
    sum_adds, _ = sch.instruction_mix.traced_sum
    return CostExpectation(
        scheme=sch.name, dot_adds=dot_adds, dot_muls=dot_muls,
        sum_adds=sum_adds, elem_bytes=elem_bytes, streams=streams,
        table_flops=sch.instruction_mix.flops)


# Named kernel constants, derived lazily (PEP 562 module __getattr__) from
# the registry so importing repro.core.ecm does not eagerly import the
# kernels package. Resolved values are cached in module globals.
_REGISTRY_CONSTANTS = {
    # paper Table 1/2 x86 variants
    "NAIVE_SP": lambda: dot_kernel_for_scheme("naive", simd="avx",
                                              name="naive"),
    "KAHAN_SCALAR_SP": lambda: dot_kernel_for_scheme(
        "kahan", simd="scalar", name="kahan-scalar"),
    "KAHAN_SSE_SP": lambda: dot_kernel_for_scheme("kahan", simd="sse",
                                                  name="kahan-sse"),
    "KAHAN_AVX_SP": lambda: dot_kernel_for_scheme("kahan", simd="avx",
                                                  name="kahan-avx"),
    "KAHAN_SCALAR_DP": lambda: dot_kernel_for_scheme(
        "kahan", simd="scalar", elem_bytes=8, name="kahan-scalar-dp"),
    "KAHAN_AVX_DP": lambda: dot_kernel_for_scheme(
        "kahan", simd="avx", elem_bytes=8, name="kahan-avx-dp"),
    # TPU adaptation blocks
    "KAHAN_DOT_TPU": lambda: tpu_block_for_scheme("kahan",
                                                  name="kahan-dot"),
    "NAIVE_DOT_TPU": lambda: tpu_block_for_scheme("naive",
                                                  name="naive-dot"),
    "KAHAN_DOT_SEQ_TPU": lambda: tpu_block_for_scheme(
        "kahan", sequential=True, name="kahan-dot-seq"),
    "DOT2_TPU": lambda: tpu_block_for_scheme("dot2", name="dot2"),
}


def __getattr__(name: str):
    try:
        builder = _REGISTRY_CONSTANTS[name]
    except KeyError:
        raise AttributeError(
            f"module {__name__!r} has no attribute {name!r}") from None
    value = builder()
    globals()[name] = value  # cache: derive once per process
    return value
