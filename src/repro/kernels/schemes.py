"""First-class compensation-scheme registry + the ``Policy`` API.

The paper's whole method is *comparing variants* of one reduction loop —
naive vs compensated, across unroll factors — through one model. This
module makes that variant axis first-class: a ``CompensationScheme``
bundles everything one variant needs, and every layer of the repo
resolves variants through the registry instead of its own ``if mode ==``
chain:

* ``update`` / ``mul_update`` / ``finalize`` — the pure-jnp accumulator
  callables. The Pallas kernel bodies (``kahan_dot`` / ``kahan_sum`` /
  ``kahan_matmul`` / ``flash_attention``) and the jnp oracles
  (``kernels.ref``) call the SAME callables, so kernel-vs-oracle bitwise
  equality holds *by construction* for every scheme, including ones
  registered after import.
* ``error_bound`` — an a-priori relative-error bound for a length-``n``
  dot with condition number ``cond`` (the accuracy-benchmark column).
* ``instruction_mix`` — adds/muls per scalar iteration, consumed by
  ``repro.core.ecm`` to derive its kernel tables (no parallel hardcoded
  variant list in the model).

Built-ins: ``naive``, ``kahan`` (paper Fig. 1b), ``pairwise`` (two-level
cascaded accumulation, the streaming form of pairwise summation), and
``dot2`` (TwoProd + TwoSum per Ogita–Rump–Oishi).

``Policy`` is the frozen call-site configuration (scheme, unroll, matmul
blocks, interpret, compute dtype). ``use_policy(...)`` installs a
context-local default so model / serving / benchmark layers resolve one
policy object instead of threading ``mode=``/``unroll=`` kwargs through
every call:

    with use_policy(scheme="dot2", unroll=4):
        ops.dot(a, b)            # dot2, unroll 4
        ops.batched_asum(x)      # same policy

Registering a new scheme makes it usable through ``ops.dot`` /
``ops.asum`` / ``batched_*`` / ``sharded_*`` / ``matmul`` /
``flash_attention``, visible to the ECM model, and swept by the accuracy
benchmarks, with no edits outside the registration call:

    schemes.register(CompensationScheme(name="mine", ...))
    ops.dot(a, b, scheme="mine")
"""

from __future__ import annotations

import contextlib
import contextvars
import dataclasses
import math
from typing import Any, Callable, Dict, Optional, Tuple, Union

import jax
import jax.numpy as jnp

from repro.core import kahan as K

Array = jax.Array
# (s, c, term, step) -> (s, c): fold one already-formed term into the
# accumulator pair. ``step`` is the sequential grid-step index (int32;
# pl.program_id in kernels, the scan counter in oracles) for schemes
# whose update depends on position (pairwise's cascade fold).
UpdateFn = Callable[[Array, Array, Array, Array], Tuple[Array, Array]]
# (s, c, a, b, step) -> (s, c): fused product-accumulate, for schemes
# where the product's rounding error matters (dot2's TwoProd).
MulUpdateFn = Callable[[Array, Array, Array, Array, Array], Tuple[Array, Array]]

#: fp32 unit roundoff, the default for ``error_bound`` (kernels compute fp32
#: unless the Policy selects another accumulate dtype).
EPS32 = 2.0 ** -24
#: f64 unit roundoff (``compute_dtype="float64"`` accumulate path).
EPS64 = 2.0 ** -53
#: bf16 unit roundoff (``compute_dtype="bfloat16"`` accumulate path).
EPS_BF16 = 2.0 ** -8

#: accumulate dtypes the kernel bodies support; anything else fails fast
#: at the Policy / engine boundary, never inside a trace.
SUPPORTED_COMPUTE_DTYPES = ("bfloat16", "float32", "float64")

_EPS_BY_NAME = {"bfloat16": EPS_BF16, "float32": EPS32, "float64": EPS64}


def unit_roundoff(compute_dtype) -> float:
    """Unit roundoff of a supported accumulate dtype (for ``error_bound``)."""
    return _EPS_BY_NAME[resolve_compute_dtype(compute_dtype).name]


def resolve_compute_dtype(spec):
    """Normalize/validate an accumulate-dtype spec -> ``jnp.dtype``.

    None resolves the ambient policy's ``compute_dtype``. Unsupported
    dtypes FAIL FAST with the supported menu; float64 additionally
    requires x64 to be enabled (otherwise jax silently truncates every
    array to fp32 and the "f64 accumulate" would be a lie), and is
    refused on a TPU backend, whose Mosaic kernels have no 64-bit float
    type (XLA would reject the program only after tracing it).
    """
    if spec is None:
        return current_policy().compute_dtype  # already validated by Policy
    dt = jnp.dtype(spec)
    if dt.name not in SUPPORTED_COMPUTE_DTYPES:
        raise ValueError(
            f"compute_dtype must be one of {list(SUPPORTED_COMPUTE_DTYPES)}; "
            f"got {dt.name!r}")
    if dt == jnp.dtype("float64"):
        if jax.default_backend() == "tpu":
            raise ValueError(
                "compute_dtype='float64' cannot run on a TPU backend "
                "(no 64-bit float in Mosaic kernels); choose one of "
                "float32 | bfloat16")
        if not jax.config.jax_enable_x64:
            raise ValueError(
                "compute_dtype='float64' requires x64 mode (enable it with "
                "jax.config.update('jax_enable_x64', True) or the "
                "jax.enable_x64(True) context manager)")
    return dt

#: pairwise cascade interval: the primary accumulator folds into the
#: secondary every FOLD sequential steps, bounding per-cell error growth
#: to O(FOLD + steps/FOLD) instead of O(steps).
PAIRWISE_FOLD = 32


@dataclasses.dataclass(frozen=True)
class InstructionMix:
    """Adds/muls executed per scalar iteration of the scheme's dot loop
    (the paper's accounting unit; useful flops per update is always 2).

    ``adds``/``muls`` are the CANONICAL counts — the figures the paper's
    accounting (and the ECM tables in ``repro.core.ecm``) use. When the
    traced kernel body executes a different raw VPU-op count (e.g. a
    split-based TwoProd where the canonical accounting assumes FMA), the
    ``traced_*`` overrides declare what the jaxpr actually contains so
    the cost auditor (``repro.analysis.costmodel``) can verify it; left
    ``None`` they default to the canonical counts, which is correct for
    any scheme whose jnp update IS its accounting.

    * ``traced_adds`` / ``traced_muls`` — per-element add/mul count of the
      product path (``mul_update``; the dot kernel body).
    * ``traced_sum_adds`` — per-element add count of the sum path
      (``update``; the asum kernel body and matmul/flash fold sites),
      which by convention has zero muls.
    """

    adds: int
    muls: int
    traced_adds: Optional[int] = None
    traced_muls: Optional[int] = None
    traced_sum_adds: Optional[int] = None

    @property
    def flops(self) -> int:
        return self.adds + self.muls

    @property
    def traced_dot(self) -> Tuple[int, int]:
        """(adds, muls) the traced ``mul_update`` body executes per element."""
        return (self.adds if self.traced_adds is None else self.traced_adds,
                self.muls if self.traced_muls is None else self.traced_muls)

    @property
    def traced_sum(self) -> Tuple[int, int]:
        """(adds, muls) the traced ``update`` (sum path) executes per element."""
        return (self.adds if self.traced_sum_adds is None
                else self.traced_sum_adds, 0)


#: keys accepted when coercing a mapping into an ``InstructionMix`` at
#: ``register()`` time (the fail-fast menu in the error message).
_MIX_KEYS = ("adds", "muls", "traced_adds", "traced_muls", "traced_sum_adds")
_MIX_REQUIRED = ("adds", "muls")


def validate_instruction_mix(mix, *, scheme_name: str = "?") -> InstructionMix:
    """Coerce/validate an ``instruction_mix`` declaration, FAIL FAST.

    Accepts an ``InstructionMix`` or a mapping with keys from
    ``{adds, muls, traced_adds, traced_muls, traced_sum_adds}``
    (``adds``/``muls`` required). Every count must be a non-negative int.
    Raised at ``schemes.register()`` / built-in construction time so a
    malformed declaration never surfaces later inside
    ``core/ecm.py`` table construction or the cost auditor.
    """
    if isinstance(mix, InstructionMix):
        fields = {k: getattr(mix, k) for k in _MIX_KEYS}
    elif isinstance(mix, dict):
        unknown = sorted(set(mix) - set(_MIX_KEYS))
        missing = sorted(set(_MIX_REQUIRED) - set(mix))
        if unknown or missing:
            raise ValueError(
                f"scheme {scheme_name!r}: instruction_mix keys must come "
                f"from {list(_MIX_KEYS)} with {list(_MIX_REQUIRED)} "
                f"required; unknown={unknown} missing={missing}")
        fields = {k: mix.get(k) for k in _MIX_KEYS}
    else:
        raise TypeError(
            f"scheme {scheme_name!r}: instruction_mix must be an "
            f"InstructionMix or a mapping with keys from {list(_MIX_KEYS)}; "
            f"got {type(mix).__name__}")
    for key, val in fields.items():
        required = key in _MIX_REQUIRED
        if val is None and not required:
            continue
        if not isinstance(val, int) or isinstance(val, bool) or val < 0:
            raise ValueError(
                f"scheme {scheme_name!r}: instruction_mix.{key} must be a "
                f"non-negative int{'' if required else ' or None'}; "
                f"got {val!r}")
    return mix if isinstance(mix, InstructionMix) else InstructionMix(**fields)


@dataclasses.dataclass(frozen=True)
class CompensationScheme:
    """One variant of the compensated reduction loop.

    All state is the engine's ``(s, c)`` accumulator pair with
    ``finalize(s, c) = s + c`` (the shared convention — merges, batching,
    and sharding all assume it). ``update``/``mul_update`` must be pure
    jnp so the same callable traces inside Pallas kernel bodies and
    ``lax.scan`` oracles, which is what makes kernel-vs-oracle equality
    bitwise for free.
    """

    name: str
    update: UpdateFn
    instruction_mix: InstructionMix
    # (n, cond, eps) -> a-priori relative-error bound for a length-n dot.
    error_bound: Callable[..., float]
    mul_update: Optional[MulUpdateFn] = None
    description: str = ""

    def __post_init__(self):
        # fail fast on malformed instruction_mix declarations (mapping
        # coerced, counts type/range-checked) — a bad declaration should
        # die here, not later inside ecm table construction or the cost
        # auditor.
        object.__setattr__(
            self, "instruction_mix",
            validate_instruction_mix(
                self.instruction_mix, scheme_name=self.name))
        if self.mul_update is None:
            upd = self.update
            object.__setattr__(
                self, "mul_update",
                lambda s, c, a, b, step, _u=upd: _u(s, c, a * b, step))

    @staticmethod
    def finalize(s: Array, c: Array) -> Array:
        """Collapse the pair to the best single estimate (the one
        convention every merge in the repo shares)."""
        return s + c


# ---------------------------------------------------------------------------
# Built-in schemes
# ---------------------------------------------------------------------------

def _naive_update(s, c, x, step):
    del step
    return s + x, c


def _kahan_update(s, c, x, step):
    del step
    return K.kahan_step(s, c, x)


def _pairwise_update(s, c, x, step):
    """Two-level cascade (streaming pairwise): accumulate into ``s``,
    fold ``s`` into ``c`` every PAIRWISE_FOLD steps. The fold and the
    final ``s + c`` are the only cross-level adds, so per-cell error
    grows O(FOLD + steps/FOLD); the lane grid and the engine's two-sum
    merge tree supply the rest of the pairwise structure."""
    s = s + x
    fold = (step % PAIRWISE_FOLD) == (PAIRWISE_FOLD - 1)
    c = jnp.where(fold, c + s, c)
    s = jnp.where(fold, jnp.zeros_like(s), s)
    return s, c


def _dot2_update(s, c, x, step):
    """TwoSum accumulation (Sum2 of Ogita–Rump–Oishi): the error of every
    add is captured exactly and parked in ``c``."""
    del step
    s, e = K.two_sum(s, x)
    return s, c + e


def _dot2_mul_update(s, c, a, b, step):
    """TwoProd + TwoSum (Dot2): both the product and the accumulation
    rounding errors are captured exactly (Veltkamp-split TwoProd — no
    fused-multiply-add assumption on the VPU)."""
    del step
    p, ep = K.two_prod(a, b)
    s, es = K.two_sum(s, p)
    return s, c + (ep + es)


def _naive_bound(n: int, cond: float, eps: float = EPS32) -> float:
    # gamma_{n-1} * cond / 2: recursive summation of rounded products.
    return 0.5 * n * eps * cond


def _kahan_bound(n: int, cond: float, eps: float = EPS32) -> float:
    # compensated sum kills the O(n) term; the rounded products leave the
    # eps*cond/2 floor (Kahan compensates the SUM, not the products).
    return (eps + 2.0 * n * eps * eps) * cond


def _pairwise_bound(n: int, cond: float, eps: float = EPS32) -> float:
    # two-level cascade: effective chain length FOLD + n/FOLD (coarse —
    # the kernel's lane grid shortens real chains much further).
    eff = PAIRWISE_FOLD + math.ceil(n / PAIRWISE_FOLD)
    return 0.5 * eff * eps * cond


def _dot2_bound(n: int, cond: float, eps: float = EPS32) -> float:
    # twice-working-precision: eps + gamma^2 * cond (Ogita et al. Prop.
    # 5.4 shape) — the cond term only surfaces past cond ~ 1/eps.
    g = 2.0 * n * eps
    return eps + 0.5 * g * g * cond


NAIVE = CompensationScheme(
    name="naive",
    update=_naive_update,
    instruction_mix=InstructionMix(adds=1, muls=1),
    error_bound=_naive_bound,
    description="s += a*b (paper Fig. 1a); error grows O(n)",
)

KAHAN = CompensationScheme(
    name="kahan",
    update=_kahan_update,
    instruction_mix=InstructionMix(adds=4, muls=1),
    error_bound=_kahan_bound,
    description="compensated accumulation (paper Fig. 1b); O(eps) sum error",
)

PAIRWISE = CompensationScheme(
    name="pairwise",
    update=_pairwise_update,
    instruction_mix=InstructionMix(adds=2, muls=1),
    error_bound=_pairwise_bound,
    description="two-level cascaded accumulation (streaming pairwise)",
)

DOT2 = CompensationScheme(
    name="dot2",
    update=_dot2_update,
    mul_update=_dot2_mul_update,
    # canonical FMA-based Ogita accounting (17 flops/elem) — the figure
    # the follow-up studies quote and the pre-existing ECM table used;
    # the split-based fp32 kernel executes more raw VPU ops, but the
    # model keeps the canonical count for cross-paper comparability.
    # The traced_* overrides declare the raw counts the Veltkamp-split
    # kernel body actually executes (verified by the cost auditor):
    # TwoProd+TwoSum = 18 adds + 7 muls per element on the product path,
    # TwoSum alone = 7 adds on the sum path.
    instruction_mix=InstructionMix(adds=13, muls=4,
                                   traced_adds=18, traced_muls=7,
                                   traced_sum_adds=7),
    error_bound=_dot2_bound,
    description="TwoProd+TwoSum (Ogita-Rump-Oishi Dot2); twice-precision",
)


# ---------------------------------------------------------------------------
# Registry
# ---------------------------------------------------------------------------

_REGISTRY: Dict[str, CompensationScheme] = {}


def register(scheme: CompensationScheme, *, override: bool = False) -> CompensationScheme:
    """Add a scheme to the registry (returns it, for decorator-ish use).

    After registration the scheme works through every entry point —
    ``ops.dot``/``asum``/``matmul``, batched and sharded variants,
    ``flash_attention`` — and appears in the ECM tables and the
    registry-driven benchmark sweeps. ``override=True`` replaces an
    existing name (note: jit caches key on the scheme *object*, so a
    replaced scheme never aliases stale compiled code).
    """
    if not isinstance(scheme, CompensationScheme):
        raise TypeError(f"expected CompensationScheme, got {type(scheme)!r}")
    # re-validate at the registry boundary: __post_init__ covers normal
    # construction, but dataclasses.replace / object.__setattr__ edits
    # between construction and registration must not slip a malformed
    # mix into the ECM tables.
    validate_instruction_mix(scheme.instruction_mix, scheme_name=scheme.name)
    if scheme.name in _REGISTRY and not override:
        raise ValueError(
            f"scheme {scheme.name!r} already registered "
            f"(pass override=True to replace)")
    _REGISTRY[scheme.name] = scheme
    return scheme


def unregister(name: str) -> None:
    """Remove a scheme (tests / plugin teardown). Built-ins included —
    there is nothing special about them beyond being pre-registered."""
    _REGISTRY.pop(name, None)


def names() -> Tuple[str, ...]:
    """Registered scheme names, registration order."""
    return tuple(_REGISTRY)


def registered() -> Dict[str, CompensationScheme]:
    """Snapshot of the registry (copy — safe to iterate while registering)."""
    return dict(_REGISTRY)


def get(name: str) -> CompensationScheme:
    """Look up a scheme by name; unknown names FAIL FAST with the full
    menu (the API-boundary validation — kernels never see bad names)."""
    try:
        return _REGISTRY[name]
    except KeyError:
        raise ValueError(
            f"unknown compensation scheme {name!r}; registered schemes: "
            f"{sorted(_REGISTRY)}") from None


for _s in (NAIVE, KAHAN, PAIRWISE, DOT2):
    register(_s)


# ---------------------------------------------------------------------------
# Policy
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class Policy:
    """Frozen per-call-site configuration for the compensated reductions.

    scheme         registered scheme name or a CompensationScheme object
    unroll         accumulator-group count U; 1-D kernel block is (8*U, 128)
    blocks         matmul (block_m, block_n, block_k) tile sizes
    interpret      None -> engine.resolve_interpret (Mosaic only on TPU)
    compute_dtype  accumulate dtype for every kernel body and oracle:
                   "float32" (default) | "float64" (needs x64 enabled) |
                   "bfloat16" (the bf16-accumulate trade-space axis).
                   Anything else fails fast at construction.

    Resolution: explicit kwargs at a call site > the call's Policy >
    the ambient ``use_policy`` default.
    """

    scheme: Union[str, CompensationScheme] = "kahan"
    unroll: int = 8
    blocks: Tuple[int, int, int] = (256, 256, 512)
    interpret: Optional[bool] = None
    compute_dtype: Any = jnp.float32

    def __post_init__(self):
        # fail fast at the boundary: bad scheme names and unsupported
        # compute dtypes never reach a kernel trace.
        object.__setattr__(self, "scheme", resolve_scheme(self.scheme))
        object.__setattr__(
            self, "compute_dtype", resolve_compute_dtype(
                jnp.float32 if self.compute_dtype is None
                else self.compute_dtype))
        if self.unroll < 1:
            raise ValueError(f"Policy.unroll must be >= 1, got {self.unroll}")


def resolve_scheme(spec: Union[str, CompensationScheme, None]) -> CompensationScheme:
    """str -> registry lookup (fail-fast); scheme -> itself; None -> the
    ambient policy's scheme."""
    if spec is None:
        return current_policy().scheme  # already resolved by Policy
    if isinstance(spec, CompensationScheme):
        return spec
    if isinstance(spec, str):
        return get(spec)
    raise TypeError(
        f"scheme must be a name, CompensationScheme, or None; got {spec!r}")


_POLICY: contextvars.ContextVar[Policy] = contextvars.ContextVar("repro_policy")
_DEFAULT_POLICY = Policy()


def current_policy() -> Policy:
    """The ambient Policy (innermost ``use_policy``, else the default)."""
    return _POLICY.get(_DEFAULT_POLICY)


@contextlib.contextmanager
def use_policy(policy: Optional[Policy] = None, /, **overrides):
    """Install a Policy as the context default.

    Either pass a ``Policy`` or field overrides applied on top of the
    current ambient policy::

        with use_policy(scheme="dot2", unroll=4):
            ops.dot(a, b)                # dot2, unroll 4

    Context-local (contextvars), so nested/with-threads usage behaves.
    """
    if policy is None:
        policy = dataclasses.replace(current_policy(), **overrides)
    elif overrides:
        raise TypeError("pass a Policy or field overrides, not both")
    elif not isinstance(policy, Policy):
        raise TypeError(f"expected Policy, got {type(policy)!r}")
    token = _POLICY.set(policy)
    try:
        yield policy
    finally:
        _POLICY.reset(token)


# ---------------------------------------------------------------------------
# Migration note: the legacy ``mode=`` alias is GONE
# ---------------------------------------------------------------------------
# Through PR 3 every entry point accepted ``mode: str`` as a deprecated
# alias for ``scheme=`` (registry-resolved, bitwise-identical results,
# DeprecationWarning). The scripts/ci.sh gate kept repro.* internals
# clean for two releases, so the alias has been REMOVED end-to-end:
# ``ops.dot(a, b, mode="kahan", unroll=4)`` is now a TypeError — write
# ``ops.dot(a, b, scheme="kahan", unroll=4)``, or set the policy once::
#
#     with use_policy(scheme="kahan", unroll=4):
#         ops.dot(a, b)
#
# A grep gate in scripts/ci.sh fails CI if ``mode=`` reappears anywhere
# in src/repro/.
