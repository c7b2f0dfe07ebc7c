"""Pallas TPU kernels for the paper's compute hot-spots.

The paper's contribution IS a kernel (the Kahan-compensated dot), so this
package carries the core artifacts:

  schemes.py      — the compensation-scheme registry (naive / kahan /
                    pairwise / dot2 + runtime registration) and the
                    frozen Policy API (``use_policy`` context default).
                    The variant axis of the whole repo lives here.
  kahan_dot.py    — compensated dot: ONE parameterized kernel body that
                    traces ``scheme.mul_update`` from the registry
                    (the paper's Fig. 1 kernels with VPU-lane partial
                    accumulators and the unroll knob).
  kahan_sum.py    — single-stream variant (loss/metric accumulation).
  kahan_matmul.py — MXU matmul with scheme-compensated inter-K-tile
                    accumulation (the TPU analog of the paper's
                    FMA-as-ADD trick). Emits the raw (s, c) output-tile
                    grids; single and batched (batch, mb, nb, ks) grids.
  flash_attention.py — fused flash attention with scheme-compensated
                    online-softmax accumulators (scores never leave VMEM).
                    Emits the raw (l, l_c, acc, acc_c) grids; the shared
                    flash_block_update body is traced by the kernel AND
                    the ref oracle (bitwise by construction).
  engine.py       — the unified CompensatedReduction engine: one (s, c)
                    accumulator contract (total = s + c, merge = two-sum
                    tree), one padding/promotion/blocking/compute-dtype
                    policy (Policy.compute_dtype: fp32 | f64 | bf16
                    accumulate), batched grids with custom_vmap rules,
                    and a custom-VJP matmul whose backward reuses the
                    compensated kernel.
  ops.py          — jit'd public wrappers (interpret on CPU, Mosaic on TPU).
  ref.py          — registry-generic pure-jnp oracles tracing the same
                    scheme callables (bitwise-identical rounding).
"""

from repro.kernels import engine  # noqa: F401
from repro.kernels import ops  # noqa: F401
from repro.kernels import schemes  # noqa: F401
from repro.kernels.schemes import (  # noqa: F401
    CompensationScheme,
    Policy,
    current_policy,
    use_policy,
)
