"""Operations and bytes that a kernel's algorithm needs for one call,
computed from its shapes. A kernel's roofline share is the least time the
chip could take for these (the larger of operations over peak FLOP/s and
bytes over peak bytes/s) over the device time the trace shows."""

from __future__ import annotations

import re

#: how the trace and the compiled step name the attention kernels' custom
#: calls, two generations of them:
#: - the stock flash kernels (`jax.experimental.pallas.ops.tpu.flash_attention`,
#:   until PR 26): the forward one after the jitted function
#:   (`jvp_jit_flash_attention__.12`), the backward ones after their name
#:   scopes (`flash_mha_bwd_dkv_...`, `flash_mha_bwd_dq_...`) (my chip run,
#:   PR 22; the recorded fixtures hold them);
#: - the splash kernels (`...ops.tpu.splash_attention`, since PR 26), which
#:   that package's `get_kernel_name` names
#:   `splash_{mha,mqa}_{fwd,dkv,dq}[_segmented]_{residuals,no_residuals}`:
#:   `splash_mha_fwd_residuals.12` and `splash_mha_dkv_no_residuals.3` in the
#:   cells (my chip run, PR 32).
FLASH_ATTENTION_OPS = re.compile(
    r"flash_attention|flash_mha|splash_m[hq]a_(fwd|dkv|dq)")


def _causal_product(batch: int, heads: int, seq: int, head_dim: int) -> float:
    """A full `[seq, seq] x head_dim` product is `2*seq*seq*head_dim`
    operations for each head of each sequence, and the causal mask needs
    half of it."""
    return 2.0 * seq * seq * head_dim * batch * heads / 2


def flash_attention_causal(batch: int, heads: int, seq: int, head_dim: int,
                           bytes_per_element: int = 2) -> dict:
    """Causal flash attention, forward and backward, as
    `jax.experimental.pallas.ops.tpu.flash_attention` splits it into three
    kernels (and as the splash kernels do without `use_fused_bwd_kernel`).

    - forward: QK^T and PV, 2 products. Reads q, k, v, writes o (and two
      float32 rows per query, which are left out);
    - dK/dV: QK^T again (the scores are not stored: that is the algorithm),
      dV = P^T dO, dP = dO V^T, dK = dS^T Q, 4 products. Reads q, k, v, o,
      dO, writes dK, dV;
    - dQ: QK^T again, dP, dQ = dS K, 3 products. Reads q, k, v, o, dO,
      writes dQ.
    """
    product = _causal_product(batch, heads, seq, head_dim)
    tensor = float(batch * heads * seq * head_dim * bytes_per_element)
    return {"flops": 9 * product, "bytes": (4 + 7 + 6) * tensor}


def fused_backward_attention_causal(batch: int, heads: int, seq: int,
                                    head_dim: int,
                                    bytes_per_element: int = 2) -> dict:
    """Causal attention as the splash kernels compute it with
    `use_fused_bwd_kernel=True`: two kernels, and the scores recomputed
    once in the backward pass, not twice.

    - forward: QK^T and PV, 2 products. Reads q, k, v, writes o and one
      float32 per query row (the log-sum-exp);
    - backward: QK^T again, dP = dO V^T, dV = P^T dO, dK = dS^T Q,
      dQ = dS K, 5 products. Reads q, k, v, dO and two float32 per query
      row (the log-sum-exp, and the row sums of dO * O, which the step
      computes outside the kernel), writes dQ, dK, dV.

    The same work whatever block sizes implement it: a partial dQ per block
    of keys, or statistics kept 128 lanes wide, are the implementation's.
    """
    product = _causal_product(batch, heads, seq, head_dim)
    tensor = float(batch * heads * seq * head_dim * bytes_per_element)
    row = float(batch * heads * seq * 4)
    return {"flops": 7 * product, "bytes": (4 + 7) * tensor + (1 + 2) * row}


def attention_causal(names, batch: int, heads: int, seq: int,
                     head_dim: int, bytes_per_element: int = 2):
    """The cost of one layer's causal attention, forward and backward, by
    the kernels whose `names` (of a trace's events or of a compiled step's
    custom calls) are found: the fused-backward count where they are splash
    kernels with no `dq` kernel among them, the three-kernel count for the
    stock flash kernels and for a splash backward in two kernels; None
    where no name is an attention kernel's."""
    found = [m for m in map(FLASH_ATTENTION_OPS.search, names) if m]
    if not found:
        return None
    splash = {m.group(1) for m in found} - {None}
    fused = bool(splash) and "dq" not in splash
    cost = fused_backward_attention_causal if fused else flash_attention_causal
    return cost(batch, heads, seq, head_dim, bytes_per_element)


def min_seconds(cost: dict, peaks: dict) -> tuple:
    """`(seconds, bound)`: the roofline's least time and which side sets
    it, `compute` or `memory`."""
    compute = cost["flops"] / peaks["bf16_flops_per_s"]
    memory = cost["bytes"] / peaks["hbm_bytes_per_s"]
    return (compute, "compute") if compute >= memory else (memory, "memory")
