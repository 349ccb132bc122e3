"""Operations and bytes that a kernel's algorithm needs for one call,
computed from its shapes. A kernel's roofline share is the least time the
chip could take for these (the larger of operations over peak FLOP/s and
bytes over peak bytes/s) over the device time the trace shows."""

from __future__ import annotations

import re

#: how the trace and the compiled step name the flash kernels' custom calls:
#: the forward one after the jitted function (`jvp_jit_flash_attention__.12`),
#: the backward ones after their name scopes (`flash_mha_bwd_dkv_...`,
#: `flash_mha_bwd_dq_...`) (my chip run, PR 22)
FLASH_ATTENTION_OPS = re.compile(r"flash_attention|flash_mha")


def flash_attention_causal(batch: int, heads: int, seq: int, head_dim: int,
                           bytes_per_element: int = 2) -> dict:
    """Causal flash attention, forward and backward, as
    `jax.experimental.pallas.ops.tpu.flash_attention` splits it into three
    kernels. A full `[seq, seq] x head_dim` product is `2*seq*seq*head_dim`
    operations for each head of each sequence, and the causal mask needs
    half of it.

    - forward: QK^T and PV, 2 products. Reads q, k, v, writes o (and two
      float32 rows per query, which are left out);
    - dK/dV: QK^T again (the scores are not stored: that is the algorithm),
      dV = P^T dO, dP = dO V^T, dK = dS^T Q, 4 products. Reads q, k, v, o,
      dO, writes dK, dV;
    - dQ: QK^T again, dP, dQ = dS K, 3 products. Reads q, k, v, o, dO,
      writes dQ.
    """
    product = 2.0 * seq * seq * head_dim * batch * heads / 2
    tensor = float(batch * heads * seq * head_dim * bytes_per_element)
    return {"flops": 9 * product, "bytes": (4 + 7 + 6) * tensor}


def min_seconds(cost: dict, peaks: dict) -> tuple:
    """`(seconds, bound)`: the roofline's least time and which side sets
    it, `compute` or `memory`."""
    compute = cost["flops"] / peaks["bf16_flops_per_s"]
    memory = cost["bytes"] / peaks["hbm_bytes_per_s"]
    return (compute, "compute") if compute >= memory else (memory, "memory")
