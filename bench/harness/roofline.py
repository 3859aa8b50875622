"""Roofline share: the least time the chip could take for the work —
the larger of FLOPs over peak FLOP/s and bytes over peak bandwidth —
over the time the work took, in percent."""
from __future__ import annotations

from typing import Dict, Optional, Tuple



def share(flops: float, nbytes: float, seconds: float,
          pk: Dict) -> Optional[Tuple[float, str]]:
    """(percent, "compute" | "memory"), or None when no time was spent."""
    if seconds <= 0:
        return None
    t_c = flops / float(pk["bf16_flops"])
    t_m = nbytes / float(pk["hbm_bytes_per_s"])
    bound = "compute" if t_c >= t_m else "memory"
    return 100.0 * max(t_c, t_m) / seconds, bound


def program_spans(ctx, program: str):
    """Executions of the decode (``DECODE``) or chunk (``CHUNK``) step
    on the first device, sorted."""
    return ctx.trace["devices"][0].executions(program)


def kernel_seconds(ctx, kernel: str, program: str) -> Tuple[float, int]:
    """Summed device seconds and calls of ``kernel`` inside ``program``'s
    executions on the first device."""
    dev = ctx.trace["devices"][0]
    ns, calls = dev.kernel_time(kernel, program_spans(ctx, program))
    return ns * 1e-9, calls


# a step program is known by the kernel only it runs
DECODE = "paged_attention"
CHUNK = "paged_prefill"
