"""Operations and bytes a packed projection needs, from its shape and the
quantization recipe — what the model needs, whatever implements it.
Each model family (``bench/families``) counts its kernels' work from
these and its own shapes, the same way: rows are the requests decoding,
not padded slots; KV bytes are the live context; packed weights are
counted at the recipe's size (1 bit per binary weight, 4 per salient
one, one 2-byte value per scale) with no lane padding."""
from __future__ import annotations

from typing import Tuple

from harness.spec import salient_channels

ACT = 2          # bf16 activation, K/V and output bytes


def matmul_weight_bytes(k: int, n: int, ratio: float, multiple: int) -> float:
    """Packed bytes of one (K, N) PTQ1.61 weight: int4 salient rows, sign
    bits for the rest, an int4 scale and zero per salient channel, one
    output scale per column and one input scale per binary channel."""
    k_s = salient_channels(k, ratio, multiple)
    k_b = k - k_s
    return k_s * n / 2 + k_b * n / 8 + ACT * (2 * k_s + n + k_b)


def mixed_matmul(rows: int, k: int, n: int, ratio: float,
                 multiple: int) -> Tuple[float, float]:
    """(FLOPs, bytes) of one mixed_matmul call over ``rows`` live rows."""
    return (2.0 * rows * k * n,
            matmul_weight_bytes(k, n, ratio, multiple)
            + ACT * rows * (k + n))
