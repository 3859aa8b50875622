"""Operations and bytes each kernel call needs, from the configuration's
shapes and the quantization recipe — what the model needs, whatever
implements it: rows are the requests decoding, not padded slots; KV
bytes are the live context; packed weights are counted at the recipe's
size (1 bit per binary weight, 4 per salient one, one 2-byte value per
scale) with no lane padding."""
from __future__ import annotations

from typing import Iterable, Tuple

from harness.spec import Dims, salient_channels

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


def decode_matmuls(dm: Dims, rows: int, ratio: float,
                   multiple: int) -> Tuple[float, float]:
    """All mixed_matmul calls of one decode step (every layer)."""
    f = b = 0.0
    for k, n in dm.projections().values():
        ff, bb = mixed_matmul(rows, k, n, ratio, multiple)
        f, b = f + ff, b + bb
    return f * dm.layers, b * dm.layers


def paged_attention(dm: Dims, contexts: Iterable[int]) -> Tuple[float, float]:
    """All paged_attention calls of one decode step: one query per row
    against its live context, every layer."""
    kv_tok = 2 * dm.hkv * dm.dh * ACT
    f = b = 0.0
    for ctx in contexts:
        f += 4.0 * dm.hq * dm.dh * ctx
        b += ctx * kv_tok + 2 * ACT * dm.hq * dm.dh
    return f * dm.layers, b * dm.layers


def paged_prefill(dm: Dims, start: int, length: int) -> Tuple[float, float]:
    """All paged_prefill calls of one chunk (every layer): ``length``
    causal queries at positions ``start..``, reading ``start`` tokens of
    context and writing the chunk's K/V."""
    kv_tok = 2 * dm.hkv * dm.dh * ACT
    keys = length * start + length * (length + 1) / 2
    f = 4.0 * dm.hq * dm.dh * keys
    b = (start + 2 * length) * kv_tok + 2 * ACT * length * dm.hq * dm.dh
    return f * dm.layers, b * dm.layers


def decode_token_flops(dm: Dims, context: int) -> float:
    """Model FLOPs of one decoded token: every projection and the head
    at 2 per weight, plus attention over its context."""
    lin = sum(k * n for k, n in dm.projections().values()) * dm.layers
    head = dm.d * dm.vocab
    return 2.0 * (lin + head) + 4.0 * dm.hq * dm.dh * context * dm.layers
