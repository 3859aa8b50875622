"""The quantization recipe's arithmetic that every model family, its
weights, its work counts and its plain reference share."""
from __future__ import annotations


def salient_channels(k: int, ratio: float, multiple: int) -> int:
    """PTQ1.61's salient input channels for K inputs: ratio·K rounded to
    the multiple, at least one multiple and leaving at least one."""
    k_s = int(round(ratio * k / multiple)) * multiple
    return max(multiple, min(k_s, k - multiple))
