"""Sizes of a configuration file, read the same way by the program's
adapter, the work functions and the plain reference."""
from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Dict, Tuple


@dataclass(frozen=True)
class Dims:
    d: int            # hidden size
    hq: int           # query heads
    hkv: int          # KV heads
    dh: int           # head size
    ff: int           # MLP width
    layers: int
    vocab: int
    rope_theta: float
    eps: float
    qkv_bias: bool
    qk_norm: bool
    tied: bool

    @property
    def vocab_padded(self) -> int:
        """Embedding rows the program keeps: the vocabulary rounded up to
        256 (the pad rows are zero and their logits masked)."""
        return -(-self.vocab // 256) * 256

    def kv_bytes_per_token(self, itemsize: int = 2) -> int:
        """K and V of one token over all layers."""
        return 2 * self.hkv * self.dh * itemsize * self.layers

    def projections(self) -> Dict[str, Tuple[int, int]]:
        """(K, N) of each packed projection of one layer, QKV and
        gate+up fused as the deployment quantizes them."""
        return {
            "qkv": (self.d, (self.hq + 2 * self.hkv) * self.dh),
            "o": (self.hq * self.dh, self.d),
            "gate_up": (self.d, 2 * self.ff),
            "down": (self.ff, self.d),
        }


def dims(spec: Dict[str, Any]) -> Dims:
    pub, arch = spec["published"], spec["architecture"]
    hq = int(pub["num_attention_heads"])
    return Dims(
        d=int(pub["hidden_size"]), hq=hq,
        hkv=int(pub["num_key_value_heads"]),
        dh=int(arch.get("head_dim") or pub["hidden_size"] // hq),
        ff=int(pub["intermediate_size"]),
        layers=int(pub["num_hidden_layers"]),
        vocab=int(pub["vocab_size"]),
        rope_theta=float(pub["rope_theta"]),
        eps=float(pub["rms_norm_eps"]),
        qkv_bias=bool(arch["qkv_bias"]), qk_norm=bool(arch["qk_norm"]),
        tied=bool(pub["tie_word_embeddings"]))


def salient_channels(k: int, ratio: float, multiple: int) -> int:
    """PTQ1.61's salient input channels for K inputs: ratio·K rounded to
    the multiple, at least one multiple and leaving at least one."""
    k_s = int(round(ratio * k / multiple)) * multiple
    return max(multiple, min(k_s, k - multiple))
