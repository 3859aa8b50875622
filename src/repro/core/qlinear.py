"""QLinear — the packed PTQ1.61 weight pytree and its forward.

Storage layout (per (K, N) linear, K = input dim):
  perm        (K,)  int32   salient-first stable channel permutation
                            (derivable from the 1-bit mask; stored for O(1)
                            use — accounted as the mask's K bits)
  w4          (k_s/2, N) u8 packed int4 codes of salient channels
  s4, z4      (k_s,) f32    per-salient-channel scale / zero-point
  bits        (k_b/8, N) u8 packed signs of binarized channels
  alpha_s     (N,) f32      analytic/learned row scale (Eq. 2)
  alpha_r1    (N,) f32      learned angular factor, output side (Eq. 9)
  alpha_r2    (k_b,) f32    learned angular factor, input side (Eq. 9)

Forward (math identical to Eq. 9 + int4 dequant):
  y = x[.., perm_s] @ W4deq  +  ((x[.., perm_b] * α_r2) @ sign) * (α_s·α_r1)

The packed arrays are PRE-PERMUTED: ``quantize_linear`` folds the
salient-first permutation into ``w4``/``bits`` row order at quantization
time, so the forward needs exactly ONE activation gather (``x[.., perm]``)
and no weight-side reordering — ``__matmul_permuted__`` skips even that
when the caller already holds salient-first activations (the kernel
dispatcher and the N-fused group path below).

Leading stack dims (scan layers L, experts E) are supported on all array
fields; static metadata lives in pytree aux so stacked QLinears slice
cleanly under `jax.lax.scan`.

The XLA path below dequantizes on the fly (what the dry-run lowers); on
TPU the Pallas kernels in ``repro.kernels`` implement the same contraction
streaming packed bytes HBM→VMEM (``use_kernel=True``).

Decode N-fusion: :class:`QLinearGroup` stores several same-input
projections (QKV, gate+up) as ONE quantized matrix concatenated along N,
sharing a single permutation / int4 scale set / α_r2 — each transformer
block then issues 2 packed matmuls instead of 5 and gathers the
activation once per group instead of once per projection.
"""
from __future__ import annotations

import dataclasses
from dataclasses import dataclass
from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core import binarize, int4, pack, saliency as sal

Tree = Any


@dataclass(frozen=True)
class QuantConfig:
    """PTQ1.61 hyper-parameters (paper §4.1 defaults)."""

    ratio: float = 0.2            # salient input-channel fraction (Fig. 6)
    multiple: int = 128           # k_s rounding (pack & 16-way TP divisibility)
    steps: int = 20               # block-wise optimization epochs
    lr: float = 5e-4              # AdamW lr for scales (paper: 5e-4 / 1e-3)
    lr_r: float = 1e-3            # lr for angular factors
    cosine_loss: bool = True      # D_NLC term (Eq. 5-6); ablation toggle
    learn_scales: bool = True     # Table-3 "Learnable Scalar" toggle
    use_mask: bool = True         # Table-3 "Structured Mask" toggle
    hessian_mask: bool = False    # OWQ-style ranking (App. B comparison)
    preprocess: bool = False      # Table-3 "Preprocess" toggle (restorative LoRA)
    use_kernel: bool = False      # dispatch Pallas kernels instead of XLA dequant


@jax.tree_util.register_pytree_node_class
@dataclass
class QLinear:
    perm: jax.Array
    w4: jax.Array
    s4: jax.Array
    z4: jax.Array
    bits: jax.Array
    alpha_s: jax.Array
    alpha_r1: jax.Array
    alpha_r2: jax.Array
    k_s: int = dataclasses.field(metadata={"static": True})
    k: int = dataclasses.field(metadata={"static": True})
    n: int = dataclasses.field(metadata={"static": True})
    use_kernel: bool = dataclasses.field(default=False, metadata={"static": True})

    _FIELDS = ("perm", "w4", "s4", "z4", "bits", "alpha_s", "alpha_r1",
               "alpha_r2")

    def tree_flatten(self):
        children = tuple(getattr(self, f) for f in self._FIELDS)
        aux = (self.k_s, self.k, self.n, self.use_kernel)
        return children, aux

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children, *aux)

    # ---- helpers -----------------------------------------------------
    @property
    def k_b(self) -> int:
        return self.k - self.k_s

    def dequant_salient(self, dtype=jnp.bfloat16) -> jax.Array:
        q = pack.unpack_nibbles(self.w4, axis=-2, dtype=jnp.float32)
        return int4.dequant_int4(q.astype(jnp.uint8), self.s4, self.z4, dtype)

    def dequant_binary(self, dtype=jnp.bfloat16) -> jax.Array:
        sign = pack.unpack_bits(self.bits, axis=-2, dtype=jnp.float32)
        return binarize.dequant_binary(sign, self.alpha_s, self.alpha_r1,
                                       self.alpha_r2, dtype)

    def to_dense(self, dtype=jnp.bfloat16) -> jax.Array:
        """Reconstruct the (…, K, N) fake-quant matrix in original channel
        order (testing / fake-quant evaluation)."""
        wq = jnp.concatenate(
            [self.dequant_salient(dtype), self.dequant_binary(dtype)], axis=-2)
        inv = jnp.argsort(self.perm, axis=-1)
        if self.perm.ndim == 1:
            return wq[..., inv, :]
        return jnp.take_along_axis(wq, inv[..., :, None], axis=-2)

    # ---- forward ------------------------------------------------------
    def __matmul_x__(self, x: jax.Array) -> jax.Array:
        """x: (..., K) -> (..., N).  2-D weights only (stacked weights are
        sliced by scan before reaching here)."""
        if self.use_kernel:
            from repro.kernels import ops
            return ops.mixed_matmul(x, self)
        return self.__matmul_permuted__(jnp.take(x, self.perm, axis=-1))

    def __matmul_permuted__(self, xp: jax.Array) -> jax.Array:
        """Forward over ALREADY salient-first-permuted activations —
        the one-gather entry point shared by the XLA path, the kernel
        dispatcher's fallback, and fused-group callers."""
        xs, xb = xp[..., : self.k_s], xp[..., self.k_s:]
        y4 = jnp.einsum("...k,kn->...n", xs, self.dequant_salient(xp.dtype))
        sign = pack.unpack_bits(self.bits, axis=-2, dtype=xp.dtype)
        yb = jnp.einsum("...k,kn->...n", xb * self.alpha_r2.astype(xp.dtype),
                        sign)
        yb = yb * (self.alpha_s * self.alpha_r1).astype(xp.dtype)
        return y4 + yb

    def __expert_matmul__(self, x: jax.Array) -> jax.Array:
        """x: (E, C, K) with stacked per-expert weights (E, ...)."""
        xp = jnp.take_along_axis(x, self.perm[:, None, :], axis=-1)
        xs, xb = xp[..., : self.k_s], xp[..., self.k_s:]
        y4 = jnp.einsum("eck,ekn->ecn", xs, self.dequant_salient(x.dtype))
        sign = pack.unpack_bits(self.bits, axis=-2, dtype=x.dtype)
        yb = jnp.einsum("eck,ekn->ecn",
                        xb * self.alpha_r2[:, None, :].astype(x.dtype), sign)
        yb = yb * (self.alpha_s * self.alpha_r1)[:, None, :].astype(x.dtype)
        return y4 + yb

    # ---- storage ------------------------------------------------------
    def packed_bytes(self) -> int:
        tot = 0
        for f in self._FIELDS:
            a = getattr(self, f)
            tot += a.size * a.dtype.itemsize
        return tot


def quantize_linear(w: jax.Array, act_stat: Optional[jax.Array],
                    qcfg: QuantConfig) -> QLinear:
    """PTQ1.61 initial quantization of one (…, K, N) weight (no learning).

    act_stat: per-input-channel saliency statistic E[|x|] (K,) (or stacked).
    Without one, each (K, N) slice ranks its channels by its own mean |w|.
    Without a mask (ablation), every channel binarizes (k_s=multiple is the
    floor, so we use k_s=0 semantics via an empty salient slice).

    Stacked weights (layers and/or experts) are quantized one (K, N) slice
    at a time, statistic included, so the f32 working set is one slice's
    and never the whole stack's.
    """
    k, n = w.shape[-2], w.shape[-1]
    if w.ndim == 2:
        fields = _quantize_slice(w, act_stat, qcfg)
    else:
        # flatten ALL leading dims, apply per (K, N) slice, restore the
        # leading shape on every field
        lead = w.shape[:-2]
        wf = w.reshape((-1,) + w.shape[-2:])
        sf = (act_stat.reshape((-1, k))
              if act_stat is not None and act_stat.ndim > 1 else None)
        outs = [_quantize_slice(wf[i], act_stat if sf is None else sf[i],
                                qcfg)
                for i in range(wf.shape[0])]
        fields = tuple(
            jnp.stack([o[j] for o in outs]).reshape(lead + outs[0][j].shape)
            for j in range(8))
    k_s = 2 * fields[1].shape[-2]           # w4 packs two codes per byte
    return QLinear(*fields, k_s=k_s, k=k, n=n, use_kernel=qcfg.use_kernel)


def _quantize_slice(wm: jax.Array, stat: Optional[jax.Array],
                    qcfg: QuantConfig) -> Tuple[jax.Array, ...]:
    """Quantize one (K, N) slice; returns the eight QLinear array fields."""
    k, n = wm.shape
    if stat is None:
        stat = jnp.mean(jnp.abs(wm.astype(jnp.float32)), axis=-1)
    if qcfg.hessian_mask:
        stat = sal.hessian_saliency(jnp.square(stat), wm)
    if qcfg.use_mask:
        _, perm, k_s = sal.structured_mask(stat, qcfg.ratio, qcfg.multiple)
    else:
        perm = jnp.arange(k, dtype=jnp.int32)
        k_s = 0
    wp = wm[perm]
    ws, wb = wp[:k_s], wp[k_s:]
    if k_s:
        q4 = int4.quantize_int4(ws)
        w4 = pack.pack_nibbles(q4["q"], axis=-2)
        s4, z4 = q4["s"], q4["z"]
    else:
        w4 = jnp.zeros((0, n), jnp.uint8)
        s4 = z4 = jnp.zeros((0,), jnp.float32)
    b = binarize.binarize_init(wb)
    bits = pack.pack_bits(b["sign"], axis=-2)
    return (perm, w4, s4, z4, bits, b["alpha_s"], b["alpha_r1"],
            b["alpha_r2"])


# ---------------------------------------------------------------------------
# N-fused projection groups (decode fast path)
# ---------------------------------------------------------------------------
@jax.tree_util.register_pytree_node_class
@dataclass
class QLinearGroup:
    """Several same-input projections fused along N into one weight.

    ``inner`` is either a plain (…, K, ΣN_i) array (exact fp fusion —
    concatenation changes no math) or a :class:`QLinear` quantized over
    the CONCATENATED weight, so every member shares one salient-first
    permutation, one (s4, z4) int4 scale set and one α_r2 — the
    structural requirement that lets the fused forward gather the
    activation once and issue one packed matmul for the whole group.

    ``splits`` records each member's output width; :meth:`split_out`
    recovers per-member outputs and :meth:`members` rebuilds unfused
    per-member views (the equivalence oracle: slicing the packed arrays
    along N is exact because pack layouts keep N contiguous).
    """

    inner: Any
    splits: Tuple[int, ...] = dataclasses.field(metadata={"static": True})

    def tree_flatten(self):
        return (self.inner,), (self.splits,)

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(children[0], *aux)

    # ---- shape helpers ------------------------------------------------
    @property
    def n(self) -> int:
        return sum(self.splits)

    @property
    def k(self) -> int:
        if isinstance(self.inner, QLinear):
            return self.inner.k
        return self.inner.shape[-2]

    # ---- forward ------------------------------------------------------
    def __matmul_x__(self, x: jax.Array) -> jax.Array:
        """Fused forward: x (..., K) -> (..., ΣN_i) in one matmul (and,
        for quantized inners, one activation gather)."""
        if hasattr(self.inner, "__matmul_x__"):
            return self.inner.__matmul_x__(x)
        return jnp.einsum("...k,kn->...n", x, self.inner.astype(x.dtype))

    def __expert_matmul__(self, x: jax.Array) -> jax.Array:
        """Fused per-expert forward: x (E, C, K) with stacked (E, …)
        member weights -> (E, C, ΣN_i) — one batched matmul (and, when
        quantized, one per-expert activation gather) for the whole
        group, the MoE twin of the decode QKV/gate-up fusion."""
        if hasattr(self.inner, "__expert_matmul__"):
            return self.inner.__expert_matmul__(x)
        return jnp.einsum("eck,ekn->ecn", x, self.inner.astype(x.dtype))

    def split_out(self, y: jax.Array) -> Tuple[jax.Array, ...]:
        """Slice a fused output back into per-member outputs."""
        return tuple(pack.split_cols(y, self.splits))

    def forward_split(self, x: jax.Array) -> Tuple[jax.Array, ...]:
        return self.split_out(self.__matmul_x__(x))

    # ---- oracle -------------------------------------------------------
    def members(self) -> Tuple[Any, ...]:
        """Per-member unfused views over the SAME quantized (or fp)
        data — the bit-equivalence oracle for the fused path."""
        if not isinstance(self.inner, QLinear):
            return tuple(pack.split_cols(self.inner, self.splits))
        q = self.inner
        out = []
        for w4, bits, a_s, a_r1, ni in zip(
                pack.split_cols(q.w4, self.splits),
                pack.split_cols(q.bits, self.splits),
                pack.split_cols(q.alpha_s, self.splits),
                pack.split_cols(q.alpha_r1, self.splits),
                self.splits):
            out.append(QLinear(q.perm, w4, q.s4, q.z4, bits, a_s, a_r1,
                               q.alpha_r2, k_s=q.k_s, k=q.k, n=ni,
                               use_kernel=q.use_kernel))
        return tuple(out)

    def packed_bytes(self) -> int:
        if isinstance(self.inner, QLinear):
            return self.inner.packed_bytes()
        return self.inner.size * self.inner.dtype.itemsize


def quantize_linear_group(ws, act_stat: Optional[jax.Array],
                          qcfg: QuantConfig) -> QLinearGroup:
    """PTQ1.61-quantize a list of same-K weights as ONE fused layout.

    The members are concatenated along N before masking/quantization, so
    the salient-channel mask (driven by the SHARED input activations)
    and all K-side parameters are common to the group — exactly the
    pre-permuted packed layout the fused decode kernel streams.
    """
    ks = {w.shape[-2] for w in ws}
    if len(ks) != 1:
        raise ValueError(f"fused members must share K, got {sorted(ks)}")
    splits = tuple(int(w.shape[-1]) for w in ws)
    fused = jnp.concatenate(list(ws), axis=-1)
    return QLinearGroup(quantize_linear(fused, act_stat, qcfg), splits)


def scale_params(q: QLinear) -> Tree:
    """The learnable subset for block-wise optimization (Eq. 7 argmin)."""
    return {"alpha_s": q.alpha_s, "alpha_r1": q.alpha_r1,
            "alpha_r2": q.alpha_r2}


def with_scales(q: QLinear, s: Tree) -> QLinear:
    return dataclasses.replace(q, alpha_s=s["alpha_s"],
                               alpha_r1=s["alpha_r1"], alpha_r2=s["alpha_r2"])


def field_axes(prefix: Tuple, in_ax, out_ax):
    """Logical axes per QLinear field, given the original weight's
    (prefix…, in_ax, out_ax) annotation.  Consumed by
    ``repro.distributed.sharding`` to build PartitionSpec QLinears."""
    return {
        "perm": prefix + (in_ax,),
        "w4": prefix + (in_ax, out_ax),
        "s4": prefix + (in_ax,),
        "z4": prefix + (in_ax,),
        "bits": prefix + (in_ax, out_ax),
        "alpha_s": prefix + (out_ax,),
        "alpha_r1": prefix + (out_ax,),
        "alpha_r2": prefix + (in_ax,),
    }
