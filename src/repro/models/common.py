"""Shared model-side helpers: run-time parallelism knobs and sharding hints."""
from __future__ import annotations

from dataclasses import dataclass

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as PS


@dataclass(frozen=True)
class Parallel:
    """Parallelism knobs the *model code* needs to know about.

    The full mesh/rule mapping lives in ``repro.distributed.sharding``; the
    model only needs the tensor-parallel degree (to pre-replicate KV heads)
    and whether to emit sequence-parallel sharding hints.
    """

    tp: int = 1                 # size of the "model" mesh axis
    dp: int = 1                 # size of the "data" (* pod) axes
    fsdp: bool = False          # ZeRO-3: shard params' embed dim over data
    sp: bool = True             # sequence-parallel activation constraints
    microbatches: int = 1       # gradient-accumulation chunks inside train_step
    remat: bool = True          # activation checkpointing on the layer scan
    attn_chunk: int = 1024      # flash-style KV chunking threshold/size
    shard_batch: bool = True    # False when global batch < dp (long_500k)
    decode_unroll: bool = False # unroll the decode layer loop: KV caches
                                # update in place (slot writes) instead of
                                # scan-carry slice round-trips (§Perf)

    def kv_heads_run(self, n_kv: int, n_q: Optional[int] = None) -> int:
        """Megatron-style KV-head replication for tensor parallelism.

        Replicate KV heads toward the TP degree so the KV projections and
        cache shard over "model", subject to the GQA constraint that the
        run-time KV count must divide the query-head count (the attention
        kernel reshapes q to (…, hkv, rep, dh)).  For archs whose head
        counts don't divide the TP degree (phi4 24H, llava 56H,
        recurrentgemma 10H) we return the largest valid count ≤ tp and let
        GSPMD pad the uneven shard — correct, with the padding cost
        visible in the §Roofline report rather than hidden.
        """
        if self.tp <= n_kv:
            return n_kv
        best = n_kv
        if n_q is None:
            # no GQA constraint available: largest multiple of n_kv ≤ tp
            return (self.tp // n_kv) * n_kv
        for cand in range(n_kv, self.tp + 1, n_kv):
            if n_q % cand == 0:
                best = cand
        return best


def current_mesh():
    """The ambient mesh during tracing, or None.

    Checks the new abstract-mesh context first, then the legacy
    ``with mesh:`` thread-resources context (which jax.jit +
    with_sharding_constraint(PartitionSpec) still uses) — the abstract
    mesh alone is empty under ``with mesh:``, which silently no-ops every
    activation hint (found via the dry-run roofline; EXPERIMENTS.md §Perf).
    """
    am = jax.sharding.get_abstract_mesh()
    if am is not None and not am.empty:
        return am
    try:
        from jax._src import mesh as mesh_lib
        m = mesh_lib.thread_resources.env.physical_mesh
        if m is not None and not m.empty:
            return m
    except Exception:
        pass
    return None


def in_mesh() -> bool:
    """True when tracing under a non-trivial device mesh."""
    m = current_mesh()
    return m is not None and m.devices.size > 1 if hasattr(m, "devices") \
        else m is not None


def hint(x: jax.Array, *axes) -> jax.Array:
    """Sharding-constraint that degrades to a no-op off-mesh (smoke tests)."""
    if not in_mesh():
        return x
    return jax.lax.with_sharding_constraint(x, PS(*axes))


def hint_act(x: jax.Array, par) -> jax.Array:
    """Residual-stream activation hint.

    (batch, seq, d_model): batch over data(+pod), and — when sequence
    parallelism is on — seq over the model axis (otherwise the residual
    stream would be replicated across TP ranks between blocks).
    """
    if not in_mesh():
        return x
    batch_axes = _batch_axes() if par.shard_batch and x.shape[0] > 1 else None
    if x.ndim == 3 and par.sp and x.shape[1] > 1:
        return jax.lax.with_sharding_constraint(x, PS(batch_axes, "model", None))
    if x.ndim == 3:
        return jax.lax.with_sharding_constraint(x, PS(batch_axes, None, None))
    return jax.lax.with_sharding_constraint(x, PS(batch_axes, None))


def _batch_axes():
    m = current_mesh()
    names = m.axis_names if m is not None else ()
    return ("pod", "data") if "pod" in names else "data"


def batch_spec(*rest) -> PS:
    """PartitionSpec with the batch dim over data(+pod) and given tail axes."""
    return PS(_batch_axes(), *rest)
