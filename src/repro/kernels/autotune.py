"""Block-size autotuner for the packed-weight Pallas kernels.

The kernels' original hard-coded defaults (bm=256, bn=512, bk=128/256)
were tuned for calibration-shaped GEMMs (M≈256).  Decode runs the same
kernels at M = n_slots (1–16): a 256-row M block is meaningless there,
and a 512-column N block forces ``N/512`` re-reads of the (M, K)
activation tile that at decode shapes could sit in VMEM whole.  This
module replaces the constants with a small static cost model:

* **feasibility** — every block dim must divide its array dim (the
  kernels have no remainder handling), ``bn`` must keep the 128-lane
  alignment, and ``bk`` must be a *common* divisor of the int4 and
  binary K spans (a multiple of 8 so packed bytes split evenly).  With
  ``tpu_tiling=True`` the Mosaic block floors apply on top: ``bk`` a
  multiple of 128 (it is the lane dim of the activation and α_in
  blocks) and ``bm`` either all of M or a multiple of 8;
* **VMEM budget** — double-buffered input tiles, the f32 accumulator
  AND the in-kernel unpack/dot temporaries must fit ``vmem_budget``
  (default 12 MiB of the 16 MiB scoped VMEM Mosaic grants a v5e
  kernel, the rest a margin for the model's error);
* **HBM bytes per call** — weight bytes stream once per M tile,
  activation bytes once per N tile, so the model prefers the largest
  feasible ``bm``/``bn`` (for decode M this collapses to ``bm=M`` and,
  VMEM permitting, ``bn=N`` — one x read per call);
* **modeled time** — ``max(flops/PEAK_FLOPS, bytes/HBM_BW)`` with the
  v5e constants from ``repro.launch.hlo_analysis`` (the same numbers
  the roofline report uses).

``choose_blocks`` is memoized (the dispatch cache): one search per
distinct ``(M, k_s, k_b, N)``, O(1) afterwards — decode calls the same
handful of shapes millions of times.
"""
from __future__ import annotations

import functools
import math
from dataclasses import dataclass
from typing import Optional, Tuple

from repro.launch.hlo_analysis import HBM_BW, PEAK_FLOPS

# Mosaic's default scoped-VMEM limit on v5e.  The matmul and decode
# footprint models below count pipeline buffers and kernel temporaries
# and keep a quarter of the limit as margin for what they miss; the
# chunked-prefill model is bounded from above by Mosaic's own count
# (compiled at page 16, head dims 32 to 512) and is held to the limit
# itself.
VMEM_LIMIT = 16 * 1024 * 1024
VMEM_BUDGET = 12 * 1024 * 1024
LANE = 128            # TPU lane width: last-dim floor of a Mosaic block
SUBLANE = 8           # second-to-last-dim floor of a Mosaic block
BM_CAP = 256          # MXU saturates at 128 rows; 256 amortizes setup
BK_CAP = 512
BN_CAP = 32768


@dataclass(frozen=True)
class BlockChoice:
    """One (bm, bn, bk) pick plus the cost-model terms behind it."""
    bm: int
    bn: int
    bk: int
    vmem_bytes: int
    hbm_bytes: int
    time_s: float


def _divisors(n: int, cap: int) -> Tuple[int, ...]:
    """Divisors of ``n`` that are ≤ cap, descending."""
    if n <= 0:
        return ()
    out = [d for d in range(1, int(math.isqrt(n)) + 1) if n % d == 0]
    out += [n // d for d in reversed(out) if n // d not in out]
    return tuple(sorted((d for d in out if d <= cap), reverse=True))


def common_bk(k_s: int, k_b: int, cap: Optional[int] = None,
              align: int = 8) -> Optional[int]:
    """Largest multiple-of-``align`` K block that divides BOTH the int4
    span ``k_s`` and the binary span ``k_b`` (an empty span constrains
    nothing).  Returns None when no such block exists — the caller must
    fall back to the XLA path rather than assert inside the kernel."""
    if cap is None:
        cap = BK_CAP
    g = math.gcd(max(k_s, 0), max(k_b, 0))
    if g == 0:
        return None
    for d in _divisors(g, cap):
        if d % align == 0:
            return d
    return None


def resolve_blocks(m: int, k_s: int, k_b: int, n: int,
                   bm: Optional[int], bn: Optional[int], bk: Optional[int],
                   *, align: int = 8, bk_default: int = 256,
                   tpu_tiling: bool = False,
                   ) -> Tuple[int, int, Optional[int]]:
    """Shared block-dim resolution for all three packed kernels.

    Missing dims come from the autotuner (legacy MXU constants when no
    feasible choice exists); explicit dims are clamped to the array and
    a ``bk`` that fails to divide a K span is repaired to the largest
    common divisor at or below it (multiple of ``align``, or of
    :data:`LANE` under ``tpu_tiling``).  Returns ``bk=None`` when no
    feasible K block exists — callers raise their kernel-specific error.
    """
    choice = choose_blocks(m, k_s, k_b, n, tpu_tiling=tpu_tiling)
    if tpu_tiling:
        align = LANE
    if bm is None:
        bm = choice.bm if choice else min(BM_CAP, m)
    if bn is None:
        bn = choice.bn if choice else min(512, n)
    if bk is None:
        bk = choice.bk if choice else bk_default
    bm, bn = min(bm, m), min(bn, n)
    bk = min((bk,) + tuple(s for s in (k_s, k_b) if s))
    if any(s % bk for s in (k_s, k_b) if s):
        bk = common_bk(k_s, k_b, cap=bk, align=align)
    return bm, bn, bk


def kernel_vmem_bytes(bm: int, bn: int, bk: int) -> int:
    """Per-step VMEM footprint of the mixed kernel: double-buffered
    input tiles (x bf16, packed nibbles + bits, f32 scale vectors), the
    revisited f32 accumulator tile, and the temporaries the kernel body
    materializes — the unpacked (bk, bn) weight tile (int32/f32 codes
    plus their bf16 MXU copy), the (bm, bn) f32 dot result and its
    scaled copy, and the (bm, bk) f32/bf16 activation casts.  Fitted
    from above to Mosaic's v5e scoped-VMEM accounting: at bk=128 the
    unpack temporaries dominate once bn reaches a few thousand."""
    inputs = (bm * bk * 2            # x tile, bf16
              + (bk // 2) * bn       # w4 tile, u8
              + (bk // 8) * bn       # bits tile, u8
              + 3 * bk * 4           # s4 / z4 / alpha_in slices
              + bn * 4)              # alpha_out slice
    temps = bk * bn * (4 + 2) + bm * bn * (4 + 4) + bm * bk * (4 + 2)
    return 2 * inputs + bm * bn * 4 + temps


def gather_in_kernel_ok(choice: BlockChoice, m: int, k: int,
                        vmem_budget: Optional[int] = None) -> bool:
    """Whether the mixed kernel can host the salient-channel gather
    itself: the activation tile grows from (bm, bk) to (bm, K) — the
    full permuted row must sit in VMEM so scalar-prefetched perm indices
    can select each K step's columns.  In exchange the activation is
    fetched once per M tile instead of once per (M, N) tile and the
    host-side XLA gather disappears.  True when the swap still fits the
    VMEM budget."""
    if vmem_budget is None:
        vmem_budget = VMEM_BUDGET
    bm = min(choice.bm, m)
    grown = choice.vmem_bytes - 2 * bm * choice.bk * 2 + 2 * bm * k * 2
    return grown <= vmem_budget


def weight_bytes(k_s: int, k_b: int, n: int) -> int:
    """Packed weight bytes one call must stream (nibbles + sign bits)."""
    return (k_s // 2) * n + (k_b // 8) * n


def vector_bytes(k_s: int, k_b: int, n: int) -> int:
    """f32 side-band vectors: s4+z4 (k_s each), alpha_in (k_b),
    alpha_out (n)."""
    return (2 * k_s + k_b + n) * 4


def modeled_hbm_bytes(m: int, k_s: int, k_b: int, n: int,
                      bm: int, bn: int) -> int:
    """HBM bytes per kernel call under the chosen tiling: each weight
    byte streams once per M tile, the bf16 activation once per N tile,
    vectors once, and the output writes once (f32 accumulator)."""
    k = k_s + k_b
    return (weight_bytes(k_s, k_b, n) * _cdiv(m, bm)
            + m * k * 2 * _cdiv(n, bn)
            + vector_bytes(k_s, k_b, n)
            + m * n * 4)


def _cdiv(a: int, b: int) -> int:
    return -(-a // b)


def modeled_time_s(m: int, k: int, n: int, hbm_bytes: int) -> float:
    return max(2.0 * m * k * n / PEAK_FLOPS, hbm_bytes / HBM_BW)


def choose_blocks(m: int, k_s: int, k_b: int, n: int,
                  vmem_budget: Optional[int] = None, *,
                  tpu_tiling: bool = False) -> Optional[BlockChoice]:
    """Pick (bm, bn, bk) for one mixed/int4/binary matmul call.

    Pass ``k_s=0`` for a pure-binary layout or ``k_b=0`` for pure int4.
    Returns None when no feasible tiling exists (misaligned N, no common
    K block, or a degenerate shape) — callers fall back to XLA.
    ``tpu_tiling=True`` restricts the search to blocks Mosaic accepts
    (see the module docstring); interpret mode has no such floors.

    The memoization IS the dispatch cache: serving decodes hit the same
    few (M, k_s, k_b, N) keys every step.  The module-level knobs
    (``VMEM_BUDGET``, ``BM_CAP``/``BK_CAP``/``BN_CAP``) are read here at
    call time and are part of the cache key, so reassigning them takes
    effect immediately — including for already-seen shapes.
    """
    return _choose_blocks_cached(
        m, k_s, k_b, n,
        VMEM_BUDGET if vmem_budget is None else vmem_budget,
        BM_CAP, BK_CAP, BN_CAP, tpu_tiling)


@functools.lru_cache(maxsize=4096)
def _choose_blocks_cached(m: int, k_s: int, k_b: int, n: int,
                          vmem_budget: int, bm_cap: int, bk_cap: int,
                          bn_cap: int, tpu_tiling: bool,
                          ) -> Optional[BlockChoice]:
    if m <= 0 or n <= 0 or k_s + k_b <= 0:
        return None
    if n % LANE != 0:
        return None
    bk_align = LANE if tpu_tiling else 8
    bk0 = common_bk(k_s, k_b, cap=bk_cap, align=bk_align)
    if bk0 is None:
        return None
    k = k_s + k_b
    bks = tuple(d for d in _divisors(bk0, bk_cap) if d % bk_align == 0)
    bns = tuple(d for d in _divisors(n, bn_cap) if d % LANE == 0)
    bms = _divisors(m, bm_cap) or (m,)
    if tpu_tiling:
        bms = tuple(d for d in bms if d == m or d % SUBLANE == 0)
    best: Optional[BlockChoice] = None
    for bm in bms:
        for bn in bns:
            # feasibility of this (bm, bn) is monotone in bk: take the
            # largest bk that fits, larger bk = fewer grid steps
            for bk in bks:
                vmem = kernel_vmem_bytes(bm, bn, bk)
                if vmem > vmem_budget:
                    continue
                hbm = modeled_hbm_bytes(m, k_s, k_b, n, bm, bn)
                cand = BlockChoice(bm, bn, bk, vmem, hbm,
                                   modeled_time_s(m, k, n, hbm))
                if (best is None or cand.hbm_bytes < best.hbm_bytes
                        or (cand.hbm_bytes == best.hbm_bytes
                            and cand.bk > best.bk)):
                    best = cand
                break
    return best


# ---------------------------------------------------------------------------
# Paged-attention decode kernel (KV compute blocks)
# ---------------------------------------------------------------------------
# The paged flash-decode kernel gathers `ppcb` pool pages per grid step
# into a (ppcb*ps*hkv, dh) VMEM block of K and one of V (every kv head;
# see kernels/paged_attention.py), double-buffered across steps.  The one
# free dim is `ppcb` (pages per compute block); ps/hkv/dh are fixed by
# the pool layout.  A grid step costs a fixed overhead, so the model
# takes the largest `ppcb` — at most PAGED_BLOCK_TOKENS tokens, at most
# the table width — whose buffers, q/out tiles, scratch and temporaries
# fit the VMEM budget.  It also exposes the per-token KV read bytes the
# serving bench asserts against.

PAGED_BLOCK_TOKENS = 512   # tokens per compute block, at most


@dataclass(frozen=True)
class PagedAttnChoice:
    """KV-block pick for one paged-attention call plus its cost terms."""
    ppcb: int                  # pool pages per compute block
    vmem_bytes: int
    kv_bytes_per_token: int    # K+V bytes one live token costs per read


def paged_kv_bytes_per_token(hkv: int, dh: int, itemsize: int = 2) -> int:
    """K+V bytes the decode read streams per live token (all kv heads)."""
    return 2 * hkv * dh * itemsize


def paged_read_bytes(context_len: int, ps: int, hkv: int, dh: int,
                     itemsize: int = 2) -> int:
    """Modeled KV bytes ONE decode step reads for a request of
    ``context_len`` live tokens under the paged kernel: whole pages, so
    at most one page of slack past the live tokens."""
    pages = -(-max(int(context_len), 0) // ps)
    return pages * ps * paged_kv_bytes_per_token(hkv, dh, itemsize)


def paged_attn_vmem_bytes(hkv: int, rep: int, dh: int, ps: int,
                          ppcb: int = 1, kv_itemsize: int = 2,
                          q_itemsize: int = 2) -> int:
    """Per-step VMEM footprint: the K and V block buffers (two slots
    each), the double-buffered q tile and f32 output tile, the resident
    (m, l, acc) scratch and row mask, and the block's temporaries (its K
    and V rows loaded, f32 scores, probabilities and mask over every
    query head x every row)."""
    rows = ppcb * ps * hkv
    hq = hkv * rep
    kv = 2 * 2 * rows * dh * kv_itemsize
    qo = 2 * hq * dh * (q_itemsize + 4)
    scratch = hq * (dh + 2) * 4 + rows * 4
    temps = 2 * rows * dh * kv_itemsize + 3 * hq * rows * 4
    return kv + qo + scratch + temps


def choose_paged_blocks(hkv: int, rep: int, dh: int, ps: int, nblk: int,
                        vmem_budget: Optional[int] = None,
                        ) -> Optional[PagedAttnChoice]:
    """Pick the pages per compute block of a paged-attention shape
    (``nblk`` = block-table width), or None when even one page cannot
    fit (callers fall back to the XLA gather path).  Memoized like
    :func:`choose_blocks` — decode hits the same key every layer of
    every tick."""
    return _choose_paged_cached(
        hkv, rep, dh, ps, nblk,
        VMEM_BUDGET if vmem_budget is None else vmem_budget)


@functools.lru_cache(maxsize=1024)
def _choose_paged_cached(hkv: int, rep: int, dh: int, ps: int, nblk: int,
                         vmem_budget: int) -> Optional[PagedAttnChoice]:
    if hkv <= 0 or rep <= 0 or dh <= 0 or ps <= 0 or nblk <= 0:
        return None
    for ppcb in range(min(nblk, max(PAGED_BLOCK_TOKENS // ps, 1)), 0, -1):
        vmem = paged_attn_vmem_bytes(hkv, rep, dh, ps, ppcb)
        if vmem <= vmem_budget:
            return PagedAttnChoice(ppcb, vmem,
                                   paged_kv_bytes_per_token(hkv, dh))
    return None


# ---------------------------------------------------------------------------
# Paged-prefill kernel (chunked scatter+attend tiles)
# ---------------------------------------------------------------------------
# The chunked-prefill kernel keeps the whole chunk's queries and the
# online-softmax scratch resident while streaming one KV page per grid
# step, so its VMEM footprint scales with the query rows (bh, rep, C)
# instead of the decode kernel's (hkv, rep).  `bh` (kv heads per grid
# step) is the only free dim; the model picks the largest one Mosaic can
# lower whose footprint fits, and exposes the per-chunk traffic terms
# the serving bench accounts against (mirroring paged_read_bytes).


def paged_prefill_vmem_bytes(bh: int, rep: int, dh: int, ps: int, c: int,
                             kv_itemsize: int = 2,
                             q_itemsize: int = 2) -> int:
    """Per-step VMEM footprint of the chunked-prefill kernel as Mosaic
    allocates it: every pipelined block twice (the q block, the f32
    output block, the context and chunk K/V page tiles in, the K/V page
    tiles out), the (m, l, acc) scratch with the (bh, rep, C, 1) running
    max and denominator padded to 128 lanes, one KV page's f32 scores
    and probabilities over every query row, lane-padded too, and one
    page's K in f32 with a lane tile per row of dh.  A block's last dim
    (dh) is padded to 128 lanes and a K/V page tile's second-minor dim
    (bh) to 8 sublanes.

    Bounded from above by Mosaic's v5e scoped-VMEM count, found by
    compiling the kernel under shrinking limits at page 16 (the test
    ``test_paged_prefill_vmem_model_bounds_mosaic`` holds each shape's
    compile to this count): at dh 128 and 64 to 8192 query rows
    (bh*rep*C) the count here is 11-60% over Mosaic's, 11-18% at 4096
    rows and more (Qwen2.5-3B's bh=2, rep=8, C=256: 15.4 MiB against
    13.6); at dh 256, 384 and 512, and 32 and 64 (lane-padded), 7-103%
    over."""
    rows = bh * rep * c
    lanes = -(-ps // LANE) * LANE
    dhp = -(-dh // LANE) * LANE
    page = ps * (-(-bh // SUBLANE) * SUBLANE) * dhp * kv_itemsize
    blocks = rows * dhp * (q_itemsize + 4) + 6 * page
    scratch = rows * (dhp + 2 * LANE) * 4
    temps = rows * lanes * (4 + kv_itemsize) + dhp * LANE * 4
    return 2 * blocks + scratch + temps


def paged_prefill_read_bytes(start: int, length: int, ps: int, hkv: int,
                             dh: int, itemsize: int = 2) -> int:
    """Modeled KV bytes ONE chunk call moves for a chunk at ``start``
    with ``length`` live tokens: context pages stream in once, chunk
    pages write once (whole pages, so at most one page of slack) — the
    prefill mirror of :func:`paged_read_bytes`."""
    ctx_pages = -(-max(int(start), 0) // ps)
    chunk_pages = -(-max(int(length), 0) // ps)
    return ((ctx_pages + chunk_pages) * ps
            * paged_kv_bytes_per_token(hkv, dh, itemsize))


@dataclass(frozen=True)
class PagedPrefillChoice:
    """KV-tile pick for one chunked-prefill call plus its cost terms."""
    bh: int                    # kv heads per block
    vmem_bytes: int
    kv_bytes_per_token: int


def choose_prefill_blocks(c: int, hkv: int, rep: int, dh: int, ps: int,
                          vmem_budget: Optional[int] = None,
                          ) -> Optional[PagedPrefillChoice]:
    """Pick the kv-heads-per-block tile for a chunked-prefill shape, or
    None when no tile Mosaic can lower fits (callers fall back to the
    XLA dense-gather path).  ``bh`` is the second-minor dim of the K/V
    page blocks, so Mosaic takes only all ``hkv`` heads or a multiple of
    8: at 8 KV heads and a 256-token chunk nothing fits.  Memoized like
    the other choosers — every chunk of every prompt hits the same
    (C, hkv, rep, dh, ps) key."""
    return _choose_prefill_cached(
        c, hkv, rep, dh, ps,
        VMEM_LIMIT if vmem_budget is None else vmem_budget)


@functools.lru_cache(maxsize=1024)
def _choose_prefill_cached(c: int, hkv: int, rep: int, dh: int, ps: int,
                           vmem_budget: int) -> Optional[PagedPrefillChoice]:
    if c <= 0 or hkv <= 0 or rep <= 0 or dh <= 0 or ps <= 0 or c % ps:
        return None
    for bh in _divisors(hkv, hkv):
        if bh != hkv and bh % SUBLANE:
            continue
        vmem = paged_prefill_vmem_bytes(bh, rep, dh, ps, c)
        if vmem <= vmem_budget:
            return PagedPrefillChoice(bh, vmem,
                                      paged_kv_bytes_per_token(hkv, dh))
    return None


def cache_info():
    """Dispatch-cache stats for the memoized choosers (matmul block
    picks, paged-attention KV tiles, chunked-prefill tiles)."""
    return {"matmul": _choose_blocks_cached.cache_info(),
            "paged_attention": _choose_paged_cached.cache_info(),
            "paged_prefill": _choose_prefill_cached.cache_info()}


def cache_clear() -> None:
    _choose_blocks_cached.cache_clear()
    _choose_paged_cached.cache_clear()
    _choose_prefill_cached.cache_clear()
