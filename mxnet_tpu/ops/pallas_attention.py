"""Fused flash-attention Pallas kernels for TPU (forward AND backward).

The one hot op where a hand kernel beats composed XLA HLO: attention.  The
reference ships hand-written CUDA for the same reason
(``src/operator/contrib/transformer.cc`` — interleaved qkv matmuls + masked
softmax).  Here the fused kernels are Pallas-on-TPU:

* forward: grid ``(B*H, Tq/block_q, Tk/block_k)`` — leading axes parallel,
  the K axis sequential ("arbitrary") so VMEM scratch carries the online-
  softmax state (running max, normaliser, fp32 accumulator) across K blocks;
  emits the per-row logsumexp as a residual for backward;
* backward: two kernels in the standard flash-training shape —
  ``dq`` (K sequential, like forward) and ``dk/dv`` (Q sequential) — that
  recompute the score block from (q, k, lse) instead of materialising the
  (Tq, Tk) probability matrix.  Both kernels work on the TRANSPOSED score
  block ``sᵀ = k·qᵀ`` so the per-row lse/delta vectors broadcast along
  sublanes as cheap ``(1, block_q)`` rows — no in-kernel transposes;
* scores hit the MXU as bf16×bf16→fp32 ``dot_general``; causal blocks that
  are fully masked are skipped (DMA still runs, compute does not).

Falls back to the pure-jnp blockwise path off-TPU; ``interpret=True`` runs
the same kernels on CPU for tests.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax

from .. import context as _context
from .registry import register

__all__ = ["flash_attention", "flash_attention_bshd",
           "pallas_flash_attention", "pallas_flash_attention_bshd",
           "pallas_flash_attention_bwd", "pallas_flash_attention_bwd_bshd",
           "attention_dispatch", "tune_attention_blocks",
           "bshd_layout_fits", "mask_tiles", "window_mask"]

_NEG_INF = -1e30
_LANES = 128

# Kernel-selection constants (see attention_dispatch):
#  * _SHORT_SEQ_MAX_TK: the largest K extent the single-pass kernel takes
#    whole as ONE block — above it the streaming online-softmax kernel
#    amortizes better than a giant score tile;
#  * _DENSE_MIN_SEQ: below this, one XLA dot covers the whole score
#    matrix and the pallas grid/DMA setup costs more than it saves —
#    dense must win, so the dispatcher never sends these to a kernel;
#  * _VMEM_CLAMP: budget for a kernel invocation's VMEM working set as
#    _fwd_vmem_bytes / _bwd_vmem_bytes estimate it, out of the 16 MiB
#    scoped limit.  The headroom is not only Mosaic's: XLA places small
#    operands of the surrounding program in VMEM too, so the same kernel
#    is refused earlier inside a train step than alone.
_SHORT_SEQ_MAX_TK = 1024
_DENSE_MIN_SEQ = 128
_VMEM_CLAMP = 12 * 1024 * 1024
_BWD_MIN_BLOCK_Q = 128
# A mask given as data (``q_mask`` / ``kv_mask``, see flash_attention):
#  * _RANK_NEVER: the rank of a key no reach sees (a padded key's), and
#    _REACH_NONE the reach of a query that sees no rank (a padded row's);
#  * _MASKED_BLOCKS: the (q, K) blocks of a streamed K axis under such a
#    mask, halved like the others until they fit.  A tile is the unit the
#    mask skips by, and a grid step that skips still costs its ~0.35 us:
#    on a block-diffusion row of 2 x 4096 (a quarter of the pairs live)
#    1024 x 1024 tiles visit 37.5% of the square in a quarter of the
#    steps that 512 x 512 tiles need for their 31%, and 512 x 2048 visit
#    half.  Forward + backward of one layer's call (32 heads on 4, D=128,
#    the kernels alone): 14.2 ms at 1024 x 1024, 16.1 at 512 x 1024, 17.5
#    at 1024 x 512, 18.6 at 512 x 512, 19.5 at 512 x 2048, 25.3 at 256 x
#    512 (my chip runs, PR 33).
#  * _WINDOW_MIN_BLOCK: under a causal window the blocks are no wider than
#    the band (the window rounded up to a power of two), this at least: a
#    band of 512 keys in a row of 4096 leaves 15 of 64 tiles of 512 x 512
#    live against 7 of 16 of 1024 x 1024.  One layer's call (72 heads on
#    8, D=128), forward + backward, the kernels alone: 8.33 ms at 512 x
#    512, 8.66 at 512 x 1024, 9.05 at 1024 x 512, 9.48 at 1024 x 1024,
#    10.97 at 256 x 512, 12.14 at 512 x 256, 16.04 at 256 x 256; the same
#    call as plain causal attention 14.82 (my chip runs, PR 37).
_RANK_NEVER = 2 ** 31 - 1
_REACH_NONE = -2 ** 31
_MASKED_BLOCKS = (1024, 1024)
_WINDOW_MIN_BLOCK = 512


def _fwd_vmem_bytes(block_q, block_k, Dp, itemsize):
    """Forward working set of one grid step: q/o blocks, k/v blocks, the
    m/l/acc scratch rows, and TWO fp32 (block_q, block_k) tiles — the
    score tile (exp/normalize reuse its buffer) plus the iota/compare/
    select temporaries of the masked variants, which every public entry
    can reach (causal, kv_lens, segment ids) and which the plan cannot
    tell apart.  The v5e compiler's own scoped-VMEM figures for the
    masked kernels stay under two tiles; the mask-free kernel needs
    one."""
    qo = 2 * block_q * Dp * itemsize
    kv = 2 * block_k * Dp * itemsize
    score = 2 * block_q * block_k * 4
    scratch = block_q * (2 * _LANES + Dp) * 4
    return qo + kv + score + scratch


def _bwd_vmem_bytes(block_q, block_k, Dp, itemsize):
    """Backward working set of one grid step, sized at the dk/dv kernel
    (the larger of the split pair; the fused single-K-block kernel holds
    the same): the pipelined q/do and k/v/dk/dv blocks, double-buffered,
    the dk/dv fp32 accumulators, and the (block_k, block_q) score
    temporaries.  pT, dpT and dsT are never all live in fp32 — the v5e
    compiler's own scoped-VMEM figures come to 1.6-1.85 fp32 tiles
    across D in {64, 128} x {bf16, f32}, masks included — so two tiles
    bound them."""
    blocks = 2 * (2 * block_q + 4 * block_k) * Dp * itemsize
    acc = 2 * block_k * Dp * 4
    score = 2 * block_q * block_k * 4
    return blocks + acc + score


def _bwd_block_q(block_q, block_k, Dp, itemsize):
    """The backward's q block for a plan's (block_q, block_k): capped at
    the largest power of two whose backward working set honours
    ``_VMEM_CLAMP``.  q blocks are independent in every backward
    kernel, so only block_k — which decides the kernel variant — is
    shared with the forward.  At the bf16 D=64 defaults (512, 2048) the
    backward keeps 512; D=128 or fp32 operands halve it to 256."""
    cap = 2048
    while cap > _BWD_MIN_BLOCK_Q and \
            _bwd_vmem_bytes(cap, block_k, Dp, itemsize) > _VMEM_CLAMP:
        cap //= 2
    return min(block_q, cap)


def _blocks_fit(block_q, block_k, Dp, itemsize):
    """THE validity predicate for an attention (block_q, block_k): the
    forward fits its budget at these blocks and the backward fits its
    own at some q block (``_bwd_block_q`` finds it).
    ``tune_attention_blocks`` halves its blocks until this holds, so no
    plan reaches a kernel the chip's compiler refuses for VMEM."""
    return _fwd_vmem_bytes(block_q, block_k, Dp, itemsize) <= _VMEM_CLAMP \
        and _bwd_vmem_bytes(min(block_q, _BWD_MIN_BLOCK_Q), block_k, Dp,
                            itemsize) <= _VMEM_CLAMP


def tune_attention_blocks(seq_q, seq_k, head_dim, dtype="bfloat16",
                          masked=False, window=None):
    """Default (block_q, block_k) for a (S, D, dtype) attention shape.

    Short K axes (<= _SHORT_SEQ_MAX_TK) take the whole axis as one
    lane-aligned block so the single-pass kernel applies; long axes take
    (512, 2048) — the largest blocks today's v5e compiler accepts with
    every mask variant, forward and backward — or, under a mask given as
    data (``masked``), ``_MASKED_BLOCKS``, and where that mask is a causal
    ``window`` blocks no wider than its band (``_WINDOW_MIN_BLOCK``);
    halved until the working sets honour their VMEM budgets (large D /
    fp32 shapes)."""
    itemsize = jnp.dtype(dtype).itemsize
    Dp = head_dim + (-head_dim) % 64
    if seq_k <= _SHORT_SEQ_MAX_TK:
        block_k = max(_LANES, seq_k + (-seq_k) % _LANES)
        block_q = min(max(8, seq_q + (-seq_q) % 8), 512)
        while block_q > 128 and \
                not _blocks_fit(block_q, block_k, Dp, itemsize):
            block_q //= 2
        return block_q, block_k
    block_q, block_k = 512, 2048
    if masked:
        block_q, block_k = _MASKED_BLOCKS
        if window is not None:
            band = max(_WINDOW_MIN_BLOCK, 1 << (int(window) - 1).bit_length())
            block_q, block_k = min(block_q, band), min(block_k, band)
    while block_k > 512 and \
            not _blocks_fit(block_q, block_k, Dp, itemsize):
        block_k //= 2
    while block_q > 256 and \
            not _blocks_fit(block_q, block_k, Dp, itemsize):
        block_q //= 2
    return block_q, block_k


def _bshd_heads_per_block(num_heads, head_dim, one_k_block):
    """How many heads one lane block of a (B, T, H*D) array gives a grid
    step of the (B, T, H, D) kernels, the array read as the projection
    wrote it — no pad, no transpose: 1 where a head is whole 128-lane
    tiles; 2 for 64-wide heads, an even number of them, in the kernels
    that hold the whole K axis as one block (heads 2p and 2p+1 are lane
    tile p); 0 where this layout does not take the shape and the entry
    goes through the (B, H, T, D) kernels."""
    if head_dim % _LANES == 0:
        return 1
    if 2 * head_dim == _LANES and num_heads % 2 == 0 and one_k_block:
        return 2
    return 0


def bshd_layout_fits(num_heads, head_dim):
    """Whether ``flash_attention_bshd`` can read heads of this width where
    a projection left them, side by side along the lanes: the choice a
    caller that holds (B, T, H*D) activations makes between the two
    public ops, from the head count and width alone."""
    return _bshd_heads_per_block(num_heads, head_dim, True) > 0


def attention_dispatch(seq_q, seq_k, head_dim, dtype="bfloat16",
                       on_tpu=None, census=True, bshd_heads=None,
                       masked=False, tiles_visited=None, window=None):
    """Per-shape kernel choice for the public flash-attention ops.

    Returns ``{"kernel": "short_seq" | "streaming" | "dense_fallback",
    "block_q": int | None, "block_k": int | None, "layout": "bhsd" |
    "bshd" | "bshd_pair" | None, "heads_per_block": int | None}``.
    ``layout`` and
    ``heads_per_block`` say how the forward kernel addresses heads:
    ``bhsd`` a head a (B*H, T, D) row; for a (B, T, H, D) caller, which
    gives its head count as ``bshd_heads``, ``bshd`` a head a lane block
    and ``bshd_pair`` two 64-wide heads a 128-lane block, or ``bhsd``
    where that layout does not take the shape
    (``_bshd_heads_per_block``).  The blocks and the kernel do not depend
    on the layout.  ``short_seq`` is
    the single-pass kernel (whole K axis in one block — no online-softmax
    streaming state), ``streaming`` the K-sequential online-softmax
    kernel, ``dense_fallback`` composed XLA attention.  The heuristic is
    chosen so no caller shape regresses below dense: tiny sequences
    (min(Tq, Tk) < _DENSE_MIN_SEQ) go dense, Tk <= _SHORT_SEQ_MAX_TK
    single-pass, longer streams.

    Blocks come from ``tune_attention_blocks`` and nowhere else, so they
    fit VMEM forward and backward (``_blocks_fit``).

    ``masked``: the call carries a mask as data (``q_mask`` / ``kv_mask``).
    A long K axis then takes ``tune_attention_blocks``' masked blocks, the
    unit the mask skips by; the plan says so (``"masked"``) and counts the
    forward's ``"tiles"`` a head row.  ``tiles_visited`` is the caller's
    count of those the mask leaves live, where the mask is there to be
    read at trace time (an eager call, or a mask made from shapes: a
    window; None for a traced one): it goes into the event, no further.
    ``window``: the masked call is ``flash_attention``'s causal window of
    that many keys: the blocks follow its band
    (``tune_attention_blocks``), the census counts it and the event names
    it.

    ``census=False`` is the secondary-lookup spelling (the custom-vjp
    backward re-reading the forward's decision): same answer, but no
    counters and no event (the shape was counted at the forward
    trace)."""
    from .. import telemetry
    if on_tpu is None:
        on_tpu = _context.on_tpu()
    if not on_tpu or min(seq_q, seq_k) < _DENSE_MIN_SEQ:
        if census:
            telemetry.inc("attention.kernel.dense_fallback")
        return {"kernel": "dense_fallback", "block_q": None,
                "block_k": None, "layout": None, "heads_per_block": None}
    block_q, block_k = tune_attention_blocks(seq_q, seq_k, head_dim, dtype,
                                             masked, window)
    kernel = "short_seq" if seq_k <= block_k else "streaming"
    per_block = 0 if bshd_heads is None else _bshd_heads_per_block(
        bshd_heads, head_dim, kernel == "short_seq")
    layout = ("bhsd", "bshd", "bshd_pair")[per_block]
    per_block = max(per_block, 1)
    plan_mask = {}
    if masked:
        plan_mask = {"masked": True,
                     "tiles": -(-seq_q // block_q) * -(-seq_k // block_k)}
    # per-shape dispatch accounting: this runs at TRACE time (once per
    # compiled shape, not per step), so the journal is a census of which
    # kernel every shape in the run got
    if census:
        telemetry.inc("attention.kernel.%s" % kernel)
        if masked:
            telemetry.inc("attention.kernel.masked")
        if window is not None:
            telemetry.inc("attention.kernel.window")
        telemetry.inc("attention.layout.%s" % layout)
        telemetry.event("attention_dispatch", kernel, seq_q=int(seq_q),
                        seq_k=int(seq_k), head_dim=int(head_dim),
                        dtype=str(dtype), block_q=block_q,
                        block_k=block_k, layout=layout,
                        heads_per_block=per_block,
                        **(dict(plan_mask, tiles_visited=tiles_visited,
                                window=window) if masked else {}))
    return dict({"kernel": kernel, "block_q": block_q, "block_k": block_k,
                 "layout": layout, "heads_per_block": per_block},
                **plan_mask)


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------

def _run_mask_specialized(pl, compute, run, qi, ki, block_q, block_k,
                          causal, has_lens, has_seg, needs_tail,
                          kvlen=None, seq_k=None):
    """Shared mask-dispatch ladder for all the kernels.

    ``compute(use_mask)`` runs the block; this picks the cheapest correct
    specialization.  A block needs NO mask when it sits wholly below the
    causal diagonal, wholly inside the valid key length (``kvlen``, a
    traced per-row scalar when ``kv_lens`` is present), and wholly inside
    the true (unpadded) K extent ``seq_k`` — so deep-inside-valid-region
    blocks skip the iota/compare/select chain even in masked configs
    (previously any kv_lens/tail config sent EVERY block down the masked
    slow path).  Segment ids can flip anywhere inside a block, so they
    always take the masked path, guarded by ``run`` (block-skip
    predicate)."""
    masked = has_lens or has_seg or causal or needs_tail
    if not masked:
        compute(False)
        return
    if has_seg:
        if run is True:
            compute(True)
        else:
            pl.when(run)(lambda: compute(True))
        return
    conds = []
    if causal:
        # block wholly below the diagonal: every row sees every column
        conds.append((qi * block_q) >= (ki * block_k + block_k - 1))
    if has_lens:
        conds.append((ki * block_k + block_k) <= kvlen)
    if needs_tail:
        conds.append((ki * block_k + block_k) <= seq_k)
    full = conds[0]
    for c in conds[1:]:
        full = jnp.logical_and(full, c)
    if isinstance(full, (bool, int)):
        # every predicate was static (python grid coords, e.g. the
        # single-block backward) — no pl.when needed
        if run is True:
            compute(not full)
        else:
            pl.when(run)(lambda: compute(not full))
        return
    if run is True:
        pl.when(full)(lambda: compute(False))
        pl.when(jnp.logical_not(full))(lambda: compute(True))
    else:
        pl.when(run & full)(lambda: compute(False))
        pl.when(run & jnp.logical_not(full))(lambda: compute(True))


def _visible(qm, km):
    """The mask given as data, on one tile: ``qm`` (block_q, 2 or 3) holds
    a query's [reach, own(, floor)] and ``km`` (2, block_k) a key's [rank,
    own]; a query sees a key whose rank is at most its reach — and, where
    the queries carry a floor, at least that — or whose ``own`` is its own
    (the operands arrive with -1 and -2 for "none", which never meet).
    Integer compares, (block_q, block_k) bool."""
    ranked = km[0:1, :] <= qm[:, 0:1]
    if qm.shape[1] == 3:
        ranked = ranked & (km[0:1, :] >= qm[:, 2:3])
    return ranked | (km[1:2, :] == qm[:, 1:2])


def _visible_T(qm, km):
    """``_visible`` for the backward's transposed score block:
    ``qm`` (2 or 3, block_q), ``km`` (block_k, 2) -> (block_k, block_q)."""
    ranked = km[:, 0:1] <= qm[0:1, :]
    if qm.shape[0] == 3:
        ranked = ranked & (km[:, 0:1] >= qm[2:3, :])
    return ranked | (km[:, 1:2] == qm[1:2, :])


def _run_streamed_block(pl, compute, tile, qi, ki, block_q, block_k, causal,
                        has_lens, has_seg, needs_tail, kvlen, seq_k):
    """Whether and how a streamed kernel's grid step computes its block.
    Under a mask given as data ``tile`` is the block's entry in the
    scalar-prefetched summary — 0 no live pair (nothing runs), 1 some (the
    mask is applied), 2 all (it is not).  Otherwise (``tile`` None) a
    block wholly above the causal diagonal or wholly past the row's valid
    length is skipped, and the ladder picks the mask."""
    if tile is not None:
        pl.when(tile == 2)(lambda: compute(False))
        pl.when(tile == 1)(lambda: compute(True))
        return
    run = True
    if causal:
        run = (qi * block_q + block_q - 1) >= (ki * block_k)
    if has_lens:
        run = run & (ki * block_k < kvlen)
    _run_mask_specialized(pl, compute, run, qi, ki, block_q, block_k,
                          causal, has_lens, has_seg, needs_tail,
                          kvlen=kvlen, seq_k=seq_k)


def _tiles_first(kernel):
    """Under a scalar-prefetch grid a kernel is handed the prefetched
    arrays first: the tile summary goes on as ``tiles=``; the second, the
    table of blocks to fetch, is the index maps' alone."""
    def call(tiles_ref, fetch_ref, *refs):
        return kernel(*refs, tiles=tiles_ref)
    return call


def _fwd_kernel(q_ref, k_ref, v_ref, *rest, scale, causal, block_q, block_k,
                seq_k, seq_k_padded, n_k, has_lens, has_seg, pid_off=0,
                tiles=None, mask_heads=1):
    import jax.experimental.pallas as pl

    rest = list(rest)
    lens_ref = rest.pop(0) if has_lens else None
    qseg_ref = rest.pop(0) if has_seg else None
    kseg_ref = rest.pop(0) if has_seg else None
    qm_ref = rest.pop(0) if tiles is not None else None
    km_ref = rest.pop(0) if tiles is not None else None
    o_ref, lse_ref, m_ref, l_ref, acc_ref = rest

    # pid_off=1 on the BSHD grid (B, H, n_q, n_k); 0 on (B*H, n_q, n_k).
    # program_id(0) stays the lens/seg batch coordinate either way.
    bi = pl.program_id(0)
    qi = pl.program_id(1 + pid_off)
    ki = pl.program_id(2 + pid_off)
    # lens rides in SMEM as ONE whole-array block (Mosaic requires SMEM
    # blocks be full-dim or (8,128)-tiled); index by the grid's batch coord
    kvlen = lens_ref[bi, 0] if has_lens else None

    # static fast path (see _run_mask_specialized): skip the iota/compare/
    # select mask chain over the (block_q, block_k) score tile whenever
    # nothing can actually mask this block
    needs_tail = seq_k != seq_k_padded

    @pl.when(ki == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    def _compute(use_mask):
        # shape-agnostic reads: blocks are (1, bq, d) on the flat grid,
        # (1, bq, 1, d) on the BSHD grid — both squeeze to (bq, d)
        q = q_ref[...].reshape(block_q, q_ref.shape[-1])
        k = k_ref[...].reshape(block_k, k_ref.shape[-1])
        v = v_ref[...].reshape(block_k, v_ref.shape[-1])
        s = lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale

        # mask: padded K tail, plus causal upper triangle, plus the
        # variable-length / segment masks when present; or the mask given
        # as data, whose padding is in the operands
        if use_mask and tiles is not None:
            mask = _visible(qm_ref[0], km_ref[0])
            s = jnp.where(mask, s, _NEG_INF)
        elif use_mask:
            col = ki * block_k + lax.broadcasted_iota(jnp.int32,
                                                      (block_q, block_k), 1)
            mask = col < (kvlen if has_lens else seq_k)
            if causal:
                row = qi * block_q + lax.broadcasted_iota(
                    jnp.int32, (block_q, block_k), 0)
                mask = mask & (row >= col)
            if has_seg:
                mask = mask & (qseg_ref[0] == kseg_ref[0])  # (bq,1)==(1,bk)
            s = jnp.where(mask, s, _NEG_INF)

        m_prev = m_ref[...][:, :1]         # (block_q, 1); lanes replicated
        l_prev = l_ref[...][:, :1]
        m_blk = jnp.max(s, axis=-1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_blk)
        corr = jnp.exp(m_prev - m_new)
        # explicit zero on masked entries: in a fully-masked row m_new is
        # itself _NEG_INF, so exp(s - m_new) would be exp(0)=1 — the row
        # must instead stay empty (l==0 → out 0, lse pinned)
        p = jnp.exp(s - m_new)
        if use_mask:
            p = jnp.where(mask, p, 0.0)
        l_new = l_prev * corr + jnp.sum(p, axis=-1, keepdims=True)
        acc_ref[...] = acc_ref[...] * corr + lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)
        l_ref[...] = jnp.broadcast_to(l_new, l_ref.shape)

    tile = None if tiles is None else tiles[
        (bi // mask_heads * pl.num_programs(1 + pid_off) + qi) * n_k + ki]
    _run_streamed_block(pl, _compute, tile, qi, ki, block_q, block_k, causal,
                        has_lens, has_seg, needs_tail, kvlen, seq_k)

    @pl.when(ki == n_k - 1)
    def _finalize():
        l = l_ref[...][:, :1]
        m = m_ref[...][:, :1]
        o_ref[...] = (acc_ref[...] / jnp.where(l > 0, l, 1.0)).astype(
            o_ref.dtype).reshape(o_ref.shape)
        # lse for empty rows (fully masked / padded) pinned to 0 so the
        # backward recompute yields exp(-1e30 - 0) == 0, never NaN
        lse = jnp.where(l > 0, m + jnp.log(l), 0.0)      # (block_q, 1)
        lse_ref[...] = lse.reshape(lse_ref.shape)


def _head_lanes(x, h, heads):
    """``x`` (rows, lanes) holds ``heads`` heads side by side along the
    lanes: head ``h``'s lanes kept and the others zero, so a contraction
    over ALL the lanes is head h's own — on a 128-deep MXU the passes a
    64-deep contraction takes, and no lane is moved.  Static 64-lane
    slices, the other way, tie in the forward and lose 1.8% in the
    backward kernel (0.3386 against 0.3326 s of a 2.76 s trace; the step
    386.2 against 387.0 samples/s: my chip runs, PR 27)."""
    if heads == 1:
        return x
    width = x.shape[-1] // heads
    lane = lax.broadcasted_iota(jnp.int32, x.shape, 1)
    return jnp.where((lane >= h * width) & (lane < (h + 1) * width), x,
                     jnp.zeros_like(x))


def _join_heads(parts):
    """``parts[h]`` (rows, lanes) is right in head h's lanes — a product
    with the whole block of v, k or q — and holds another head's numbers
    elsewhere: the one lane-dense array that takes each head's lanes from
    its own part."""
    out = parts[-1]
    if len(parts) > 1:
        width = out.shape[-1] // len(parts)
        lane = lax.broadcasted_iota(jnp.int32, out.shape, 1)
        for h in range(len(parts) - 2, -1, -1):
            out = jnp.where(lane < (h + 1) * width, parts[h], out)
    return out


def _columns_as_rows(cols):
    """n (rows, 1) float32 columns -> (n, rows): per-row vectors laid
    along the lanes, as the backward's transposed score blocks broadcast
    them.  Stored as a column such a vector is one lane in 128 of its
    tiles in HBM — four times the bytes of a 64-wide output block — and
    XLA turns it round again before the backward.  The MXU does the turn,
    for all the columns at once: column i goes to lane i of one
    (rows, 128) array and an identity picks lane i into row i, at float32
    precision."""
    rows = cols[0].shape[0]
    lane = lax.broadcasted_iota(jnp.int32, (rows, _LANES), 1)
    spread = jnp.zeros((rows, _LANES), jnp.float32)
    for i, col in enumerate(cols):
        spread = jnp.where(lane == i, col, spread)
    pick = (lax.broadcasted_iota(jnp.int32, (8, _LANES), 0)
            == lax.broadcasted_iota(jnp.int32, (8, _LANES), 1))
    return lax.dot_general(pick.astype(jnp.float32), spread,
                           (((1,), (1,)), ((), ())),
                           precision=lax.Precision.HIGHEST,
                           preferred_element_type=jnp.float32)[:len(cols)]


def _head_vector(ref, h, heads, shape):
    """Head h's lse or delta row out of a block that holds one a head,
    (1, heads, 1, lanes); with one head, the block's only vector."""
    return (ref[...] if heads == 1 else ref[0, h]).reshape(shape)


def _fwd_kernel_single(q_ref, k_ref, v_ref, *rest, scale, causal, block_q,
                       block_k, seq_k, seq_k_padded, has_lens, has_seg,
                       pid_off=0, heads=1, lse_rows=False, has_mask=False):
    """Short-sequence forward: the whole K axis is ONE block, so the
    online-softmax streaming machinery — m/l VMEM scratch carried across
    K iterations, the per-iteration accumulator rescale, the init/
    finalize grid-edge phases — collapses to a single-pass softmax over
    one resident score tile.  Same mask ladder, same outputs (o, lse),
    no scratch at all.  ``heads`` > 1: the q, k, v and o blocks hold that
    many heads side by side along the lanes (``_head_lanes``), the lse
    block one vector a head; the softmax runs once a head, under one
    mask, and the store is one lane-dense block.  ``lse_rows``: the lse
    block is (1, heads, 1, block_q), a row a head along the lanes
    (``_columns_as_rows``), not (…, block_q, 1) columns.  ``has_mask``:
    the mask given as data (``_visible``), on every tile: one K block
    leaves nothing to skip."""
    import jax.experimental.pallas as pl

    rest = list(rest)
    lens_ref = rest.pop(0) if has_lens else None
    qseg_ref = rest.pop(0) if has_seg else None
    kseg_ref = rest.pop(0) if has_seg else None
    qm_ref = rest.pop(0) if has_mask else None
    km_ref = rest.pop(0) if has_mask else None
    o_ref, lse_ref = rest

    bi = pl.program_id(0)
    qi = pl.program_id(1 + pid_off)
    ki = 0
    kvlen = lens_ref[bi, 0] if has_lens else None
    needs_tail = seq_k != seq_k_padded

    def _mask():
        if has_mask:
            return _visible(qm_ref[0], km_ref[0])
        col = lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
        mask = col < (kvlen if has_lens else seq_k)
        if causal:
            row = qi * block_q + lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0)
            mask = mask & (row >= col)
        if has_seg:
            mask = mask & (qseg_ref[0] == kseg_ref[0])
        return mask

    def _compute(use_mask):
        q = q_ref[...].reshape(block_q, q_ref.shape[-1])
        k = k_ref[...].reshape(block_k, k_ref.shape[-1])
        v = v_ref[...].reshape(block_k, v_ref.shape[-1])
        mask, outs, lses = None, [], []
        for h in range(heads):
            s = lax.dot_general(_head_lanes(q, h, heads), k,
                                (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
            if use_mask:
                mask = _mask() if mask is None else mask
                s = jnp.where(mask, s, _NEG_INF)
            m = jnp.max(s, axis=-1, keepdims=True)
            # fully-masked rows: m == _NEG_INF makes exp(s - m) == 1 on the
            # masked entries — zero them so the row stays empty (l == 0)
            p = jnp.exp(s - m)
            if use_mask:
                p = jnp.where(mask, p, 0.0)
            l = jnp.sum(p, axis=-1, keepdims=True)
            acc = lax.dot_general(p.astype(v.dtype), v,
                                  (((1,), (0,)), ((), ())),
                                  preferred_element_type=jnp.float32)
            outs.append(acc / jnp.where(l > 0, l, 1.0))
            lses.append(jnp.where(l > 0, m + jnp.log(l), 0.0))
        o_ref[...] = _join_heads(outs).astype(o_ref.dtype).reshape(
            o_ref.shape)
        if lse_rows:
            rows = _columns_as_rows(lses)
            for h in range(heads):
                lse_ref[0, h] = rows[h:h + 1]
        else:
            lse_ref[...] = lses[0].reshape(lse_ref.shape)

    # run stays True: with a single K block every q block must execute
    # (its o/lse outputs have no other writer); fully-masked rows emit
    # exact zeros through the mask.  The ladder still specializes
    # blocks nothing can mask down to the mask-free path — but not under
    # a length mask: with one K block its mask-free body would serve only
    # the rows with no padding at all, and is a second copy of the
    # kernel's code: 0.13 s more a BERT layer in every trace and lowering
    # of the step (24 kernels, each compile and each eager call; 3 s of
    # the cell's 38 s set-up) for a forward of 1.520 against 1.504 ms and
    # a backward of 2.736 against 2.721 ms a call without it (my chip
    # runs, PR 27)
    if has_lens or has_mask:
        _compute(True)
    else:
        _run_mask_specialized(pl, _compute, True, qi, ki, block_q, block_k,
                              causal, has_lens, has_seg, needs_tail,
                              kvlen=kvlen, seq_k=seq_k)


def _pad_qkv(q, k, v, block_q, block_k):
    B, H, Tq, D = q.shape
    Tk = k.shape[2]
    pad_q = (-Tq) % block_q
    pad_k = (-Tk) % block_k
    pad_d = (-D) % 64          # Mosaic handles 64-lane minor tiles natively
    qp = jnp.pad(q, ((0, 0), (0, 0), (0, pad_q), (0, pad_d)))
    kp = jnp.pad(k, ((0, 0), (0, 0), (0, pad_k), (0, pad_d)))
    vp = jnp.pad(v, ((0, 0), (0, 0), (0, pad_k), (0, pad_d)))
    Tqp, Tkp, Dp = Tq + pad_q, Tk + pad_k, D + pad_d
    Hkv = k.shape[1]          # < H under grouped-query attention
    return (qp.reshape(B * H, Tqp, Dp), kp.reshape(B * Hkv, Tkp, Dp),
            vp.reshape(B * Hkv, Tkp, Dp), Tqp, Tkp, Dp)


def _kv_group(q, k):
    """Query heads per key-value head (1: plain multi-head attention).
    Head h of q reads key-value head h // group, so in the kernels' merged
    (B*H, ...) layouts row b of q meets row b // group of k and v: the
    BlockSpec index maps do the sharing and no repeated K/V exists in
    HBM."""
    H, Hkv = q.shape[1], k.shape[1]
    if H % Hkv:
        raise ValueError("%d query heads do not divide over %d key-value "
                         "heads" % (H, Hkv))
    return H // Hkv


def _kv_row(group):
    """Grid batch coordinate of q -> row of k/v."""
    return (lambda b: b) if group == 1 else (lambda b: b // group)


def _expand_mask_operands(kv_lens, q_segments, kv_segments, B, H, Tqp, Tkp,
                          true_tk=None, transposed=False):
    """Broadcast per-batch mask operands over heads into the kernels'
    (B*H, …) layouts: lens (BH, 1) int32, and segment ids shaped so they
    broadcast against the score block each kernel works on — forward
    scores (block_q, block_k): q as (BH, Tqp, 1) columns, kv as
    (BH, 1, Tkp) rows; ``transposed`` (backward, score blocks are
    (block_k, block_q)): q rows / kv columns.  q/kv padding positions get
    distinct sentinels (-1 / -2) so they never match anything."""
    lens = qs = ks = None
    if kv_lens is not None:
        lens = kv_lens.astype(jnp.int32)
        if true_tk is not None:
            # clamp to the true (unpadded) K length: the kernels' length
            # mask REPLACES the padded-tail mask, so an out-of-range
            # kv_lens would let zero-padded key rows attend
            lens = jnp.minimum(lens, true_tk)
        lens = jnp.broadcast_to(lens[:, None], (B, H)).reshape(B * H, 1)
    if q_segments is not None:
        Tq = q_segments.shape[1]
        qs = jnp.pad(q_segments.astype(jnp.int32), ((0, 0), (0, Tqp - Tq)),
                     constant_values=-1)
        qs = jnp.broadcast_to(qs[:, None, :], (B, H, Tqp)).reshape(
            (B * H, 1, Tqp) if transposed else (B * H, Tqp, 1))
        Tk = kv_segments.shape[1]
        ks = jnp.pad(kv_segments.astype(jnp.int32), ((0, 0), (0, Tkp - Tk)),
                     constant_values=-2)
        ks = jnp.broadcast_to(ks[:, None, :], (B, H, Tkp)).reshape(
            (B * H, Tkp, 1) if transposed else (B * H, 1, Tkp))
    return lens, qs, ks


def _check_mask_alone(q_mask, kv_mask, causal, kv_lens, q_segments):
    if (q_mask is None) != (kv_mask is None):
        raise ValueError("q_mask and kv_mask go together")
    if q_mask is not None and (causal or kv_lens is not None
                               or q_segments is not None):
        raise ValueError(
            "q_mask / kv_mask stand alone: say causal order, valid lengths "
            "and segments in the ranks, reaches and owns themselves")


def window_mask(batch, seq_q, seq_k, window):
    """A causal window as the mask's integers, from shapes alone (numpy,
    so a traced program holds them as constants): query i sees the
    ``window`` keys ``i - window < j <= i``, itself included.  Returns
    ``q_mask`` (batch, seq_q, 3) [reach = i, own = none, floor = i -
    window + 1] and ``kv_mask`` (batch, seq_k, 2) [rank = j, none]."""
    import numpy as onp
    pos_q, pos_k = onp.arange(seq_q), onp.arange(seq_k)
    # graftlint: disable-next=trace-host-sync -- numpy on purpose: shapes
    # and a static window in, constants of the traced program out
    q_mask = onp.stack([pos_q, onp.full(seq_q, -1), pos_q - window + 1], -1)
    kv_mask = onp.stack([pos_k, onp.full(seq_k, -1)], -1)
    return tuple(onp.broadcast_to(m.astype("int32"), (batch,) + m.shape)
                 for m in (q_mask, kv_mask))


def _mask_operands(q_mask, kv_mask, Tqp, Tkp):
    """The mask given as data, as the kernels read it: ``q_mask``
    (B, Tq, 2) [reach, own] or (B, Tq, 3) [reach, own, floor] and
    ``kv_mask`` (B, Tk, 2) [rank, own] -> int32 (B, Tqp, 2 or 3) and
    (B, Tkp, 2).  A negative ``own`` (none) becomes -1 on a query and -2
    on a key, so that equality alone decides; a padded query gets the
    reach no rank is under (and the floor no rank is over) and a padded
    key the rank no reach is over, so that padding needs no mask of its
    own.  Two integers a query stay two: the kernels' code for them is
    what it was."""
    qm, km = q_mask.astype(jnp.int32), kv_mask.astype(jnp.int32)
    reach = jnp.minimum(qm[..., 0], _RANK_NEVER - 1)
    q_own = jnp.where(qm[..., 1] < 0, -1, qm[..., 1])
    k_own = jnp.where(km[..., 1] < 0, -2, km[..., 1])
    pad_q, pad_k = Tqp - qm.shape[1], Tkp - km.shape[1]

    def padded(x, pad, value):
        return jnp.pad(x, ((0, 0), (0, pad)), constant_values=value)
    q_cols = [padded(reach, pad_q, _REACH_NONE), padded(q_own, pad_q, -1)]
    # graftlint: disable-next=retrace-shape-branch -- the mask's own width,
    # two integers a query or three: a property of the call, by design
    if qm.shape[-1] == 3:
        q_cols.append(padded(qm[..., 2], pad_q, _RANK_NEVER))
    return (jnp.stack(q_cols, axis=-1),
            jnp.stack([padded(km[..., 0], pad_k, _RANK_NEVER),
                       padded(k_own, pad_k, -2)], axis=-1))


def _tile_states(qm, km, block_q, block_k):
    """The per-tile summary of ``_mask_operands``' arrays, (B, n_q, n_k)
    int32, from each block's extremes and never from the pairs: 2 where
    every pair of the tile is live (the largest rank is under the
    smallest reach, and the smallest over the largest floor), 0 where none
    can be (the smallest rank is over the largest reach, or the largest
    rank of a key that has one is under the smallest floor — a tile under
    a window's band is dead as one over the diagonal is — and the blocks'
    ranges of ``own`` do not meet), else 1.
    A 0 is always true; a 1 may hold no live pair where the ``own``s of a
    block are not consecutive integers — such a tile is visited and
    masked to nothing."""
    B = qm.shape[0]
    reach, q_own = (qm[..., i].reshape(B, -1, block_q) for i in (0, 1))
    rank, k_own = (km[..., i].reshape(B, -1, block_k) for i in (0, 1))

    def own_range(own):
        some = own >= 0
        return (jnp.where(some, own, _RANK_NEVER).min(-1),
                jnp.where(some, own, -1).max(-1))
    (q_lo, q_hi), (k_lo, k_hi) = own_range(q_own), own_range(k_own)
    every = rank.max(-1)[:, None, :] <= reach.min(-1)[:, :, None]
    ranked = rank.min(-1)[:, None, :] <= reach.max(-1)[:, :, None]
    # graftlint: disable-next=retrace-shape-branch -- the mask's own width
    # (``_mask_operands``)
    if qm.shape[-1] == 3:
        floor = qm[..., 2].reshape(B, -1, block_q)
        # a key that never ranks (padding) is over every reach already
        ranked_hi = jnp.where(rank == _RANK_NEVER, _REACH_NONE, rank).max(-1)
        every = every & (rank.min(-1)[:, None, :]
                         >= floor.max(-1)[:, :, None])
        ranked = ranked & (ranked_hi[:, None, :]
                           >= floor.min(-1)[:, :, None])
    some = ranked | ((q_lo[:, :, None] <= k_hi[:, None, :])
                     & (k_lo[:, None, :] <= q_hi[:, :, None]))
    return jnp.where(every, 2, some.astype(jnp.int32))


def _states_at(q_mask, kv_mask, block_q, block_k):
    """``_tile_states`` of the public operands padded to whole blocks."""
    Tq, Tk = q_mask.shape[1], kv_mask.shape[1]
    return _tile_states(*_mask_operands(
        q_mask, kv_mask, Tq + (-Tq) % block_q, Tk + (-Tk) % block_k),
        block_q, block_k)


def _resident_block(live):
    """For each step of the last axis of ``live`` (bool), the block that
    should be in VMEM then: the step's own where it is live, else the
    last live one before it — the index map repeats it and nothing is
    fetched — or, before the first live one, that first."""
    n = live.shape[-1]
    idx = jnp.where(live, jnp.arange(n, dtype=jnp.int32), -1)
    last = lax.cummax(idx, axis=live.ndim - 1)
    first = jnp.argmax(live, axis=-1).astype(jnp.int32)[..., None]
    return jnp.where(last >= 0, last, first)


def mask_tiles(q_mask, kv_mask, head_dim, dtype="bfloat16", window=None):
    """``(visited, total)`` tiles of one masked ``flash_attention`` call
    a head row, forward and backward together (the forward's, ``flash_dq``'s
    and ``flash_dkv``'s grids), at the blocks ``attention_dispatch`` plans
    and from the summary the kernels skip by; float32 scalars.  (0, 0)
    where no streamed kernel would run (one K block, or no TPU: there is
    nothing to skip).  ``window``: the mask is ``window_mask``'s, whose
    calls plan their blocks by the band."""
    Tq, Tk = q_mask.shape[1], kv_mask.shape[1]
    plan = attention_dispatch(Tq, Tk, head_dim, dtype, census=False,
                              masked=True, window=window)
    if plan["kernel"] != "streaming":
        return jnp.zeros((), jnp.float32), jnp.zeros((), jnp.float32)
    block_q, block_k = plan["block_q"], plan["block_k"]
    Dp = head_dim + (-head_dim) % 64
    visited = total = 0
    for bq, calls in ((block_q, 1), (_bwd_block_q(
            block_q, block_k, Dp, jnp.dtype(dtype).itemsize), 2)):
        states = _states_at(q_mask, kv_mask, bq, block_k)
        visited = visited + calls * jnp.sum(states > 0)
        total = total + calls * states.size
    return visited.astype(jnp.float32), jnp.float32(total)


def pallas_flash_attention(q, k, v, causal=False, scale=None,
                           block_q: Optional[int] = None,
                           block_k: Optional[int] = None,
                           interpret: bool = False, return_lse: bool = False,
                           kv_lens=None, q_segments=None, kv_segments=None,
                           q_mask=None, kv_mask=None):
    # Default blocks come from tune_attention_blocks: (1024, 2048) on the
    # streaming path (v5e S=2048, D=64 fwd+bwd sweep: ~61 TF/s vs ~35 TF/s
    # for XLA dense attention), the whole lane-aligned K axis as one block
    # for S <= _SHORT_SEQ_MAX_TK, which routes to the single-pass kernel.
    """Raw kernel entry: q/k/v (B, H, T, D) → (B, H, Tq, D) [, lse].

    When the padded K axis fits ONE block (n_k == 1) the single-pass
    ``_fwd_kernel_single`` runs instead of the streaming online-softmax
    kernel — no m/l scratch carry, no accumulator rescale.

    ``kv_lens`` (B,) int masks keys at/after the per-row valid length —
    K blocks wholly past it are skipped, the partial block is masked
    inside the online softmax.  ``q_segments``/``kv_segments`` (B, T) int
    ids restrict attention to equal segments (packed-sequence masking,
    ref transformer.cc's masked softmax).  Fully-masked rows emit 0.

    ``q_mask`` (B, Tq, 2) / ``kv_mask`` (B, Tk, 2): a mask given as data
    (``flash_attention`` has the rule), in place of the others.  With the
    K axis streamed the kernel is ``flash_masked_fwd``: a per-tile summary
    of the operands (``_tile_states``) is scalar-prefetched, a tile with
    no live pair runs nothing and fetches nothing — its K/V index map
    repeats the block that is resident — a tile wholly live skips the
    mask, and a partial one applies it in float32."""
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    B, H, Tq, D = q.shape
    Tk = k.shape[2]
    scale = scale if scale is not None else D ** -0.5
    if (q_segments is None) != (kv_segments is None):
        raise ValueError("q_segments and kv_segments go together")
    _check_mask_alone(q_mask, kv_mask, causal, kv_lens, q_segments)

    if block_q is None or block_k is None:
        tq, tk = tune_attention_blocks(Tq, Tk, D, q.dtype,
                                       q_mask is not None)
        block_q = tq if block_q is None else block_q
        block_k = tk if block_k is None else block_k
    block_q = min(block_q, max(8, Tq))
    block_k = min(block_k, max(8, Tk))
    qp, kp, vp, Tqp, Tkp, Dp = _pad_qkv(q, k, v, block_q, block_k)
    kvb = _kv_row(_kv_group(q, k))
    n_q = Tqp // block_q
    n_k = Tkp // block_k
    lens, qs, ks = _expand_mask_operands(kv_lens, q_segments, kv_segments,
                                         B, H, Tqp, Tkp, true_tk=Tk)

    single = n_k == 1
    masked = q_mask is not None
    if masked:
        qm, km_cols = _mask_operands(q_mask, kv_mask, Tqp, Tkp)
        km = jnp.swapaxes(km_cols, 1, 2)      # keys along the lanes
    extra, extra_specs = [], []
    if masked and single:
        extra += [qm, km]
        extra_specs += [
            pl.BlockSpec((1, block_q, qm.shape[-1]),
                         lambda b, qi: (b // H, qi, 0)),
            pl.BlockSpec((1, 2, block_k), lambda b, qi: (b // H, 0, 0)),
        ]
    if lens is not None:
        extra.append(lens)
        extra_specs.append(pl.BlockSpec(
            lens.shape, lambda b, qi, ki=0: (0, 0),
            memory_space=pltpu.SMEM))
    if qs is not None:
        extra += [qs, ks]
        if single:
            extra_specs += [
                pl.BlockSpec((1, block_q, 1), lambda b, qi: (b, qi, 0)),
                pl.BlockSpec((1, 1, block_k), lambda b, qi: (b, 0, 0)),
            ]
        else:
            extra_specs += [
                pl.BlockSpec((1, block_q, 1), lambda b, qi, ki: (b, qi, 0)),
                pl.BlockSpec((1, 1, block_k), lambda b, qi, ki: (b, 0, ki)),
            ]

    common = dict(scale=scale, causal=causal, block_q=block_q,
                  block_k=block_k, seq_k=Tk, seq_k_padded=Tkp,
                  has_lens=lens is not None, has_seg=qs is not None)
    if single:
        out, lse = pl.pallas_call(
            functools.partial(_fwd_kernel_single, has_mask=masked, **common),
            grid=(B * H, n_q),
            in_specs=[
                pl.BlockSpec((1, block_q, Dp), lambda b, qi: (b, qi, 0)),
                pl.BlockSpec((1, block_k, Dp), lambda b, qi: (kvb(b), 0, 0)),
                pl.BlockSpec((1, block_k, Dp), lambda b, qi: (kvb(b), 0, 0)),
            ] + extra_specs,
            out_specs=[
                pl.BlockSpec((1, block_q, Dp), lambda b, qi: (b, qi, 0)),
                pl.BlockSpec((1, block_q, 1), lambda b, qi: (b, qi, 0)),
            ],
            out_shape=[
                jax.ShapeDtypeStruct((B * H, Tqp, Dp), q.dtype),
                jax.ShapeDtypeStruct((B * H, Tqp, 1), jnp.float32),
            ],
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "parallel")),
            interpret=interpret,
            name="flash_short_fwd",
        )(qp, kp, vp, *extra)
        out = out.reshape(B, H, Tqp, Dp)[:, :, :Tq, :D]
        if return_lse:
            return out, lse.reshape(B, H, Tqp)[:, :, :Tq]
        return out

    kernel = functools.partial(_fwd_kernel, n_k=n_k, **common)
    out_shape = [jax.ShapeDtypeStruct((B * H, Tqp, Dp), q.dtype),
                 jax.ShapeDtypeStruct((B * H, Tqp, 1), jnp.float32)]
    scratch = [pltpu.VMEM((block_q, _LANES), jnp.float32),
               pltpu.VMEM((block_q, _LANES), jnp.float32),
               pltpu.VMEM((block_q, Dp), jnp.float32)]
    if masked:
        states = _tile_states(qm, km_cols, block_q, block_k)
        fetch = _resident_block(states > 0)

        def kblk(b, qi, ki, tiles, fetch):
            return fetch[(b // H * n_q + qi) * n_k + ki]
        out, lse = pl.pallas_call(
            _tiles_first(functools.partial(kernel, mask_heads=H)),
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=2,
                grid=(B * H, n_q, n_k),
                in_specs=[
                    pl.BlockSpec((1, block_q, Dp),
                                 lambda b, qi, ki, *_: (b, qi, 0)),
                    pl.BlockSpec((1, block_k, Dp), lambda b, qi, ki, *t:
                                 (kvb(b), kblk(b, qi, ki, *t), 0)),
                    pl.BlockSpec((1, block_k, Dp), lambda b, qi, ki, *t:
                                 (kvb(b), kblk(b, qi, ki, *t), 0)),
                    pl.BlockSpec((1, block_q, qm.shape[-1]),
                                 lambda b, qi, ki, *_: (b // H, qi, 0)),
                    pl.BlockSpec((1, 2, block_k), lambda b, qi, ki, *t:
                                 (b // H, 0, kblk(b, qi, ki, *t))),
                ],
                out_specs=[
                    pl.BlockSpec((1, block_q, Dp),
                                 lambda b, qi, ki, *_: (b, qi, 0)),
                    pl.BlockSpec((1, block_q, 1),
                                 lambda b, qi, ki, *_: (b, qi, 0)),
                ],
                scratch_shapes=scratch),
            out_shape=out_shape,
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "parallel", "arbitrary")),
            interpret=interpret,
            name="flash_masked_fwd",
        )(states.reshape(-1), fetch.reshape(-1), qp, kp, vp, qm, km)
        out = out.reshape(B, H, Tqp, Dp)[:, :, :Tq, :D]
        if return_lse:
            return out, lse.reshape(B, H, Tqp)[:, :, :Tq]
        return out
    out, lse = pl.pallas_call(
        kernel,
        grid=(B * H, n_q, n_k),
        in_specs=[
            pl.BlockSpec((1, block_q, Dp), lambda b, qi, ki: (b, qi, 0)),
            pl.BlockSpec((1, block_k, Dp),
                         lambda b, qi, ki: (kvb(b), ki, 0)),
            pl.BlockSpec((1, block_k, Dp),
                         lambda b, qi, ki: (kvb(b), ki, 0)),
        ] + extra_specs,
        out_specs=[
            pl.BlockSpec((1, block_q, Dp), lambda b, qi, ki: (b, qi, 0)),
            pl.BlockSpec((1, block_q, 1), lambda b, qi, ki: (b, qi, 0)),
        ],
        out_shape=out_shape,
        scratch_shapes=scratch,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name="flash_stream_fwd",
    )(qp, kp, vp, *extra)
    out = out.reshape(B, H, Tqp, Dp)[:, :, :Tq, :D]
    if return_lse:
        return out, lse.reshape(B, H, Tqp)[:, :, :Tq]
    return out


def _swap_heads(x):
    """(B, T, H, D) <-> (B, H, T, D)."""
    return jnp.swapaxes(x, 1, 2)


def _heads_along_lanes(x, block):
    """(B, T, H, D) -> (B, Tp, H*D): the heads side by side along the
    lanes, as a projection wrote them — a reshape, no copy.  The kernels
    address a lane block of this array and the head width is never
    padded; only a T that its block does not divide is, with zero rows."""
    B, T, H, D = x.shape
    x = x.reshape(B, T, H * D)
    pad = (-T) % block
    return jnp.pad(x, ((0, 0), (0, pad), (0, 0))) if pad else x


def _first_rows(x, n, axis):
    """``x`` without the rows a padded T added along ``axis``."""
    return x if x.shape[axis] == n else lax.slice_in_dim(x, 0, n, axis=axis)


def pallas_flash_attention_bshd(q, k, v, causal=False, scale=None,
                                block_q: Optional[int] = None,
                                block_k: Optional[int] = None,
                                interpret: bool = False,
                                return_lse: bool = False, kv_lens=None):
    """Flash forward on (B, T, H, D) inputs — the layout Dense-projected
    activations already have, so no (B,T,H,D)→(B,H,T,D) copy stands
    before or after the kernel (24 ms of the 194 ms BERT-base step at 64
    rows of 512: ledger, PR 26, `copy`).  The kernels of
    :func:`pallas_flash_attention` on a (B, lane blocks, n_q[, n_k]) grid
    whose BlockSpecs take a lane block of the unpadded (B, T, H*D) array:
    one head where the head width is a multiple of 128, and with the K
    axis in one block two 64-wide heads (``_bshd_heads_per_block``).  Any
    other shape goes through the (B, H, T, D) kernels and their
    transposes.  Returns (B, Tq, H, D) [, lse (B, H, Tq)]."""
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    B, Tq, H, D = q.shape
    Tk = k.shape[1]
    scale = scale if scale is not None else D ** -0.5
    if block_q is None or block_k is None:
        tq, tk = tune_attention_blocks(Tq, Tk, D, q.dtype)
        block_q = tq if block_q is None else block_q
        block_k = tk if block_k is None else block_k
    block_q = min(block_q, max(8, Tq))
    block_k = min(block_k, max(8, Tk))
    single = Tk <= block_k
    heads = _bshd_heads_per_block(H, D, single)
    if not heads:
        res = pallas_flash_attention(
            _swap_heads(q), _swap_heads(k), _swap_heads(v), causal=causal,
            scale=scale, block_q=block_q, block_k=block_k,
            interpret=interpret, return_lse=True, kv_lens=kv_lens)
        return (_swap_heads(res[0]), res[1]) if return_lse \
            else _swap_heads(res[0])
    qp = _heads_along_lanes(q, block_q)
    kp = _heads_along_lanes(k, block_k)
    vp = _heads_along_lanes(v, block_k)
    Tqp, Tkp = qp.shape[1], kp.shape[1]
    n_q = Tqp // block_q
    n_k = Tkp // block_k
    lanes = heads * D

    extra, extra_specs = [], []
    if kv_lens is not None:
        lens = jnp.minimum(kv_lens.astype(jnp.int32), Tk).reshape(B, 1)
        extra.append(lens)
        extra_specs.append(pl.BlockSpec(
            lens.shape, lambda b, h, qi, ki=0: (0, 0),
            memory_space=pltpu.SMEM))

    def result(out, lse):
        # lse is (B, H, Tqp, 1) columns or (B, H, 1, Tqp) rows
        out = _first_rows(out, Tq, 1).reshape(B, Tq, H, D)
        if return_lse:
            return out, _first_rows(lse.reshape(B, H, Tqp), Tq, 2)
        return out

    common = dict(scale=scale, causal=causal, block_q=block_q,
                  block_k=block_k, seq_k=Tk, seq_k_padded=Tkp,
                  has_lens=kv_lens is not None, has_seg=False, pid_off=1)
    if single:
        # grid coordinate g is a lane block: ``heads`` heads of q, k, v
        # and o, and their lse rows g*heads .. g*heads + heads - 1
        return result(*pl.pallas_call(
            functools.partial(_fwd_kernel_single, heads=heads,
                              lse_rows=True, **common),
            grid=(B, H // heads, n_q),
            in_specs=[
                pl.BlockSpec((1, block_q, lanes),
                             lambda b, g, qi: (b, qi, g)),
                pl.BlockSpec((1, block_k, lanes),
                             lambda b, g, qi: (b, 0, g)),
                pl.BlockSpec((1, block_k, lanes),
                             lambda b, g, qi: (b, 0, g)),
            ] + extra_specs,
            out_specs=[
                pl.BlockSpec((1, block_q, lanes),
                             lambda b, g, qi: (b, qi, g)),
                pl.BlockSpec((1, heads, 1, block_q),
                             lambda b, g, qi: (b, g, 0, qi)),
            ],
            out_shape=[
                jax.ShapeDtypeStruct((B, Tqp, H * D), q.dtype),
                jax.ShapeDtypeStruct((B, H, 1, Tqp), jnp.float32),
            ],
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "parallel", "parallel")),
            interpret=interpret,
            name="flash_bshd_cols_fwd",
        )(qp, kp, vp, *extra))

    kernel = functools.partial(_fwd_kernel, n_k=n_k, **common)
    return result(*pl.pallas_call(
        kernel,
        grid=(B, H, n_q, n_k),
        in_specs=[
            pl.BlockSpec((1, block_q, D),
                         lambda b, h, qi, ki: (b, qi, h)),
            pl.BlockSpec((1, block_k, D),
                         lambda b, h, qi, ki: (b, ki, h)),
            pl.BlockSpec((1, block_k, D),
                         lambda b, h, qi, ki: (b, ki, h)),
        ] + extra_specs,
        out_specs=[
            pl.BlockSpec((1, block_q, D),
                         lambda b, h, qi, ki: (b, qi, h)),
            # trailing singleton keeps the block's last-two dims legal
            # ((block_q, 1): full-dim match on the minor axis)
            pl.BlockSpec((1, 1, block_q, 1),
                         lambda b, h, qi, ki: (b, h, qi, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, Tqp, H * D), q.dtype),
            jax.ShapeDtypeStruct((B, H, Tqp, 1), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, _LANES), jnp.float32),
            pltpu.VMEM((block_q, _LANES), jnp.float32),
            pltpu.VMEM((block_q, D), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
        name="flash_bshd_stream_fwd",
    )(qp, kp, vp, *extra))


# ---------------------------------------------------------------------------
# backward
# ---------------------------------------------------------------------------

def _scores_T(q, k, lse_row, scale, qi, ki, block_q, block_k, seq_k, causal,
              kvlen=None, qseg_row=None, kseg_col=None, use_mask=True,
              visible=None):
    """Recomputed transposed probability block pᵀ (block_k, block_q).
    ``visible``: the (block_k, block_q) mask given as data
    (``_visible_T``), in place of the others."""
    sT = lax.dot_general(k, q, (((1,), (1,)), ((), ())),
                         preferred_element_type=jnp.float32) * scale
    if use_mask and visible is not None:
        sT = jnp.where(visible, sT, _NEG_INF)
    elif use_mask:
        kcol = ki * block_k + lax.broadcasted_iota(jnp.int32,
                                                   (block_k, block_q), 0)
        mask = kcol < (seq_k if kvlen is None else kvlen)
        if causal:
            qrow = qi * block_q + lax.broadcasted_iota(jnp.int32,
                                                       (block_k, block_q), 1)
            mask = mask & (qrow >= kcol)
        if qseg_row is not None:
            mask = mask & (kseg_col == qseg_row)    # (bk,1)==(1,bq)
        sT = jnp.where(mask, sT, _NEG_INF)
    return jnp.exp(sT - lse_row)           # lse_row: (1, block_q)


def _bwd_unpack(rest, has_lens, has_seg, has_mask=False):
    rest = list(rest)
    lens_ref = rest.pop(0) if has_lens else None
    qseg_ref = rest.pop(0) if has_seg else None
    kseg_ref = rest.pop(0) if has_seg else None
    mask_refs = (rest.pop(0), rest.pop(0)) if has_mask else None
    return lens_ref, qseg_ref, kseg_ref, mask_refs, rest


def _delta_row(do, out):
    """δ = rowsum(dO ∘ O) of (rows, lanes) blocks, as the (1, rows) lane
    vector a transposed score block broadcasts; ``do`` with another
    head's lanes zero gives one head's.  The float32 products meet a row
    of ones on the MXU, which sums and transposes at once (a (rows, 1)
    column of sums would need a relayout), at float32 precision."""
    prod = do.astype(jnp.float32) * out.astype(jnp.float32)
    return lax.dot_general(jnp.ones((8, prod.shape[-1]), jnp.float32), prod,
                           (((1,), (1,)), ((), ())),
                           precision=lax.Precision.HIGHEST,
                           preferred_element_type=jnp.float32)[:1]


def _bwd_core(q_ref, k_ref, v_ref, do_ref, lse_ref, dlt_ref, qseg_ref,
              kseg_ref, has_seg, use_mask, qi, ki, scale, causal,
              block_q, block_k, seq_k, kvlen, head=0, heads=1,
              delta_of_out=False, mask_refs=None):
    """Shared recompute for all backward kernels: block reads, the
    transposed probability block pᵀ, and dsᵀ = pᵀ∘(dpᵀ − δ)·scale.
    Returns (q, k, v, do, pT, dsT).  ``heads`` > 1: the blocks hold that
    many heads side by side along the lanes, with one lse and delta row
    a head; pT and dsT are head ``head``'s, from q and do with the other
    heads' lanes zero (``_head_lanes``), and q, k, v, do come back
    whole.  ``delta_of_out``: ``dlt_ref`` is the forward's output block
    and δ is summed here (``_delta_row``), not read.  ``mask_refs``: the
    (2 or 3, block_q) and (block_k, 2) blocks of a mask given as data."""
    q = q_ref[...].reshape(block_q, q_ref.shape[-1])
    k = k_ref[...].reshape(block_k, k_ref.shape[-1])
    v = v_ref[...].reshape(block_k, v_ref.shape[-1])
    do = do_ref[...].reshape(block_q, do_ref.shape[-1])
    do_head = _head_lanes(do, head, heads)
    lse_row = _head_vector(lse_ref, head, heads, (1, block_q))
    if delta_of_out:
        dlt_row = _delta_row(do_head, dlt_ref[...].reshape(do.shape))
    else:
        dlt_row = _head_vector(dlt_ref, head, heads, (1, block_q))
    pT = _scores_T(_head_lanes(q, head, heads), k, lse_row, scale, qi, ki,
                   block_q, block_k, seq_k, causal, kvlen=kvlen,
                   qseg_row=qseg_ref[0] if has_seg else None,
                   kseg_col=kseg_ref[0] if has_seg else None,
                   use_mask=use_mask,
                   visible=_visible_T(mask_refs[0][0], mask_refs[1][0])
                   if use_mask and mask_refs is not None else None)
    dpT = lax.dot_general(v, do_head, (((1,), (1,)), ((), ())),
                          preferred_element_type=jnp.float32)
    dsT = pT * (dpT - dlt_row) * scale          # (block_k, block_q)
    return q, k, v, do, pT, dsT


def _dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, dlt_ref, *rest,
               scale, causal, block_q, block_k, seq_k, seq_k_padded, n_k,
               has_lens, has_seg, pid_off=0, tiles=None, mask_heads=1):
    import jax.experimental.pallas as pl

    lens_ref, qseg_ref, kseg_ref, mask_refs, rest = _bwd_unpack(
        rest, has_lens, has_seg, tiles is not None)
    dq_ref, acc_ref = rest

    qi = pl.program_id(1 + pid_off)
    ki = pl.program_id(2 + pid_off)
    kvlen = lens_ref[pl.program_id(0), 0] if has_lens else None
    needs_tail = seq_k != seq_k_padded

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    def _compute(use_mask):
        q, k, v, do, pT, dsT = _bwd_core(
            q_ref, k_ref, v_ref, do_ref, lse_ref, dlt_ref, qseg_ref,
            kseg_ref, has_seg, use_mask, qi, ki, scale, causal,
            block_q, block_k, seq_k, kvlen, mask_refs=mask_refs)
        acc_ref[...] += lax.dot_general(
            dsT.astype(q.dtype), k, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    tile = None if tiles is None else tiles[
        (pl.program_id(0) // mask_heads * pl.num_programs(1 + pid_off) + qi)
        * n_k + ki]
    _run_streamed_block(pl, _compute, tile, qi, ki, block_q, block_k, causal,
                        has_lens, has_seg, needs_tail, kvlen, seq_k)

    @pl.when(ki == n_k - 1)
    def _finalize():
        dq_ref[...] = acc_ref[...].astype(dq_ref.dtype).reshape(
            dq_ref.shape)


def _dqkv_fused_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, dlt_ref,
                       *rest, scale, causal, block_q, block_k, seq_k,
                       seq_k_padded, n_q, has_lens, has_seg, pid_off=0,
                       has_mask=False):
    """Single-K-block backward (n_k == 1): the score/dp recompute is
    shared, so the whole backward is 5 dots (s, dv, dp, dq, dk) instead
    of the split kernels' 7.  Grid (BH, n_q) sequential over q blocks:
    dq writes per-block, dk/dv accumulate in VMEM scratch."""
    import jax.experimental.pallas as pl

    lens_ref, qseg_ref, kseg_ref, mask_refs, rest = _bwd_unpack(
        rest, has_lens, has_seg, has_mask)
    dq_ref, dk_ref, dv_ref, dk_acc, dv_acc = rest

    qi = pl.program_id(1 + pid_off)
    ki = 0
    kvlen = lens_ref[pl.program_id(0), 0] if has_lens else None
    needs_tail = seq_k != seq_k_padded

    @pl.when(qi == 0)
    def _init():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    def _compute(use_mask):
        q, k, v, do, pT, dsT = _bwd_core(
            q_ref, k_ref, v_ref, do_ref, lse_ref, dlt_ref, qseg_ref,
            kseg_ref, has_seg, use_mask, qi, ki, scale, causal,
            block_q, block_k, seq_k, kvlen, mask_refs=mask_refs)
        dv_acc[...] += lax.dot_general(
            pT.astype(do.dtype), do, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        dq_ref[...] = lax.dot_general(
            dsT.astype(q.dtype), k, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32).astype(
                dq_ref.dtype).reshape(dq_ref.shape)
        dk_acc[...] += lax.dot_general(
            dsT.astype(q.dtype), q, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    # run stays True: every q block must execute (a skipped block would
    # leave its dq output unwritten); masked rows contribute exact zeros
    # through pT == 0.  The ladder still specializes causal full-blocks
    # to the mask-free path.
    _run_mask_specialized(pl, _compute, True, qi, ki, block_q, block_k,
                          causal, has_lens, has_seg or has_mask, needs_tail,
                          kvlen=kvlen, seq_k=seq_k)

    @pl.when(qi == n_q - 1)
    def _finalize():
        dk_ref[...] = dk_acc[...].astype(dk_ref.dtype).reshape(
            dk_ref.shape)
        dv_ref[...] = dv_acc[...].astype(dv_ref.dtype).reshape(
            dv_ref.shape)


def _dqkv_single_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, dlt_ref,
                        *rest, scale, causal, block_q, block_k, seq_k,
                        seq_k_padded, has_lens, has_seg, heads=1,
                        delta_of_out=False, has_mask=False):
    """Single-block backward (n_q == n_k == 1): the short-seq analogue of
    ``_dqkv_fused_kernel``.  With the whole (Tq, Tk) extent resident as
    one block there is no grid axis to stream over, so the dk/dv VMEM
    accumulators and the init/finalize phases disappear — one score/dp
    recompute, 5 dots, three direct output writes.  ``heads`` > 1: every
    block holds that many heads side by side along the lanes; the 5 dots
    run once a head against the WHOLE do, k and q blocks, and each of
    the three writes is one lane-dense block (``_join_heads``).
    ``delta_of_out``: the sixth operand is the forward's output block,
    not δ (``_bwd_core``)."""
    import jax.experimental.pallas as pl

    lens_ref, qseg_ref, kseg_ref, mask_refs, rest = _bwd_unpack(
        rest, has_lens, has_seg, has_mask)
    dq_ref, dk_ref, dv_ref = rest

    kvlen = lens_ref[pl.program_id(0), 0] if has_lens else None
    needs_tail = seq_k != seq_k_padded

    def _compute(use_mask):
        dv, dq, dk = [], [], []
        for h in range(heads):
            q, k, v, do, pT, dsT = _bwd_core(
                q_ref, k_ref, v_ref, do_ref, lse_ref, dlt_ref, qseg_ref,
                kseg_ref, has_seg, use_mask, 0, 0, scale, causal,
                block_q, block_k, seq_k, kvlen, head=h, heads=heads,
                delta_of_out=delta_of_out, mask_refs=mask_refs)
            dv.append(lax.dot_general(
                pT.astype(do.dtype), do, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32))
            dq.append(lax.dot_general(
                dsT.astype(q.dtype), k, (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32))
            dk.append(lax.dot_general(
                dsT.astype(q.dtype), q, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32))
        for ref, parts in ((dv_ref, dv), (dq_ref, dq), (dk_ref, dk)):
            ref[...] = _join_heads(parts).astype(ref.dtype).reshape(
                ref.shape)

    if has_lens or has_mask:
        _compute(True)      # one body, as in _fwd_kernel_single
    else:
        _run_mask_specialized(pl, _compute, True, 0, 0, block_q, block_k,
                              causal, has_lens, has_seg, needs_tail,
                              kvlen=kvlen, seq_k=seq_k)


def _dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, dlt_ref, *rest,
                scale, causal, block_q, block_k, seq_k, seq_k_padded, n_q,
                has_lens, has_seg, pid_off=0, group=1, tiles=None,
                mask_heads=1):
    import jax.experimental.pallas as pl

    lens_ref, qseg_ref, kseg_ref, mask_refs, rest = _bwd_unpack(
        rest, has_lens, has_seg, tiles is not None)
    dk_ref, dv_ref, dk_acc, dv_acc = rest

    ki = pl.program_id(1 + pid_off)
    # the sequential axis: n_q q blocks, for each of the ``group`` query
    # heads that share this key-value head in turn
    step = pl.program_id(2 + pid_off)
    qi = step if group == 1 else step % n_q
    kvlen = lens_ref[pl.program_id(0), 0] if has_lens else None
    needs_tail = seq_k != seq_k_padded

    @pl.when(step == 0)
    def _init():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    def _compute(use_mask):
        q, k, v, do, pT, dsT = _bwd_core(
            q_ref, k_ref, v_ref, do_ref, lse_ref, dlt_ref, qseg_ref,
            kseg_ref, has_seg, use_mask, qi, ki, scale, causal,
            block_q, block_k, seq_k, kvlen, mask_refs=mask_refs)
        dv_acc[...] += lax.dot_general(
            pT.astype(do.dtype), do, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        dk_acc[...] += lax.dot_general(
            dsT.astype(q.dtype), q, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    # ``mask_heads`` is the key-value heads a batch row here; dk/dv of keys
    # past the valid length are zero: such a block is skipped too
    tile = None if tiles is None else tiles[
        (pl.program_id(0) // mask_heads * n_q + qi)
        * pl.num_programs(1 + pid_off) + ki]
    _run_streamed_block(pl, _compute, tile, qi, ki, block_q, block_k, causal,
                        has_lens, has_seg, needs_tail, kvlen, seq_k)

    @pl.when(step == group * n_q - 1)
    def _finalize():
        dk_ref[...] = dk_acc[...].astype(dk_ref.dtype).reshape(
            dk_ref.shape)
        dv_ref[...] = dv_acc[...].astype(dv_ref.dtype).reshape(
            dv_ref.shape)


def _masked_split_bwd(pl, pltpu, operands, masks, common, dims, interpret):
    """``flash_masked_dq`` and ``flash_masked_dkv``: the split backward
    under a mask given as data, on ``pallas_flash_attention_bwd``'s padded
    operands.  Both grids are the unmasked kernels'; each step reads its
    tile's state from the scalar-prefetched summary, and the operand that
    the sequential axis streams — K and V for dq, the q side for dk/dv —
    is addressed through the table of resident blocks, so a dead tile's
    step fetches nothing.  Returns the padded (dq, dk, dv)."""
    qp, kp, vp, dop, lsep, dltp = operands
    qm, qm_rows, km = masks
    B, H, group, n_q, n_k, Dp = dims
    Hkv = H // group
    kvb = _kv_row(group)
    block_q, block_k = common["block_q"], common["block_k"]
    states = _tile_states(qm, km, block_q, block_k)       # (B, n_q, n_k)
    live = states > 0
    tiles = states.reshape(-1)

    def kblk(b, qi, ki, tiles, fetch):
        return fetch[(b // H * n_q + qi) * n_k + ki]
    dq = pl.pallas_call(
        _tiles_first(functools.partial(_dq_kernel, n_k=n_k, mask_heads=H,
                                       **common)),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(B * H, n_q, n_k),
            in_specs=[
                pl.BlockSpec((1, block_q, Dp),
                             lambda b, qi, ki, *_: (b, qi, 0)),
                pl.BlockSpec((1, block_k, Dp), lambda b, qi, ki, *t:
                             (kvb(b), kblk(b, qi, ki, *t), 0)),
                pl.BlockSpec((1, block_k, Dp), lambda b, qi, ki, *t:
                             (kvb(b), kblk(b, qi, ki, *t), 0)),
                pl.BlockSpec((1, block_q, Dp),
                             lambda b, qi, ki, *_: (b, qi, 0)),
                pl.BlockSpec((1, 1, block_q),
                             lambda b, qi, ki, *_: (b, 0, qi)),
                pl.BlockSpec((1, 1, block_q),
                             lambda b, qi, ki, *_: (b, 0, qi)),
                pl.BlockSpec((1, qm_rows.shape[1], block_q),
                             lambda b, qi, ki, *_: (b // H, 0, qi)),
                pl.BlockSpec((1, block_k, 2), lambda b, qi, ki, *t:
                             (b // H, kblk(b, qi, ki, *t), 0)),
            ],
            out_specs=pl.BlockSpec((1, block_q, Dp),
                                   lambda b, qi, ki, *_: (b, qi, 0)),
            scratch_shapes=[pltpu.VMEM((block_q, Dp), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct(qp.shape, qp.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name="flash_masked_dq",
    )(tiles, _resident_block(live).reshape(-1), qp, kp, vp, dop, lsep, dltp,
      qm_rows, km)

    # dk/dv: a key-value head's sequential axis walks the q blocks of its
    # ``group`` query heads in turn (step j: head j // n_q, block
    # j % n_q), every head under the same column of the summary
    steps = group * n_q
    walk = jnp.tile(jnp.swapaxes(live, 1, 2), (1, 1, group))  # (B,n_k,steps)

    def step(b, ki, j, tiles, fetch):
        return fetch[(b // Hkv * n_k + ki) * steps + j]

    def q_block(b, ki, j, *t):
        at = step(b, ki, j, *t)
        return b * group + at // n_q, at % n_q

    def rows(index):                 # (row, q block) -> a block index
        return lambda b, ki, j, *t: index(*q_block(b, ki, j, *t))
    dk, dv = pl.pallas_call(
        _tiles_first(functools.partial(_dkv_kernel, n_q=n_q, group=group,
                                       mask_heads=Hkv, **common)),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(B * Hkv, n_k, steps),
            in_specs=[
                pl.BlockSpec((1, block_q, Dp), rows(lambda r, i: (r, i, 0))),
                pl.BlockSpec((1, block_k, Dp),
                             lambda b, ki, j, *_: (b, ki, 0)),
                pl.BlockSpec((1, block_k, Dp),
                             lambda b, ki, j, *_: (b, ki, 0)),
                pl.BlockSpec((1, block_q, Dp), rows(lambda r, i: (r, i, 0))),
                pl.BlockSpec((1, 1, block_q), rows(lambda r, i: (r, 0, i))),
                pl.BlockSpec((1, 1, block_q), rows(lambda r, i: (r, 0, i))),
                pl.BlockSpec((1, qm_rows.shape[1], block_q),
                             lambda b, ki, j, *t: (
                                 b // Hkv, 0, step(b, ki, j, *t) % n_q)),
                pl.BlockSpec((1, block_k, 2),
                             lambda b, ki, j, *_: (b // Hkv, ki, 0)),
            ],
            out_specs=[
                pl.BlockSpec((1, block_k, Dp),
                             lambda b, ki, j, *_: (b, ki, 0)),
                pl.BlockSpec((1, block_k, Dp),
                             lambda b, ki, j, *_: (b, ki, 0)),
            ],
            scratch_shapes=[pltpu.VMEM((block_k, Dp), jnp.float32),
                            pltpu.VMEM((block_k, Dp), jnp.float32)]),
        out_shape=[jax.ShapeDtypeStruct(kp.shape, kp.dtype),
                   jax.ShapeDtypeStruct(vp.shape, vp.dtype)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name="flash_masked_dkv",
    )(tiles, _resident_block(walk).reshape(-1), qp, kp, vp, dop, lsep, dltp,
      qm_rows, km)
    return dq, dk, dv


def pallas_flash_attention_bwd(q, k, v, out, lse, do, causal=False,
                               scale=None, block_q: Optional[int] = None,
                               block_k: Optional[int] = None,
                               interpret: bool = False,
                               kv_lens=None, q_segments=None,
                               kv_segments=None, q_mask=None, kv_mask=None):
    """Flash backward: (dq, dk, dv) without materialising (Tq, Tk).
    Under ``q_mask`` / ``kv_mask`` with the K axis streamed the split
    kernels are ``flash_masked_dq`` and ``flash_masked_dkv``: each skips
    the tiles with no live pair by the summary at ITS blocks, and its
    index maps fetch nothing for them (``pallas_flash_attention``)."""
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    B, H, Tq, D = q.shape
    Tk = k.shape[2]
    scale = scale if scale is not None else D ** -0.5
    _check_mask_alone(q_mask, kv_mask, causal, kv_lens, q_segments)
    if block_q is None or block_k is None:
        tq, tk = tune_attention_blocks(Tq, Tk, D, q.dtype,
                                       q_mask is not None)
        block_q = tq if block_q is None else block_q
        block_k = tk if block_k is None else block_k
    block_q = min(block_q, max(8, Tq))
    block_k = min(block_k, max(8, Tk))
    # the backward picks its own q block under its own VMEM budget
    block_q = _bwd_block_q(block_q, block_k, D + (-D) % 64,
                           q.dtype.itemsize)

    # delta = rowsum(dO ∘ O) — one cheap fused elementwise+reduce pass
    delta = jnp.sum(do.astype(jnp.float32) * out.astype(jnp.float32),
                    axis=-1)                                   # (B,H,Tq)

    qp, kp, vp, Tqp, Tkp, Dp = _pad_qkv(q, k, v, block_q, block_k)
    group = _kv_group(q, k)
    kvb = _kv_row(group)
    Hkv = H // group
    pad_q = Tqp - Tq
    dop = jnp.pad(do, ((0, 0), (0, 0), (0, pad_q), (0, Dp - D))).reshape(
        B * H, Tqp, Dp)
    # rows (BH, 1, Tqp): the lse/delta vectors live along lanes so kernels
    # broadcast them against transposed score blocks with no relayout
    lsep = jnp.pad(lse, ((0, 0), (0, 0), (0, pad_q))).reshape(
        B * H, 1, Tqp)
    dltp = jnp.pad(delta, ((0, 0), (0, 0), (0, pad_q))).reshape(
        B * H, 1, Tqp)
    n_q = Tqp // block_q
    n_k = Tkp // block_k

    # mask operands, bwd orientation: q segments as lane rows, kv segments
    # as sublane columns (scores are transposed in the backward kernels)
    lens, qs_row, ks_col = _expand_mask_operands(
        kv_lens, q_segments, kv_segments, B, H, Tqp, Tkp, true_tk=Tk,
        transposed=True)

    common = dict(scale=scale, causal=causal, block_q=block_q,
                  block_k=block_k, seq_k=Tk, seq_k_padded=Tkp,
                  has_lens=lens is not None, has_seg=qs_row is not None)
    masked = q_mask is not None
    if masked:
        # queries along the lanes, keys down the sublanes
        qm, km = _mask_operands(q_mask, kv_mask, Tqp, Tkp)
        qm_rows = jnp.swapaxes(qm, 1, 2)

    def per_kv_head(d):
        """(B*H, Tkp, Dp) dk or dv of the one-K-block kernels, which
        write one per QUERY head, summed over each key-value head's
        group."""
        d = d.reshape(B, Hkv, group, Tkp, Dp)
        d = d[:, :, 0] if group == 1 else \
            jnp.sum(d.astype(jnp.float32), axis=2).astype(d.dtype)
        return d[:, :, :Tk, :D]

    if n_k == 1:
        # single-K-block fast path: ONE fused kernel recomputes the
        # score/dp pair once and emits dq, dk, dv together — 5 dots
        # instead of the split kernels' 7 (both the S=2048 bench shape
        # and BERT's S=512 land here with the default block_k=2048)
        fused_extra, fused_especs = [], []
        if lens is not None:
            fused_extra.append(lens)
            fused_especs.append(pl.BlockSpec(
                lens.shape, lambda b, qi=0: (0, 0),
                memory_space=pltpu.SMEM))
        if n_q == 1:
            # short-seq fast path: the whole extent is one block — no
            # q streaming, no dk/dv scratch accumulators (see
            # _dqkv_single_kernel)
            if qs_row is not None:
                fused_extra += [qs_row, ks_col]
                fused_especs += [
                    pl.BlockSpec((1, 1, block_q), lambda b: (b, 0, 0)),
                    pl.BlockSpec((1, block_k, 1), lambda b: (b, 0, 0)),
                ]
            if masked:
                fused_extra += [qm_rows, km]
                fused_especs += [
                    pl.BlockSpec((1, qm_rows.shape[1], block_q),
                                 lambda b: (b // H, 0, 0)),
                    pl.BlockSpec((1, block_k, 2), lambda b: (b // H, 0, 0)),
                ]
            dq, dk, dv = pl.pallas_call(
                functools.partial(_dqkv_single_kernel, has_mask=masked,
                                  **common),
                grid=(B * H,),
                in_specs=[
                    pl.BlockSpec((1, block_q, Dp), lambda b: (b, 0, 0)),
                    pl.BlockSpec((1, block_k, Dp), lambda b: (kvb(b), 0, 0)),
                    pl.BlockSpec((1, block_k, Dp), lambda b: (kvb(b), 0, 0)),
                    pl.BlockSpec((1, block_q, Dp), lambda b: (b, 0, 0)),
                    pl.BlockSpec((1, 1, block_q), lambda b: (b, 0, 0)),
                    pl.BlockSpec((1, 1, block_q), lambda b: (b, 0, 0)),
                ] + fused_especs,
                out_specs=[
                    pl.BlockSpec((1, block_q, Dp), lambda b: (b, 0, 0)),
                    pl.BlockSpec((1, block_k, Dp), lambda b: (b, 0, 0)),
                    pl.BlockSpec((1, block_k, Dp), lambda b: (b, 0, 0)),
                ],
                out_shape=[
                    jax.ShapeDtypeStruct((B * H, Tqp, Dp), q.dtype),
                    jax.ShapeDtypeStruct((B * H, Tkp, Dp), k.dtype),
                    jax.ShapeDtypeStruct((B * H, Tkp, Dp), v.dtype),
                ],
                compiler_params=pltpu.CompilerParams(
                    dimension_semantics=("parallel",)),
                interpret=interpret,
                name="flash_dqkv_single",
            )(qp, kp, vp, dop, lsep, dltp, *fused_extra)
            dq = dq.reshape(B, H, Tqp, Dp)[:, :, :Tq, :D]
            return dq, per_kv_head(dk), per_kv_head(dv)
        if qs_row is not None:
            fused_extra += [qs_row, ks_col]
            fused_especs += [
                pl.BlockSpec((1, 1, block_q), lambda b, qi: (b, 0, qi)),
                pl.BlockSpec((1, block_k, 1), lambda b, qi: (b, 0, 0)),
            ]
        if masked:
            fused_extra += [qm_rows, km]
            fused_especs += [
                pl.BlockSpec((1, qm_rows.shape[1], block_q),
                             lambda b, qi: (b // H, 0, qi)),
                pl.BlockSpec((1, block_k, 2), lambda b, qi: (b // H, 0, 0)),
            ]
        dq, dk, dv = pl.pallas_call(
            functools.partial(_dqkv_fused_kernel, n_q=n_q, has_mask=masked,
                              **common),
            grid=(B * H, n_q),
            in_specs=[
                pl.BlockSpec((1, block_q, Dp), lambda b, qi: (b, qi, 0)),
                pl.BlockSpec((1, block_k, Dp), lambda b, qi: (kvb(b), 0, 0)),
                pl.BlockSpec((1, block_k, Dp), lambda b, qi: (kvb(b), 0, 0)),
                pl.BlockSpec((1, block_q, Dp), lambda b, qi: (b, qi, 0)),
                pl.BlockSpec((1, 1, block_q), lambda b, qi: (b, 0, qi)),
                pl.BlockSpec((1, 1, block_q), lambda b, qi: (b, 0, qi)),
            ] + fused_especs,
            out_specs=[
                pl.BlockSpec((1, block_q, Dp), lambda b, qi: (b, qi, 0)),
                pl.BlockSpec((1, block_k, Dp), lambda b, qi: (b, 0, 0)),
                pl.BlockSpec((1, block_k, Dp), lambda b, qi: (b, 0, 0)),
            ],
            out_shape=[
                jax.ShapeDtypeStruct((B * H, Tqp, Dp), q.dtype),
                jax.ShapeDtypeStruct((B * H, Tkp, Dp), k.dtype),
                jax.ShapeDtypeStruct((B * H, Tkp, Dp), v.dtype),
            ],
            scratch_shapes=[pltpu.VMEM((block_k, Dp), jnp.float32),
                            pltpu.VMEM((block_k, Dp), jnp.float32)],
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "arbitrary")),
            interpret=interpret,
            name="flash_dqkv_fused",
        )(qp, kp, vp, dop, lsep, dltp, *fused_extra)
        dq = dq.reshape(B, H, Tqp, Dp)[:, :, :Tq, :D]
        return dq, per_kv_head(dk), per_kv_head(dv)

    def unpadded(dq, dk, dv):
        return (dq.reshape(B, H, Tqp, Dp)[:, :, :Tq, :D],
                dk.reshape(B, Hkv, Tkp, Dp)[:, :, :Tk, :D],
                dv.reshape(B, Hkv, Tkp, Dp)[:, :, :Tk, :D])

    if masked:
        return unpadded(*_masked_split_bwd(
            pl, pltpu, (qp, kp, vp, dop, lsep, dltp), (qm, qm_rows, km),
            common, (B, H, group, n_q, n_k, Dp), interpret))

    def extra_for(kv_idx, q_idx, q_row=lambda b, i, j: b, lens=lens,
                  ks_col=ks_col):
        # kv_idx/q_idx map grid coords -> (k-block index, q-block index),
        # q_row the grid's batch coordinate -> the row of q's operands
        ops, specs = [], []
        if lens is not None:
            ops.append(lens)
            specs.append(pl.BlockSpec(
                lens.shape, lambda b, i, j: (0, 0),
                memory_space=pltpu.SMEM))
        if qs_row is not None:
            ops += [qs_row, ks_col]
            specs += [
                pl.BlockSpec((1, 1, block_q),
                             lambda b, i, j: (q_row(b, i, j), 0,
                                              q_idx(i, j))),
                pl.BlockSpec((1, block_k, 1),
                             lambda b, i, j: (b, kv_idx(i, j), 0)),
            ]
        return ops, specs

    dq_extra, dq_especs = extra_for(lambda i, j: j, lambda i, j: i)
    qkv_specs = [
        pl.BlockSpec((1, block_q, Dp), lambda b, qi, ki: (b, qi, 0)),
        pl.BlockSpec((1, block_k, Dp), lambda b, qi, ki: (kvb(b), ki, 0)),
        pl.BlockSpec((1, block_k, Dp), lambda b, qi, ki: (kvb(b), ki, 0)),
        pl.BlockSpec((1, block_q, Dp), lambda b, qi, ki: (b, qi, 0)),
        pl.BlockSpec((1, 1, block_q), lambda b, qi, ki: (b, 0, qi)),
        pl.BlockSpec((1, 1, block_q), lambda b, qi, ki: (b, 0, qi)),
    ] + dq_especs
    dq = pl.pallas_call(
        functools.partial(_dq_kernel, n_k=n_k, **common),
        grid=(B * H, n_q, n_k),
        in_specs=qkv_specs,
        out_specs=pl.BlockSpec((1, block_q, Dp),
                               lambda b, qi, ki: (b, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((B * H, Tqp, Dp), q.dtype),
        scratch_shapes=[pltpu.VMEM((block_q, Dp), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name="flash_dq",
    )(qp, kp, vp, dop, lsep, dltp, *dq_extra)

    # dk/dv: one grid row per KEY-VALUE head; its sequential axis walks
    # the q blocks of every query head of the group in turn (j // n_q is
    # the head within the group, j % n_q its q block), so the group's sum
    # is the kernel's own accumulation
    if group == 1:
        q_row, q_blk = (lambda b, ki, j: b), (lambda j: j)
        kv_extra, kv_especs = extra_for(lambda i, j: i, lambda i, j: j)
    else:
        q_row = lambda b, ki, j: b * group + j // n_q
        q_blk = lambda j: j % n_q
        lens_kv, _, ks_kv = _expand_mask_operands(
            kv_lens, q_segments, kv_segments, B, Hkv, Tqp, Tkp, true_tk=Tk,
            transposed=True)
        kv_extra, kv_especs = extra_for(
            lambda i, j: i, lambda i, j: q_blk(j), q_row=q_row,
            lens=lens_kv, ks_col=ks_kv)
    kv_specs = [
        pl.BlockSpec((1, block_q, Dp),
                     lambda b, ki, j: (q_row(b, ki, j), q_blk(j), 0)),
        pl.BlockSpec((1, block_k, Dp), lambda b, ki, j: (b, ki, 0)),
        pl.BlockSpec((1, block_k, Dp), lambda b, ki, j: (b, ki, 0)),
        pl.BlockSpec((1, block_q, Dp),
                     lambda b, ki, j: (q_row(b, ki, j), q_blk(j), 0)),
        pl.BlockSpec((1, 1, block_q),
                     lambda b, ki, j: (q_row(b, ki, j), 0, q_blk(j))),
        pl.BlockSpec((1, 1, block_q),
                     lambda b, ki, j: (q_row(b, ki, j), 0, q_blk(j))),
    ] + kv_especs
    dk, dv = pl.pallas_call(
        functools.partial(_dkv_kernel, n_q=n_q, group=group, **common),
        grid=(B * Hkv, n_k, group * n_q),
        in_specs=kv_specs,
        out_specs=[
            pl.BlockSpec((1, block_k, Dp), lambda b, ki, qi: (b, ki, 0)),
            pl.BlockSpec((1, block_k, Dp), lambda b, ki, qi: (b, ki, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B * Hkv, Tkp, Dp), k.dtype),
            jax.ShapeDtypeStruct((B * Hkv, Tkp, Dp), v.dtype),
        ],
        scratch_shapes=[pltpu.VMEM((block_k, Dp), jnp.float32),
                        pltpu.VMEM((block_k, Dp), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name="flash_dkv",
    )(qp, kp, vp, dop, lsep, dltp, *kv_extra)

    return unpadded(dq, dk, dv)


def pallas_flash_attention_bwd_bshd(q, k, v, out, lse, do, causal=False,
                                    scale=None, block_q: Optional[int] = None,
                                    block_k: Optional[int] = None,
                                    interpret: bool = False, kv_lens=None):
    """Flash backward on (B, T, H, D) operands (lse from the BSHD
    forward, (B, H, Tq)): (dq, dk, dv) in BSHD, written as lane blocks of
    (B, T, H*D) arrays — no pad, no slice, no transpose
    (:func:`pallas_flash_attention_bshd` has the layout).  Where the
    whole extent is one q and one K block, ONE kernel a lane block
    (``_dqkv_single_kernel``, two 64-wide heads or one head of a multiple
    of 128); past that, heads of a multiple of 128 run the split dq +
    dk/dv pair, and any other shape the (B, H, T, D) kernels."""
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    B, Tq, H, D = q.shape
    Tk = k.shape[1]
    scale = scale if scale is not None else D ** -0.5
    if block_q is None or block_k is None:
        tq, tk = tune_attention_blocks(Tq, Tk, D, q.dtype)
        block_q = tq if block_q is None else block_q
        block_k = tk if block_k is None else block_k
    block_q = min(block_q, max(8, Tq))
    block_k = min(block_k, max(8, Tk))
    # the working set is reckoned at whole 128-lane blocks
    bwd_block_q = _bwd_block_q(block_q, block_k, D + (-D) % _LANES,
                               q.dtype.itemsize)
    single = Tq <= bwd_block_q and Tk <= block_k
    heads = _bshd_heads_per_block(H, D, single)
    if not heads:
        grads = pallas_flash_attention_bwd(
            _swap_heads(q), _swap_heads(k), _swap_heads(v),
            _swap_heads(out), lse, _swap_heads(do), causal=causal,
            scale=scale, block_q=block_q, block_k=block_k,
            interpret=interpret, kv_lens=kv_lens)
        return tuple(_swap_heads(g) for g in grads)
    block_q = bwd_block_q

    qp = _heads_along_lanes(q, block_q)
    dop = _heads_along_lanes(do, block_q)
    kp = _heads_along_lanes(k, block_k)
    vp = _heads_along_lanes(v, block_k)
    Tqp, Tkp = qp.shape[1], kp.shape[1]

    def lane_rows(x):
        # (B, H, Tq) -> (B, H, 1, Tqp): a vector a head along the lanes,
        # head-major like the grid
        pad = Tqp - Tq
        return (jnp.pad(x, ((0, 0), (0, 0), (0, pad))) if pad
                else x).reshape(B, H, 1, Tqp)

    lsep = lane_rows(lse)
    n_q = Tqp // block_q
    n_k = Tkp // block_k

    lens = None
    if kv_lens is not None:
        lens = jnp.minimum(kv_lens.astype(jnp.int32), Tk).reshape(B, 1)

    common = dict(scale=scale, causal=causal, block_q=block_q,
                  block_k=block_k, seq_k=Tk, seq_k_padded=Tkp,
                  has_lens=lens is not None, has_seg=False)

    def lens_specs():
        if lens is None:
            return [], []
        return [lens], [pl.BlockSpec(lens.shape,
                                     lambda b, h, i=0, j=0: (0, 0),
                                     memory_space=pltpu.SMEM)]

    def result(dq, dk, dv):
        return (_first_rows(dq, Tq, 1).reshape(B, Tq, H, D),
                _first_rows(dk, Tk, 1).reshape(B, Tk, H, D),
                _first_rows(dv, Tk, 1).reshape(B, Tk, H, D))

    lops, lspecs = lens_specs()
    if single:
        # one kernel a lane block g: ``heads`` heads of every operand and
        # gradient and their lse rows g*heads .. g*heads + heads - 1; the
        # kernel sums δ = rowsum(dO ∘ O) itself from the forward's output
        # block (in XLA that sum over 64 of 768 lanes is a relayout of a
        # float32 (B, T, H*D) array: 100 MB a BERT-base layer)
        lanes = heads * D
        return result(*pl.pallas_call(
            functools.partial(_dqkv_single_kernel, heads=heads,
                              delta_of_out=True, **common),
            grid=(B, H // heads),
            in_specs=[
                pl.BlockSpec((1, block_q, lanes), lambda b, g: (b, 0, g)),
                pl.BlockSpec((1, block_k, lanes), lambda b, g: (b, 0, g)),
                pl.BlockSpec((1, block_k, lanes), lambda b, g: (b, 0, g)),
                pl.BlockSpec((1, block_q, lanes), lambda b, g: (b, 0, g)),
                pl.BlockSpec((1, heads, 1, block_q),
                             lambda b, g: (b, g, 0, 0)),
                pl.BlockSpec((1, block_q, lanes), lambda b, g: (b, 0, g)),
            ] + lspecs,
            out_specs=[
                pl.BlockSpec((1, block_q, lanes), lambda b, g: (b, 0, g)),
                pl.BlockSpec((1, block_k, lanes), lambda b, g: (b, 0, g)),
                pl.BlockSpec((1, block_k, lanes), lambda b, g: (b, 0, g)),
            ],
            out_shape=[
                jax.ShapeDtypeStruct((B, Tqp, H * D), q.dtype),
                jax.ShapeDtypeStruct((B, Tkp, H * D), k.dtype),
                jax.ShapeDtypeStruct((B, Tkp, H * D), v.dtype),
            ],
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "parallel")),
            interpret=interpret,
            name="flash_bshd_cols_dqkv",
        )(qp, kp, vp, dop, lsep, _heads_along_lanes(out, block_q), *lops))

    # delta = rowsum(dO ∘ O), emitted directly in (B, H, Tq) order — the
    # einsum output order makes XLA fuse the transpose into the reduce
    dltp = lane_rows(jnp.einsum("bqhd,bqhd->bhq", do.astype(jnp.float32),
                                out.astype(jnp.float32)))
    qkv_specs = [
        pl.BlockSpec((1, block_q, D), lambda b, h, qi, ki: (b, qi, h)),
        pl.BlockSpec((1, block_k, D), lambda b, h, qi, ki: (b, ki, h)),
        pl.BlockSpec((1, block_k, D), lambda b, h, qi, ki: (b, ki, h)),
        pl.BlockSpec((1, block_q, D), lambda b, h, qi, ki: (b, qi, h)),
        pl.BlockSpec((1, 1, 1, block_q),
                     lambda b, h, qi, ki: (b, h, 0, qi)),
        pl.BlockSpec((1, 1, 1, block_q),
                     lambda b, h, qi, ki: (b, h, 0, qi)),
    ] + lspecs
    dq = pl.pallas_call(
        functools.partial(_dq_kernel, n_k=n_k, pid_off=1, **common),
        grid=(B, H, n_q, n_k),
        in_specs=qkv_specs,
        out_specs=pl.BlockSpec((1, block_q, D),
                               lambda b, h, qi, ki: (b, qi, h)),
        out_shape=jax.ShapeDtypeStruct((B, Tqp, H * D), q.dtype),
        scratch_shapes=[pltpu.VMEM((block_q, D), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
        name="flash_bshd_dq",
    )(qp, kp, vp, dop, lsep, dltp, *lops)

    lops, lspecs = lens_specs()
    kv_specs = [
        pl.BlockSpec((1, block_q, D), lambda b, h, ki, qi: (b, qi, h)),
        pl.BlockSpec((1, block_k, D), lambda b, h, ki, qi: (b, ki, h)),
        pl.BlockSpec((1, block_k, D), lambda b, h, ki, qi: (b, ki, h)),
        pl.BlockSpec((1, block_q, D), lambda b, h, ki, qi: (b, qi, h)),
        pl.BlockSpec((1, 1, 1, block_q),
                     lambda b, h, ki, qi: (b, h, 0, qi)),
        pl.BlockSpec((1, 1, 1, block_q),
                     lambda b, h, ki, qi: (b, h, 0, qi)),
    ] + lspecs
    dk, dv = pl.pallas_call(
        functools.partial(_dkv_kernel, n_q=n_q, pid_off=1, **common),
        grid=(B, H, n_k, n_q),
        in_specs=kv_specs,
        out_specs=[
            pl.BlockSpec((1, block_k, D),
                         lambda b, h, ki, qi: (b, ki, h)),
            pl.BlockSpec((1, block_k, D),
                         lambda b, h, ki, qi: (b, ki, h)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, Tkp, H * D), k.dtype),
            jax.ShapeDtypeStruct((B, Tkp, H * D), v.dtype),
        ],
        scratch_shapes=[pltpu.VMEM((block_k, D), jnp.float32),
                        pltpu.VMEM((block_k, D), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
        name="flash_bshd_dkv",
    )(qp, kp, vp, dop, lsep, dltp, *lops)
    return result(dq, dk, dv)


# ---------------------------------------------------------------------------
# public op with custom VJP
# ---------------------------------------------------------------------------

def per_batch_shard(fn, operands, replicated=(), summed=None):
    """A kernel call inside a program whose batch GSPMD shards goes through
    ``parallel.mesh.per_batch_shard`` (imported late: ``parallel`` imports
    the op registry)."""
    from ..parallel.mesh import per_batch_shard as shard
    return shard(fn, operands, replicated=replicated, summed=summed)


def _int_zero_cotangent(x):
    """Cotangent for integer-valued primals (mask operands): float0 zeros,
    or None when the primal was absent."""
    if x is None:
        return None
    import numpy as onp
    return onp.zeros(x.shape, jax.dtypes.float0)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 10))
def flash_attention(q, k, v, causal=False, scale=None, kv_lens=None,
                    q_segments=None, kv_segments=None, q_mask=None,
                    kv_mask=None, window=None):
    """Fused attention: Pallas kernels on TPU, jnp blockwise elsewhere.

    softmax(q·kᵀ·scale [+ masks])·v over (B, H, T, D) inputs; ``k`` and
    ``v`` may have fewer heads, (B, H / g, T, D): grouped-query attention,
    query head h on key-value head h // g, shared through the kernels'
    index maps.  Masking:
    ``causal`` (static), ``kv_lens`` (B,) per-row valid key length
    (padding mask — blocks past the length are skipped, not just masked),
    and ``q_segments``/``kv_segments`` (B, T) packed-sequence ids.
    Rows with no visible key return 0.

    Or a mask given as data, in place of the three: ``q_mask`` (B, Tq, 2)
    holds each query's [reach, own] and ``kv_mask`` (B, Tk, 2) each key's
    [rank, own], integers; query i sees key j iff ``rank[j] <= reach[i]``
    or ``own[j] == own[i] >= 0`` (a negative ``own`` is none).  A third
    integer a query, ``q_mask`` (B, Tq, 3) [reach, own, floor], bounds the
    ranks from below as well: ``floor[i] <= rank[j] <= reach[i]`` (absent:
    no floor).  Causal
    order is rank = reach = position; packed documents are ``own``s;
    block diffusion's row of clean and noised halves is both (a clean
    key's rank its block, a noised key's ``2**31 - 1`` — never —, a
    noised query's reach the block before its own, and the noised
    tokens' ``own`` their block).  On the chip the kernels visit only the
    tiles that hold a live pair (``pallas_flash_attention``).

    ``window`` (static, with ``causal`` and nothing else): query i sees
    the ``window`` keys ``i - window < j <= i``.  It is that mask, made
    from the shapes (``window_mask``: floor = i - window + 1), so the
    tiles under the band are skipped as those over the diagonal are; a
    window that covers the row is plain causal attention and runs as
    such."""
    return _flash_fwd(q, k, v, causal, scale, kv_lens, q_segments,
                      kv_segments, q_mask, kv_mask, window)[0]


def _reference_attention(q, k, v, causal, scale, kv_lens=None,
                         q_segments=None, kv_segments=None, q_mask=None,
                         kv_mask=None):
    group = _kv_group(q, k)
    # graftlint: disable-next=trace-tracer-branch -- group is a Python int
    # from the operands' static shapes
    if group > 1:
        # off the chip the shared heads are repeated (the kernels' index
        # maps share them instead); autodiff sums the group's gradients
        k, v = jnp.repeat(k, group, axis=1), jnp.repeat(v, group, axis=1)
    if kv_lens is None and q_segments is None and q_mask is None:
        from ..parallel.ring_attention import blockwise_attention
        return blockwise_attention(q, k, v, causal=causal, scale=scale)
    # masked dense oracle (test/CPU path): additive -inf mask, fp32 softmax
    D = q.shape[-1]
    Tq, Tk = q.shape[2], k.shape[2]
    sc = scale if scale is not None else D ** -0.5
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k).astype(jnp.float32) * sc
    mask = jnp.ones((q.shape[0], 1, Tq, Tk), bool)
    if kv_lens is not None:
        mask = mask & (jnp.arange(Tk)[None, None, None, :]
                       < kv_lens[:, None, None, None])
    if q_segments is not None:
        mask = mask & (q_segments[:, None, :, None]
                       == kv_segments[:, None, None, :])
    # graftlint: disable-next=trace-tracer-branch -- causal is the op's
    # static (nondiff) argument
    if causal:
        mask = mask & (jnp.arange(Tq)[:, None] >= jnp.arange(Tk)[None, :])
    if q_mask is not None:
        reach, q_own = q_mask[:, None, :, None, 0], q_mask[:, None, :, None, 1]
        rank, k_own = kv_mask[:, None, None, :, 0], kv_mask[:, None, None, :, 1]
        ranked = rank <= reach
        # graftlint: disable-next=retrace-shape-branch -- the mask's own
        # width (``_mask_operands``)
        if q_mask.shape[-1] == 3:
            ranked = ranked & (rank >= q_mask[:, None, :, None, 2])
        mask = mask & (ranked | ((k_own == q_own) & (q_own >= 0)))
    s = jnp.where(mask, s, _NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    # fully-masked rows: uniform softmax garbage -> force exact zeros,
    # matching the kernel's l==0 convention
    any_visible = jnp.any(mask, axis=-1, keepdims=True)
    p = jnp.where(any_visible, p, 0.0).astype(q.dtype)
    return jnp.einsum("bhqk,bhkd->bhqd", p, v)


def _tiles_visited(q, k, q_mask, kv_mask, on_tpu, window=None):
    """The forward's tiles a head row that a mask given as data leaves
    live, at the planned blocks, where the mask is there to be read — an
    eager call, or a window's integers (numpy, from the shapes), on a TPU
    with the K axis streamed; None otherwise."""
    if isinstance(q_mask, jax.core.Tracer) \
            or isinstance(kv_mask, jax.core.Tracer):
        return None
    plan = attention_dispatch(q.shape[2], k.shape[2], q.shape[3], q.dtype,
                              on_tpu=on_tpu, census=False, masked=True,
                              window=window)
    if plan["kernel"] != "streaming":
        return None
    # concrete operands — an eager call's, or a window's, made from the
    # shapes — are summed now, inside a traced program too
    with jax.ensure_compile_time_eval():
        states = _states_at(jnp.asarray(q_mask), jnp.asarray(kv_mask),
                            plan["block_q"], plan["block_k"])
        # graftlint: disable-next=trace-host-sync -- a traced mask
        # returned above
        return int(jnp.sum(states > 0))


def _windowed(q, k, causal, window, q_mask, kv_mask, *others):
    """``flash_attention``'s ``window`` as the masked call it is: returns
    ``(causal, q_mask, kv_mask, window)`` with the mask made from the
    shapes (numpy) where the window is narrower than the row, and plain
    causal attention (window None) where it covers it."""
    if window is None:
        return causal, q_mask, kv_mask, None
    # graftlint: disable-next=trace-tracer-branch -- causal and window are
    # the op's static (nondiff) arguments: Python values at trace time
    if not causal or window < 1 or q_mask is not None or any(
            o is not None for o in others):
        raise ValueError("window=%r goes with causal=True and no other "
                         "mask, and holds at least the query's own key"
                         % (window,))
    # graftlint: disable-next=* -- a static window against the row's
    # length: which kernels run is a property of the shape, by design
    if window >= k.shape[2]:
        return True, None, None, None
    return (False,) + window_mask(q.shape[0], q.shape[2], k.shape[2],
                                  window) + (window,)


def _flash_fwd(q, k, v, causal, scale, kv_lens, q_segments, kv_segments,
               q_mask=None, kv_mask=None, window=None):
    given = (q_mask, kv_mask)
    causal, q_mask, kv_mask, window = _windowed(
        q, k, causal, window, q_mask, kv_mask, kv_lens, q_segments)
    on_tpu = _context.on_tpu(q)
    plan = attention_dispatch(
        q.shape[2], k.shape[2], q.shape[3], q.dtype, on_tpu=on_tpu,
        masked=q_mask is not None, window=window,
        tiles_visited=None if q_mask is None else _tiles_visited(
            q, k, q_mask, kv_mask, on_tpu, window))
    # graftlint: disable-next=trace-tracer-branch -- the plan is Python
    # values: the dispatcher reads shapes, a dtype and a host-side count
    if plan["kernel"] != "dense_fallback":
        out, lse = per_batch_shard(
            lambda q, k, v, kl, qs, ks, qm, km: pallas_flash_attention(
                q, k, v, causal=causal, scale=scale, return_lse=True,
                block_q=plan["block_q"], block_k=plan["block_k"],
                kv_lens=kl, q_segments=qs, kv_segments=ks, q_mask=qm,
                kv_mask=km),
            (q, k, v, kv_lens, q_segments, kv_segments, q_mask, kv_mask))
        return out, (q, k, v, out, lse, kv_lens, q_segments, kv_segments)\
            + given
    _check_mask_alone(q_mask, kv_mask, causal, kv_lens, q_segments)
    out = _reference_attention(q, k, v, causal, scale, kv_lens, q_segments,
                               kv_segments, q_mask, kv_mask)
    return out, (q, k, v, None, None, kv_lens, q_segments, kv_segments) \
        + given


def _flash_bwd(causal, scale, window, res, g):
    q, k, v, out, lse, kv_lens, q_segments, kv_segments = res[:8]
    # the residuals hold the CALLER's mask (None under a window, which is
    # made from the shapes again): the cotangents below are theirs
    given = res[8:]
    causal, q_mask, kv_mask, window = _windowed(q, k, causal, window, *given)
    if lse is not None:
        # re-consult the dispatcher (trace-time, deterministic) for the
        # forward's blocks: custom_vjp residuals cannot carry static
        # ints.  census=False: the shape was counted at the forward trace
        plan = attention_dispatch(q.shape[2], k.shape[2], q.shape[3],
                                  q.dtype, census=False,
                                  masked=q_mask is not None, window=window)
        dq, dk, dv = per_batch_shard(
            lambda q, k, v, out, lse, g, kl, qs, ks, qm, km:
            pallas_flash_attention_bwd(
                q, k, v, out, lse, g, causal=causal, scale=scale,
                block_q=plan["block_q"], block_k=plan["block_k"],
                kv_lens=kl, q_segments=qs, kv_segments=ks, q_mask=qm,
                kv_mask=km),
            (q, k, v, out, lse, g, kv_lens, q_segments, kv_segments, q_mask,
             kv_mask))
    else:
        # recompute-based VJP through the memory-linear jnp path
        _, vjp = jax.vjp(
            lambda q_, k_, v_: _reference_attention(
                q_, k_, v_, causal, scale, kv_lens, q_segments, kv_segments,
                q_mask, kv_mask),
            q, k, v)
        dq, dk, dv = vjp(g)
    return (dq, dk, dv) + tuple(_int_zero_cotangent(m) for m in (
        kv_lens, q_segments, kv_segments) + given)


flash_attention.defvjp(_flash_fwd, _flash_bwd)


@register("_contrib_flash_attention", aliases=("flash_attention",))
def _flash_attention_op(queries, keys, values, causal: bool = False,
                        scale: Optional[float] = None, kv_lens=None,
                        q_segments=None, kv_segments=None, q_mask=None,
                        kv_mask=None, window: Optional[int] = None):
    """Fused multi-head attention op (TPU-native counterpart of the
    reference's ``_contrib_interleaved_matmul_selfatt_*`` pipeline,
    src/operator/contrib/transformer.cc).  The mask operands follow
    causal/scale so pre-mask positional callers keep working."""
    return flash_attention(queries, keys, values, causal, scale, kv_lens,
                           q_segments, kv_segments, q_mask, kv_mask, window)


@register("_contrib_attention_mask_tiles", num_outputs=2,
          differentiable=False, aliases=("attention_mask_tiles",))
def _attention_mask_tiles_op(q_mask, kv_mask, head_dim: int = 128,
                             dtype: str = "bfloat16",
                             window: Optional[int] = None):
    """``mask_tiles``: the tiles a masked ``flash_attention`` call visits
    and has, a head row, forward and backward."""
    return mask_tiles(q_mask, kv_mask, head_dim, dtype, window)


# --- BSHD (batch, seq, heads, head_dim) entry: no layout transposes ----

@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def flash_attention_bshd(q, k, v, causal=False, scale=None, kv_lens=None):
    """Fused attention over (B, T, H, D) operands — the natural layout of
    Dense-projected activations.  Functionally identical to
    :func:`flash_attention` on the transposed inputs, but where
    ``bshd_layout_fits`` the Pallas kernels address heads as lane blocks
    of the (B, T, H*D) arrays, so neither forward nor backward
    materializes a (B,T,H,D)↔(B,H,T,D) transpose."""
    return _flash_bshd_fwd(q, k, v, causal, scale, kv_lens)[0]


def _flash_bshd_fwd(q, k, v, causal, scale, kv_lens):
    plan = attention_dispatch(q.shape[1], k.shape[1], q.shape[3], q.dtype,
                              on_tpu=_context.on_tpu(q),
                              bshd_heads=q.shape[2])
    if plan["kernel"] != "dense_fallback":
        out, lse = per_batch_shard(
            lambda q, k, v, kl: pallas_flash_attention_bshd(
                q, k, v, causal=causal, scale=scale, return_lse=True,
                block_q=plan["block_q"], block_k=plan["block_k"],
                kv_lens=kl),
            (q, k, v, kv_lens))
        return out, (q, k, v, out, lse, kv_lens)
    out = _reference_attention(_swap_heads(q), _swap_heads(k),
                               _swap_heads(v), causal, scale, kv_lens,
                               None, None)
    return _swap_heads(out), (q, k, v, None, None, kv_lens)


def _flash_bshd_bwd(causal, scale, res, g):
    q, k, v, out, lse, kv_lens = res
    if lse is not None:
        # the forward's blocks, as in _flash_bwd (BSHD layout: T is axis
        # 1, D axis 3)
        plan = attention_dispatch(q.shape[1], k.shape[1], q.shape[3],
                                  q.dtype, census=False)
        dq, dk, dv = per_batch_shard(
            lambda q, k, v, out, lse, g, kl:
            pallas_flash_attention_bwd_bshd(
                q, k, v, out, lse, g, causal=causal, scale=scale,
                block_q=plan["block_q"], block_k=plan["block_k"],
                kv_lens=kl),
            (q, k, v, out, lse, g, kv_lens))
    else:
        _, vjp = jax.vjp(
            lambda q_, k_, v_: _swap_heads(_reference_attention(
                _swap_heads(q_), _swap_heads(k_), _swap_heads(v_), causal,
                scale, kv_lens, None, None)),
            q, k, v)
        dq, dk, dv = vjp(g)
    return dq, dk, dv, _int_zero_cotangent(kv_lens)


flash_attention_bshd.defvjp(_flash_bshd_fwd, _flash_bshd_bwd)


@register("_contrib_flash_attention_bshd",
          aliases=("flash_attention_bshd",))
def _flash_attention_bshd_op(queries, keys, values, causal: bool = False,
                             scale: Optional[float] = None, kv_lens=None):
    """BSHD-layout fused attention (see :func:`flash_attention_bshd`)."""
    return flash_attention_bshd(queries, keys, values, causal, scale,
                                kv_lens)
