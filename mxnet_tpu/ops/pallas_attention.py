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
           "bshd_layout_fits"]

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


def tune_attention_blocks(seq_q, seq_k, head_dim, dtype="bfloat16"):
    """Default (block_q, block_k) for a (S, D, dtype) attention shape.

    Short K axes (<= _SHORT_SEQ_MAX_TK) take the whole axis as one
    lane-aligned block so the single-pass kernel applies; long axes take
    (512, 2048) — the largest blocks today's v5e compiler accepts with
    every mask variant, forward and backward — halved until the working
    sets honour their VMEM budgets (large D / fp32 shapes)."""
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
                       on_tpu=None, census=True, bshd_heads=None):
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
    block_q, block_k = tune_attention_blocks(seq_q, seq_k, head_dim, dtype)
    kernel = "short_seq" if seq_k <= block_k else "streaming"
    per_block = 0 if bshd_heads is None else _bshd_heads_per_block(
        bshd_heads, head_dim, kernel == "short_seq")
    layout = ("bhsd", "bshd", "bshd_pair")[per_block]
    per_block = max(per_block, 1)
    # per-shape dispatch accounting: this runs at TRACE time (once per
    # compiled shape, not per step), so the journal is a census of which
    # kernel every shape in the run got
    if census:
        telemetry.inc("attention.kernel.%s" % kernel)
        telemetry.inc("attention.layout.%s" % layout)
        telemetry.event("attention_dispatch", kernel, seq_q=int(seq_q),
                        seq_k=int(seq_k), head_dim=int(head_dim),
                        dtype=str(dtype), block_q=block_q,
                        block_k=block_k, layout=layout,
                        heads_per_block=per_block)
    return {"kernel": kernel, "block_q": block_q, "block_k": block_k,
            "layout": layout, "heads_per_block": per_block}


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


def _fwd_kernel(q_ref, k_ref, v_ref, *rest, scale, causal, block_q, block_k,
                seq_k, seq_k_padded, n_k, has_lens, has_seg, pid_off=0):
    import jax.experimental.pallas as pl

    rest = list(rest)
    lens_ref = rest.pop(0) if has_lens else None
    qseg_ref = rest.pop(0) if has_seg else None
    kseg_ref = rest.pop(0) if has_seg else None
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
        # variable-length / segment masks when present
        if use_mask:
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

    run = True
    if causal:
        # skip blocks entirely above the diagonal
        run = (qi * block_q + block_q - 1) >= (ki * block_k)
    if has_lens:
        # skip K blocks entirely past this batch row's valid length
        run = run & (ki * block_k < kvlen)
    _run_mask_specialized(pl, _compute, run, qi, ki, block_q, block_k,
                          causal, has_lens, has_seg, needs_tail,
                          kvlen=kvlen, seq_k=seq_k)

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
                       pid_off=0, heads=1, lse_rows=False):
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
    (``_columns_as_rows``), not (…, block_q, 1) columns."""
    import jax.experimental.pallas as pl

    rest = list(rest)
    lens_ref = rest.pop(0) if has_lens else None
    qseg_ref = rest.pop(0) if has_seg else None
    kseg_ref = rest.pop(0) if has_seg else None
    o_ref, lse_ref = rest

    bi = pl.program_id(0)
    qi = pl.program_id(1 + pid_off)
    ki = 0
    kvlen = lens_ref[bi, 0] if has_lens else None
    needs_tail = seq_k != seq_k_padded

    def _mask():
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
    if has_lens:
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


def pallas_flash_attention(q, k, v, causal=False, scale=None,
                           block_q: Optional[int] = None,
                           block_k: Optional[int] = None,
                           interpret: bool = False, return_lse: bool = False,
                           kv_lens=None, q_segments=None, kv_segments=None):
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
    ref transformer.cc's masked softmax).  Fully-masked rows emit 0."""
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    B, H, Tq, D = q.shape
    Tk = k.shape[2]
    scale = scale if scale is not None else D ** -0.5
    if (q_segments is None) != (kv_segments is None):
        raise ValueError("q_segments and kv_segments go together")

    if block_q is None or block_k is None:
        tq, tk = tune_attention_blocks(Tq, Tk, D, q.dtype)
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
    extra, extra_specs = [], []
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
            functools.partial(_fwd_kernel_single, **common),
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
        out_shape=[
            jax.ShapeDtypeStruct((B * H, Tqp, Dp), q.dtype),
            jax.ShapeDtypeStruct((B * H, Tqp, 1), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, _LANES), jnp.float32),
            pltpu.VMEM((block_q, _LANES), jnp.float32),
            pltpu.VMEM((block_q, Dp), jnp.float32),
        ],
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
              kvlen=None, qseg_row=None, kseg_col=None, use_mask=True):
    """Recomputed transposed probability block pᵀ (block_k, block_q)."""
    sT = lax.dot_general(k, q, (((1,), (1,)), ((), ())),
                         preferred_element_type=jnp.float32) * scale
    if use_mask:
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


def _bwd_unpack(rest, has_lens, has_seg):
    rest = list(rest)
    lens_ref = rest.pop(0) if has_lens else None
    qseg_ref = rest.pop(0) if has_seg else None
    kseg_ref = rest.pop(0) if has_seg else None
    return lens_ref, qseg_ref, kseg_ref, rest


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
              delta_of_out=False):
    """Shared recompute for all backward kernels: block reads, the
    transposed probability block pᵀ, and dsᵀ = pᵀ∘(dpᵀ − δ)·scale.
    Returns (q, k, v, do, pT, dsT).  ``heads`` > 1: the blocks hold that
    many heads side by side along the lanes, with one lse and delta row
    a head; pT and dsT are head ``head``'s, from q and do with the other
    heads' lanes zero (``_head_lanes``), and q, k, v, do come back
    whole.  ``delta_of_out``: ``dlt_ref`` is the forward's output block
    and δ is summed here (``_delta_row``), not read."""
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
                   use_mask=use_mask)
    dpT = lax.dot_general(v, do_head, (((1,), (1,)), ((), ())),
                          preferred_element_type=jnp.float32)
    dsT = pT * (dpT - dlt_row) * scale          # (block_k, block_q)
    return q, k, v, do, pT, dsT


def _dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, dlt_ref, *rest,
               scale, causal, block_q, block_k, seq_k, seq_k_padded, n_k,
               has_lens, has_seg, pid_off=0):
    import jax.experimental.pallas as pl

    lens_ref, qseg_ref, kseg_ref, rest = _bwd_unpack(rest, has_lens, has_seg)
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
            block_q, block_k, seq_k, kvlen)
        acc_ref[...] += lax.dot_general(
            dsT.astype(q.dtype), k, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    run = True
    if causal:
        run = (qi * block_q + block_q - 1) >= (ki * block_k)
    if has_lens:
        run = run & (ki * block_k < kvlen)
    _run_mask_specialized(pl, _compute, run, qi, ki, block_q, block_k,
                          causal, has_lens, has_seg, needs_tail,
                          kvlen=kvlen, seq_k=seq_k)

    @pl.when(ki == n_k - 1)
    def _finalize():
        dq_ref[...] = acc_ref[...].astype(dq_ref.dtype).reshape(
            dq_ref.shape)


def _dqkv_fused_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, dlt_ref,
                       *rest, scale, causal, block_q, block_k, seq_k,
                       seq_k_padded, n_q, has_lens, has_seg, pid_off=0):
    """Single-K-block backward (n_k == 1): the score/dp recompute is
    shared, so the whole backward is 5 dots (s, dv, dp, dq, dk) instead
    of the split kernels' 7.  Grid (BH, n_q) sequential over q blocks:
    dq writes per-block, dk/dv accumulate in VMEM scratch."""
    import jax.experimental.pallas as pl

    lens_ref, qseg_ref, kseg_ref, rest = _bwd_unpack(rest, has_lens, has_seg)
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
            block_q, block_k, seq_k, kvlen)
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
                          causal, has_lens, has_seg, needs_tail,
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
                        delta_of_out=False):
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

    lens_ref, qseg_ref, kseg_ref, rest = _bwd_unpack(rest, has_lens, has_seg)
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
                delta_of_out=delta_of_out)
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

    if has_lens:
        _compute(True)      # one body, as in _fwd_kernel_single
    else:
        _run_mask_specialized(pl, _compute, True, 0, 0, block_q, block_k,
                              causal, has_lens, has_seg, needs_tail,
                              kvlen=kvlen, seq_k=seq_k)


def _dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, dlt_ref, *rest,
                scale, causal, block_q, block_k, seq_k, seq_k_padded, n_q,
                has_lens, has_seg, pid_off=0, group=1):
    import jax.experimental.pallas as pl

    lens_ref, qseg_ref, kseg_ref, rest = _bwd_unpack(rest, has_lens, has_seg)
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
            block_q, block_k, seq_k, kvlen)
        dv_acc[...] += lax.dot_general(
            pT.astype(do.dtype), do, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        dk_acc[...] += lax.dot_general(
            dsT.astype(q.dtype), q, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    run = True
    if causal:
        run = (qi * block_q + block_q - 1) >= (ki * block_k)
    if has_lens:
        # dk/dv of keys past the valid length are zero — skip the block
        run = run & (ki * block_k < kvlen)
    _run_mask_specialized(pl, _compute, run, qi, ki, block_q, block_k,
                          causal, has_lens, has_seg, needs_tail,
                          kvlen=kvlen, seq_k=seq_k)

    @pl.when(step == group * n_q - 1)
    def _finalize():
        dk_ref[...] = dk_acc[...].astype(dk_ref.dtype).reshape(
            dk_ref.shape)
        dv_ref[...] = dv_acc[...].astype(dv_ref.dtype).reshape(
            dv_ref.shape)


def pallas_flash_attention_bwd(q, k, v, out, lse, do, causal=False,
                               scale=None, block_q: Optional[int] = None,
                               block_k: Optional[int] = None,
                               interpret: bool = False,
                               kv_lens=None, q_segments=None,
                               kv_segments=None):
    """Flash backward: (dq, dk, dv) without materialising (Tq, Tk)."""
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    B, H, Tq, D = q.shape
    Tk = k.shape[2]
    scale = scale if scale is not None else D ** -0.5
    if block_q is None or block_k is None:
        tq, tk = tune_attention_blocks(Tq, Tk, D, q.dtype)
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
            dq, dk, dv = pl.pallas_call(
                functools.partial(_dqkv_single_kernel, **common),
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
        dq, dk, dv = pl.pallas_call(
            functools.partial(_dqkv_fused_kernel, n_q=n_q, **common),
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

    dq = dq.reshape(B, H, Tqp, Dp)[:, :, :Tq, :D]
    dk = dk.reshape(B, Hkv, Tkp, Dp)[:, :, :Tk, :D]
    dv = dv.reshape(B, Hkv, Tkp, Dp)[:, :, :Tk, :D]
    return dq, dk, dv


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


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def flash_attention(q, k, v, causal=False, scale=None, kv_lens=None,
                    q_segments=None, kv_segments=None):
    """Fused attention: Pallas kernels on TPU, jnp blockwise elsewhere.

    softmax(q·kᵀ·scale [+ masks])·v over (B, H, T, D) inputs; ``k`` and
    ``v`` may have fewer heads, (B, H / g, T, D): grouped-query attention,
    query head h on key-value head h // g, shared through the kernels'
    index maps.  Masking:
    ``causal`` (static), ``kv_lens`` (B,) per-row valid key length
    (padding mask — blocks past the length are skipped, not just masked),
    and ``q_segments``/``kv_segments`` (B, T) packed-sequence ids.
    Rows with no visible key return 0."""
    return _flash_fwd(q, k, v, causal, scale, kv_lens, q_segments,
                      kv_segments)[0]


def _reference_attention(q, k, v, causal, scale, kv_lens=None,
                         q_segments=None, kv_segments=None):
    group = _kv_group(q, k)
    # graftlint: disable-next=trace-tracer-branch -- group is a Python int
    # from the operands' static shapes
    if group > 1:
        # off the chip the shared heads are repeated (the kernels' index
        # maps share them instead); autodiff sums the group's gradients
        k, v = jnp.repeat(k, group, axis=1), jnp.repeat(v, group, axis=1)
    if kv_lens is None and q_segments is None:
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
    if causal:
        mask = mask & (jnp.arange(Tq)[:, None] >= jnp.arange(Tk)[None, :])
    s = jnp.where(mask, s, _NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    # fully-masked rows: uniform softmax garbage -> force exact zeros,
    # matching the kernel's l==0 convention
    any_visible = jnp.any(mask, axis=-1, keepdims=True)
    p = jnp.where(any_visible, p, 0.0).astype(q.dtype)
    return jnp.einsum("bhqk,bhkd->bhqd", p, v)


def _flash_fwd(q, k, v, causal, scale, kv_lens, q_segments, kv_segments):
    plan = attention_dispatch(q.shape[2], k.shape[2], q.shape[3], q.dtype,
                              on_tpu=_context.on_tpu(q))
    if plan["kernel"] != "dense_fallback":
        out, lse = per_batch_shard(
            lambda q, k, v, kl, qs, ks: pallas_flash_attention(
                q, k, v, causal=causal, scale=scale, return_lse=True,
                block_q=plan["block_q"], block_k=plan["block_k"],
                kv_lens=kl, q_segments=qs, kv_segments=ks),
            (q, k, v, kv_lens, q_segments, kv_segments))
        return out, (q, k, v, out, lse, kv_lens, q_segments, kv_segments)
    out = _reference_attention(q, k, v, causal, scale, kv_lens, q_segments,
                               kv_segments)
    return out, (q, k, v, None, None, kv_lens, q_segments, kv_segments)


def _flash_bwd(causal, scale, res, g):
    q, k, v, out, lse, kv_lens, q_segments, kv_segments = res
    if lse is not None:
        # re-consult the dispatcher (trace-time, deterministic) for the
        # forward's blocks: custom_vjp residuals cannot carry static
        # ints.  census=False: the shape was counted at the forward trace
        plan = attention_dispatch(q.shape[2], k.shape[2], q.shape[3],
                                  q.dtype, census=False)
        dq, dk, dv = per_batch_shard(
            lambda q, k, v, out, lse, g, kl, qs, ks:
            pallas_flash_attention_bwd(
                q, k, v, out, lse, g, causal=causal, scale=scale,
                block_q=plan["block_q"], block_k=plan["block_k"],
                kv_lens=kl, q_segments=qs, kv_segments=ks),
            (q, k, v, out, lse, g, kv_lens, q_segments, kv_segments))
    else:
        # recompute-based VJP through the memory-linear jnp path
        _, vjp = jax.vjp(
            lambda q_, k_, v_: _reference_attention(
                q_, k_, v_, causal, scale, kv_lens, q_segments, kv_segments),
            q, k, v)
        dq, dk, dv = vjp(g)
    return (dq, dk, dv, _int_zero_cotangent(kv_lens),
            _int_zero_cotangent(q_segments), _int_zero_cotangent(kv_segments))


flash_attention.defvjp(_flash_fwd, _flash_bwd)


@register("_contrib_flash_attention", aliases=("flash_attention",))
def _flash_attention_op(queries, keys, values, causal: bool = False,
                        scale: Optional[float] = None, kv_lens=None,
                        q_segments=None, kv_segments=None):
    """Fused multi-head attention op (TPU-native counterpart of the
    reference's ``_contrib_interleaved_matmul_selfatt_*`` pipeline,
    src/operator/contrib/transformer.cc).  The mask operands follow
    causal/scale so pre-mask positional callers keep working."""
    return flash_attention(queries, keys, values, causal, scale, kv_lens,
                           q_segments, kv_segments)


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
