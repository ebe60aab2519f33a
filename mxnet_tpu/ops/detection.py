"""Detection training/inference ops: MultiBoxTarget, MultiBoxDetection,
Proposal/MultiProposal, PSROIPooling.

Reference: ``src/operator/contrib/multibox_target.cc`` (bipartite + per-
anchor matching, negative mining, variance-encoded location targets),
``multibox_detection.cc`` (decode + per-class NMS),
``proposal.cc``/``multi_proposal.cc`` (RPN proposal generation),
``psroi_pooling.cc`` (position-sensitive ROI pooling — the reference runs
these on the accelerator: multibox_target.cu, multi_proposal.cu).

TPU-native mapping: all four ops are pure jnp/lax compositions with
static shapes, so SSD/RPN train steps jit into one XLA program with NO
host callbacks:

* the greedy sequential parts (bipartite matching, NMS sweeps) become
  ``lax.scan``/``fori_loop`` over score-sorted candidates with masked
  IoU matrices — the same shape tricks as ``ops/vision.py`` box_nms;
* "append to output" compaction becomes a stable argsort on the keep
  mask (kept rows first, order preserved), bit-matching the reference's
  sequential writes.

The original numpy implementations are kept as ``*_host`` oracles; the
test suite asserts the jitted device path equals them element-wise.
"""
from __future__ import annotations

import numpy as onp

import jax
import jax.numpy as jnp
from jax import lax

from .registry import register

__all__ = ["multibox_target", "multibox_detection", "proposal",
           "psroi_pooling", "multibox_target_host",
           "multibox_detection_host", "proposal_host"]


def _iou_matrix_jnp(a, b):
    """(N,4) × (M,4) corner-box IoU on device."""
    ix1 = jnp.maximum(a[:, None, 0], b[None, :, 0])
    iy1 = jnp.maximum(a[:, None, 1], b[None, :, 1])
    ix2 = jnp.minimum(a[:, None, 2], b[None, :, 2])
    iy2 = jnp.minimum(a[:, None, 3], b[None, :, 3])
    inter = jnp.clip(ix2 - ix1, 0) * jnp.clip(iy2 - iy1, 0)
    aa = (a[:, 2] - a[:, 0]) * (a[:, 3] - a[:, 1])
    ba = (b[:, 2] - b[:, 0]) * (b[:, 3] - b[:, 1])
    union = aa[:, None] + ba[None] - inter
    return jnp.where(union > 0, inter / jnp.maximum(union, 1e-12), 0.0)


def _stable_desc_order(scores, valid):
    """Indices sorting valid entries by descending score (stable), invalid
    last — the device analogue of argsort(-score)[mask] compaction."""
    return jnp.argsort(jnp.where(valid, -scores, jnp.inf), stable=True)


def _iou_matrix(anchors, boxes):
    """(N,4) corner anchors × (M,4) corner boxes → (N,M) IoU."""
    ix1 = onp.maximum(anchors[:, None, 0], boxes[None, :, 0])
    iy1 = onp.maximum(anchors[:, None, 1], boxes[None, :, 1])
    ix2 = onp.minimum(anchors[:, None, 2], boxes[None, :, 2])
    iy2 = onp.minimum(anchors[:, None, 3], boxes[None, :, 3])
    inter = onp.clip(ix2 - ix1, 0, None) * onp.clip(iy2 - iy1, 0, None)
    a_area = (anchors[:, 2] - anchors[:, 0]) * (anchors[:, 3] - anchors[:, 1])
    b_area = (boxes[:, 2] - boxes[:, 0]) * (boxes[:, 3] - boxes[:, 1])
    union = a_area[:, None] + b_area[None] - inter
    return onp.where(union > 0, inter / onp.maximum(union, 1e-12), 0.0)


def _encode_loc(anchor, gt, variances):
    """Variance-encoded center-offset regression target (reference
    multibox_target.cc AssignLocTargets)."""
    aw = anchor[2] - anchor[0]
    ah = anchor[3] - anchor[1]
    ax = (anchor[0] + anchor[2]) * 0.5
    ay = (anchor[1] + anchor[3]) * 0.5
    gw = gt[2] - gt[0]
    gh = gt[3] - gt[1]
    gx = (gt[0] + gt[2]) * 0.5
    gy = (gt[1] + gt[3]) * 0.5
    vx, vy, vw, vh = variances
    return onp.array([(gx - ax) / aw / vx, (gy - ay) / ah / vy,
                      onp.log(max(gw / aw, 1e-12)) / vw,
                      onp.log(max(gh / ah, 1e-12)) / vh], onp.float32)


def multibox_target_host(anchors_a, labels_a, preds_a,
                         overlap_threshold=0.5, ignore_label=-1.0,
                         negative_mining_ratio=-1.0,
                         negative_mining_thresh=0.5,
                         minimum_negative_samples=0,
                         variances=(0.1, 0.1, 0.2, 0.2)):
    """Numpy oracle for :func:`multibox_target` (sequential reference
    semantics, multibox_target.cc:305)."""
    var = tuple(float(v) for v in variances)
    anchors_a, labels_a, preds_a = (onp.asarray(x) for x in
                                    (anchors_a, labels_a, preds_a))
    B = labels_a.shape[0]
    N = anchors_a.shape[1]
    anc = anchors_a.reshape(-1, 4).astype(onp.float32)
    loc_t = onp.zeros((B, N * 4), onp.float32)
    loc_m = onp.zeros((B, N * 4), onp.float32)
    cls_t = onp.zeros((B, N), onp.float32)
    for b in range(B):
        lab = labels_a[b]
        valid = lab[(lab[:, 0] != -1)][:, :5]
        if valid.shape[0] == 0:
            continue
        ious = _iou_matrix(anc, valid[:, 1:5].astype(onp.float32))
        match = onp.full(N, -1, onp.int64)     # gt id per anchor
        flags = onp.full(N, -1, onp.int8)      # 1 pos / 0 neg / -1 ignore
        # greedy bipartite pass: each gt grabs its best free anchor
        work = ious.copy()
        for _ in range(valid.shape[0]):
            j, k = onp.unravel_index(onp.argmax(work), work.shape)
            if work[j, k] <= 1e-6:
                break
            match[j] = k
            flags[j] = 1
            work[j, :] = -1.0
            work[:, k] = -1.0
        # threshold pass for the remaining anchors
        if overlap_threshold > 0:
            best_gt = ious.argmax(axis=1)
            best_iou = ious.max(axis=1)
            take = (flags != 1) & (best_iou > overlap_threshold)
            match[take] = best_gt[take]
            flags[take] = 1
        num_pos = int((flags == 1).sum())
        if negative_mining_ratio > 0:
            n_neg = min(int(num_pos * negative_mining_ratio),
                        N - num_pos)
            n_neg = max(n_neg, int(minimum_negative_samples))
            best_iou = ious.max(axis=1)
            cand = (flags != 1) & (best_iou < negative_mining_thresh)
            # hardest negatives = highest background probability loss:
            # rank by descending P(class != background)… the reference
            # ranks by ascending background softmax prob
            logits = preds_a[b]                      # (C, N)
            mx = logits.max(axis=0)
            prob_bg = onp.exp(logits[0] - mx) / onp.exp(
                logits - mx).sum(axis=0)
            n_neg = min(n_neg, int(cand.sum()))
            order = onp.argsort(onp.where(cand, prob_bg, onp.inf),
                                kind="stable")
            flags[order[:n_neg]] = 0
        else:
            flags[flags != 1] = 0
        for j in onp.nonzero(flags == 1)[0]:
            g = valid[match[j]]
            cls_t[b, j] = g[0] + 1
            loc_m[b, 4 * j:4 * j + 4] = 1.0
            loc_t[b, 4 * j:4 * j + 4] = _encode_loc(
                anc[j], g[1:5].astype(onp.float32), var)
        cls_t[b, flags == -1] = ignore_label
    return loc_t, loc_m, cls_t


def _encode_loc_jnp(anc, gt, variances):
    """Vectorized variance-encoded regression targets: (N,4)x(N,4)→(N,4)."""
    aw = anc[:, 2] - anc[:, 0]
    ah = anc[:, 3] - anc[:, 1]
    ax = (anc[:, 0] + anc[:, 2]) * 0.5
    ay = (anc[:, 1] + anc[:, 3]) * 0.5
    gw = gt[:, 2] - gt[:, 0]
    gh = gt[:, 3] - gt[:, 1]
    gx = (gt[:, 0] + gt[:, 2]) * 0.5
    gy = (gt[:, 1] + gt[:, 3]) * 0.5
    vx, vy, vw, vh = variances
    return jnp.stack([
        (gx - ax) / aw / vx, (gy - ay) / ah / vy,
        jnp.log(jnp.maximum(gw / aw, 1e-12)) / vw,
        jnp.log(jnp.maximum(gh / ah, 1e-12)) / vh], axis=1)


@register("_contrib_MultiBoxTarget", aliases=("MultiBoxTarget",),
          num_outputs=3, differentiable=False)
def multibox_target(anchors, labels, cls_preds, overlap_threshold=0.5,
                    ignore_label=-1.0, negative_mining_ratio=-1.0,
                    negative_mining_thresh=0.5, minimum_negative_samples=0,
                    variances=(0.1, 0.1, 0.2, 0.2)):
    """SSD training-target assignment (reference multibox_target.cc:305;
    device kernel multibox_target.cu) — pure jnp/lax, jits on TPU.

    anchors (1, N, 4), labels (B, M, 5) rows [cls, x1, y1, x2, y2] padded
    with -1, cls_preds (B, C, N) → (loc_target (B, 4N), loc_mask (B, 4N),
    cls_target (B, N)); cls_target is gt_class+1, 0 background, and
    ignore_label for unmined anchors when mining is on.

    The greedy bipartite pass is a ``fori_loop`` over the (static) label
    count; negative mining ranks background probabilities with a stable
    argsort and selects by rank, matching the sequential oracle
    (:func:`multibox_target_host`) element-wise.
    """
    var = tuple(float(v) for v in variances)
    anchors = jnp.asarray(anchors)
    labels = jnp.asarray(labels)
    cls_preds = jnp.asarray(cls_preds)
    N = anchors.shape[1]
    M = labels.shape[1]
    anc = anchors.reshape(-1, 4).astype(jnp.float32)

    def one_batch(lab, logits):
        valid = lab[:, 0] != -1                       # (M,)
        gt = lab[:, 1:5].astype(jnp.float32)
        ious = jnp.where(valid[None, :],
                         _iou_matrix_jnp(anc, gt), 0.0)  # (N, M)

        # greedy bipartite: each gt grabs its best free anchor
        def bip(_, carry):
            work, match, flags = carry
            idx = jnp.argmax(work)
            j, k = idx // M, idx % M
            hit = work.ravel()[idx] > 1e-6
            match = jnp.where(hit, match.at[j].set(k), match)
            flags = jnp.where(hit, flags.at[j].set(1), flags)
            work = jnp.where(hit, work.at[j, :].set(-1.0), work)
            work = jnp.where(hit, work.at[:, k].set(-1.0), work)
            return work, match, flags

        work0 = jnp.where(valid[None, :], ious, -1.0)
        _, match, flags = lax.fori_loop(
            0, M, bip, (work0, jnp.zeros(N, jnp.int32),
                        jnp.full(N, -1, jnp.int32)))

        best_gt = jnp.argmax(ious, axis=1)
        best_iou = jnp.max(ious, axis=1)
        if overlap_threshold > 0:
            take = (flags != 1) & (best_iou > overlap_threshold)
            match = jnp.where(take, best_gt.astype(jnp.int32), match)
            flags = jnp.where(take, 1, flags)

        if negative_mining_ratio > 0:
            num_pos = jnp.sum(flags == 1)
            n_neg = jnp.minimum(
                (num_pos * negative_mining_ratio).astype(jnp.int32),
                N - num_pos)
            n_neg = jnp.maximum(n_neg, int(minimum_negative_samples))
            cand = (flags != 1) & (best_iou < negative_mining_thresh)
            n_neg = jnp.minimum(n_neg, jnp.sum(cand))
            mx = jnp.max(logits, axis=0)
            e = jnp.exp(logits - mx)
            prob_bg = e[0] / jnp.sum(e, axis=0)
            order = jnp.argsort(jnp.where(cand, prob_bg, jnp.inf),
                                stable=True)
            rank = jnp.argsort(order, stable=True)     # rank within order
            flags = jnp.where(cand & (rank < n_neg), 0, flags)
        else:
            flags = jnp.where(flags != 1, 0, flags)

        g = lab[jnp.clip(match, 0, M - 1)]             # (N, 5)
        pos = flags == 1
        cls_t = jnp.where(pos, g[:, 0] + 1.0, 0.0)
        cls_t = jnp.where(flags == -1, ignore_label, cls_t)
        # an object-free image (no valid gt) is ALL background — the
        # oracle short-circuits before mining ever marks ignores
        cls_t = jnp.where(jnp.any(valid), cls_t, 0.0)
        loc = _encode_loc_jnp(anc, g[:, 1:5].astype(jnp.float32), var)
        loc_t = jnp.where(pos[:, None], loc, 0.0).reshape(-1)
        loc_m = jnp.where(pos[:, None], 1.0,
                          0.0) * jnp.ones((N, 4))
        return loc_t.astype(jnp.float32), loc_m.reshape(-1), cls_t

    loc_t, loc_m, cls_t = jax.vmap(one_batch)(labels, cls_preds)
    return (loc_t.astype(jnp.float32), loc_m.astype(jnp.float32),
            cls_t.astype(jnp.float32))


def _decode_boxes(anc, loc, variances, clip):
    """(N,4) anchors + (N,4) predictions → (N,4) corner boxes (reference
    multibox_detection.cc TransformLocations)."""
    aw = anc[:, 2] - anc[:, 0]
    ah = anc[:, 3] - anc[:, 1]
    ax = (anc[:, 0] + anc[:, 2]) * 0.5
    ay = (anc[:, 1] + anc[:, 3]) * 0.5
    vx, vy, vw, vh = variances
    ox = loc[:, 0] * vx * aw + ax
    oy = loc[:, 1] * vy * ah + ay
    ow = onp.exp(loc[:, 2] * vw) * aw * 0.5
    oh = onp.exp(loc[:, 3] * vh) * ah * 0.5
    out = onp.stack([ox - ow, oy - oh, ox + ow, oy + oh], axis=1)
    if clip:
        out = onp.clip(out, 0.0, 1.0)
    return out


def multibox_detection_host(prob_a, loc_a, anchors_a, clip=True,
                            threshold=0.01, background_id=0,
                            nms_threshold=0.5, force_suppress=False,
                            variances=(0.1, 0.1, 0.2, 0.2), nms_topk=-1):
    """Numpy oracle for :func:`multibox_detection` (sequential reference
    semantics, multibox_detection.cc:218)."""
    var = tuple(float(v) for v in variances)
    prob_a, loc_a, anchors_a = (onp.asarray(x) for x in
                                (prob_a, loc_a, anchors_a))
    B, C, N = prob_a.shape
    anc = anchors_a.reshape(-1, 4).astype(onp.float32)
    out = onp.full((B, N, 6), -1.0, onp.float32)
    for b in range(B):
        probs = prob_a[b]                       # (C, N)
        # reference multibox_detection.cc:125: id = raw argmax over
        # non-background classes, output as id-1 regardless of which
        # class is background
        masked = probs.copy()
        masked[background_id] = -onp.inf
        raw = masked.argmax(axis=0)
        ids = (raw - 1).astype(onp.float32)
        scores = masked.max(axis=0)
        keep = scores >= threshold
        boxes = _decode_boxes(anc, loc_a[b].reshape(N, 4), var, clip)
        order = onp.argsort(-scores, kind="stable")
        if nms_topk > 0:
            order = order[:nms_topk]
        rows = []
        kept_boxes = onp.zeros((0, 4), onp.float32)
        kept_ids = onp.zeros((0,), onp.float32)
        for j in order:
            if not keep[j]:
                continue
            if len(rows):
                ious = _iou_matrix(boxes[j][None], kept_boxes)[0]
                same = kept_ids == ids[j] if not force_suppress \
                    else onp.ones_like(kept_ids, bool)
                if (ious[same] > nms_threshold).any():
                    continue
            rows.append((ids[j], scores[j]) + tuple(boxes[j]))
            kept_boxes = onp.vstack([kept_boxes, boxes[j][None]])
            kept_ids = onp.append(kept_ids, ids[j])
        for i, r in enumerate(rows):
            out[b, i] = r
    return out


def _decode_boxes_jnp(anc, loc, variances, clip):
    aw = anc[:, 2] - anc[:, 0]
    ah = anc[:, 3] - anc[:, 1]
    ax = (anc[:, 0] + anc[:, 2]) * 0.5
    ay = (anc[:, 1] + anc[:, 3]) * 0.5
    vx, vy, vw, vh = variances
    ox = loc[:, 0] * vx * aw + ax
    oy = loc[:, 1] * vy * ah + ay
    ow = jnp.exp(loc[:, 2] * vw) * aw * 0.5
    oh = jnp.exp(loc[:, 3] * vh) * ah * 0.5
    out = jnp.stack([ox - ow, oy - oh, ox + ow, oy + oh], axis=1)
    return jnp.clip(out, 0.0, 1.0) if clip else out


@register("_contrib_MultiBoxDetection", aliases=("MultiBoxDetection",),
          differentiable=False)
def multibox_detection(cls_prob, loc_pred, anchors, clip=True,
                       threshold=0.01, background_id=0, nms_threshold=0.5,
                       force_suppress=False,
                       variances=(0.1, 0.1, 0.2, 0.2), nms_topk=-1):
    """SSD inference decode + NMS (reference multibox_detection.cc:218;
    device kernel multibox_detection.cu) — pure jnp/lax, jits on TPU.

    cls_prob (B, C, N), loc_pred (B, 4N), anchors (1, N, 4) →
    (B, N, 6) rows [class_id, score, x1, y1, x2, y2], -1 for suppressed.
    The greedy per-class NMS is a ``lax.scan`` suppression sweep over
    score-sorted candidates; kept rows compact to the front via a stable
    argsort on the keep mask (matching the oracle's sequential writes).
    """
    var = tuple(float(v) for v in variances)
    cls_prob = jnp.asarray(cls_prob)
    loc_pred = jnp.asarray(loc_pred)
    anchors = jnp.asarray(anchors)
    B, C, N = cls_prob.shape
    if background_id != 0:
        # the reference kernel hardcodes class 0 as background (its class
        # loop starts at j=1 and outputs argmax-1); any other value would
        # make foreground ids collide with the -1 suppressed marker
        raise ValueError("MultiBoxDetection supports background_id=0 only "
                         "(like the reference multibox_detection.cc)")
    anc = anchors.reshape(-1, 4).astype(jnp.float32)

    def one_batch(probs, loc):
        masked = probs.at[background_id].set(-jnp.inf)
        ids = (jnp.argmax(masked, axis=0) - 1).astype(jnp.float32)
        scores = jnp.max(masked, axis=0)
        keep = scores >= threshold
        boxes = _decode_boxes_jnp(anc, loc.reshape(N, 4), var, clip)

        order = _stable_desc_order(scores, jnp.ones(N, bool))
        if nms_topk > 0:
            keep = keep & (jnp.argsort(order, stable=True) < nms_topk)
        sb = boxes[order]
        sids = ids[order]
        sscores = scores[order]
        svalid = keep[order]
        iou = _iou_matrix_jnp(sb, sb)
        if not force_suppress:
            iou = jnp.where(sids[:, None] == sids[None, :], iou, 0.0)

        def sweep(alive, i):
            keep_i = alive[i] & svalid[i]
            suppress = keep_i & (iou[i] > nms_threshold) & (
                jnp.arange(N) > i)
            return alive & ~suppress, keep_i

        _, kept = lax.scan(sweep, jnp.ones(N, bool), jnp.arange(N))
        rows = jnp.concatenate(
            [sids[:, None], sscores[:, None], sb], axis=1)    # (N, 6)
        rows = jnp.where(kept[:, None], rows, -1.0)
        # compact kept rows to the front, preserving score order
        pack = jnp.argsort(~kept, stable=True)
        return rows[pack]

    return jax.vmap(one_batch)(cls_prob,
                               loc_pred.reshape(B, -1)).astype(jnp.float32)


def _rpn_anchors(H, W, scales, ratios, feature_stride):
    """Static anchor grid (reference proposal.cc anchor generation)."""
    base = []
    cx = cy = (feature_stride - 1) / 2.0
    for r in ratios:
        size = feature_stride * feature_stride
        ws = int(round(onp.sqrt(size / r)))
        hs = int(round(ws * r))
        for s in scales:
            w2, h2 = ws * s / 2.0, hs * s / 2.0
            base.append([cx - w2 + 0.5, cy - h2 + 0.5,
                         cx + w2 - 0.5, cy + h2 - 0.5])
    base = onp.array(base, onp.float32)          # (A, 4)
    sx = onp.arange(W) * feature_stride
    sy = onp.arange(H) * feature_stride
    shift = onp.stack(onp.meshgrid(sx, sy), axis=-1).reshape(-1, 2)
    return (base[None, :, :] + onp.tile(shift, 2)[:, None, :]
            ).reshape(-1, 4)                     # (H*W*A, 4)


def proposal_host(prob_a, pred_a, info_a, rpn_pre_nms_top_n=6000,
                  rpn_post_nms_top_n=300, threshold=0.7, rpn_min_size=16,
                  scales=(4, 8, 16, 32), ratios=(0.5, 1, 2),
                  feature_stride=16, iou_loss=False):
    """Numpy oracle for :func:`proposal` (sequential reference semantics,
    proposal.cc); returns (rois, scores)."""
    prob_a, pred_a, info_a = (onp.asarray(x) for x in
                              (prob_a, pred_a, info_a))
    B = prob_a.shape[0]
    H, W = prob_a.shape[2], prob_a.shape[3]
    A = len(scales) * len(ratios)
    post_n = int(rpn_post_nms_top_n)
    anchors = _rpn_anchors(H, W, scales, ratios, feature_stride)
    rois = onp.zeros((B * post_n, 5), onp.float32)
    scores_out = onp.zeros((B * post_n, 1), onp.float32)
    for b in range(B):
        im_h, im_w, im_scale = info_a[b]
        scores = prob_a[b, A:].transpose(1, 2, 0).reshape(-1)
        deltas = pred_a[b].reshape(A, 4, H, W).transpose(
            2, 3, 0, 1).reshape(-1, 4)
        if iou_loss:
            # IoU-loss decode: deltas are direct corner offsets
            # (reference proposal.cc IoUTransformInv :93)
            boxes = anchors + deltas
        else:
            # cx/cy/w/h deltas (Fast-RCNN BBoxTransformInv)
            aw = anchors[:, 2] - anchors[:, 0] + 1
            ah = anchors[:, 3] - anchors[:, 1] + 1
            axc = anchors[:, 0] + 0.5 * (aw - 1)
            ayc = anchors[:, 1] + 0.5 * (ah - 1)
            pxc = deltas[:, 0] * aw + axc
            pyc = deltas[:, 1] * ah + ayc
            pw = onp.exp(onp.clip(deltas[:, 2], -10, 10)) * aw
            ph = onp.exp(onp.clip(deltas[:, 3], -10, 10)) * ah
            boxes = onp.stack(
                [pxc - 0.5 * (pw - 1), pyc - 0.5 * (ph - 1),
                 pxc + 0.5 * (pw - 1), pyc + 0.5 * (ph - 1)], axis=1)
        boxes[:, 0::2] = onp.clip(boxes[:, 0::2], 0, im_w - 1)
        boxes[:, 1::2] = onp.clip(boxes[:, 1::2], 0, im_h - 1)
        ms = rpn_min_size * im_scale
        ok = ((boxes[:, 2] - boxes[:, 0] + 1 >= ms)
              & (boxes[:, 3] - boxes[:, 1] + 1 >= ms))
        # the reference (FilterBox) only zeroes undersized boxes'
        # scores; they sort last but remain real boxes, so the output
        # always carries valid coordinates and batch indices
        eff_scores = onp.where(ok, scores, 0.0)
        idx = onp.argsort(-eff_scores,
                          kind="stable")[:int(rpn_pre_nms_top_n)]
        picked = []
        kept = onp.zeros((0, 4), onp.float32)
        for j in idx:
            if len(picked) and (_iou_matrix(boxes[j][None], kept)[0]
                                > threshold).any():
                continue
            picked.append(j)
            kept = onp.vstack([kept, boxes[j][None]])
            if len(picked) >= post_n:
                break
        # pad by repeating the first proposal (reference behavior)
        while picked and len(picked) < post_n:
            picked.append(picked[0])
        rois[b * post_n:(b + 1) * post_n, 0] = b
        for i, j in enumerate(picked):
            rois[b * post_n + i, 1:] = boxes[j]
            scores_out[b * post_n + i, 0] = eff_scores[j]
    return rois, scores_out


@register("_contrib_Proposal", aliases=("Proposal", "_contrib_MultiProposal",
                                        "MultiProposal"),
          differentiable=False)
def proposal(cls_prob, bbox_pred, im_info, rpn_pre_nms_top_n=6000,
             rpn_post_nms_top_n=300, threshold=0.7, rpn_min_size=16,
             scales=(4, 8, 16, 32), ratios=(0.5, 1, 2),
             feature_stride=16, output_score=False, iou_loss=False):
    """RPN proposal generation (reference proposal.cc / multi_proposal.cu)
    — pure jnp/lax, jits on TPU.

    cls_prob (B, 2A, H, W), bbox_pred (B, 4A, H, W), im_info (B, 3)
    [height, width, scale] → rois (B*post_n, 5) [batch_idx, x1, y1, x2, y2]
    (+ scores with output_score).  Top-``pre_nms`` candidates are selected
    with one stable sort, the greedy NMS sweep is a ``lax.scan`` over the
    pre-NMS IoU matrix, and the first ``post_n`` survivors compact to the
    front (padded by repeating the first kept proposal, as upstream).
    """
    cls_prob = jnp.asarray(cls_prob)
    bbox_pred = jnp.asarray(bbox_pred)
    im_info = jnp.asarray(im_info)
    B = cls_prob.shape[0]
    H, W = cls_prob.shape[2], cls_prob.shape[3]
    A = len(scales) * len(ratios)
    post_n = int(rpn_post_nms_top_n)
    K = H * W * A
    pre_n = min(int(rpn_pre_nms_top_n), K)
    anchors = jnp.asarray(_rpn_anchors(H, W, scales, ratios,
                                       feature_stride))

    def one_batch(prob, pred, info):
        im_h, im_w, im_scale = info[0], info[1], info[2]
        scores = prob[A:].transpose(1, 2, 0).reshape(-1)
        deltas = pred.reshape(A, 4, H, W).transpose(2, 3, 0, 1).reshape(
            -1, 4)
        if iou_loss:
            # IoU-loss decode: deltas are direct corner offsets
            # (reference proposal.cc IoUTransformInv :93)
            boxes = anchors + deltas
        else:
            # cx/cy/w/h deltas (Fast-RCNN BBoxTransformInv)
            aw = anchors[:, 2] - anchors[:, 0] + 1
            ah = anchors[:, 3] - anchors[:, 1] + 1
            axc = anchors[:, 0] + 0.5 * (aw - 1)
            ayc = anchors[:, 1] + 0.5 * (ah - 1)
            pxc = deltas[:, 0] * aw + axc
            pyc = deltas[:, 1] * ah + ayc
            pw = jnp.exp(jnp.clip(deltas[:, 2], -10, 10)) * aw
            ph = jnp.exp(jnp.clip(deltas[:, 3], -10, 10)) * ah
            boxes = jnp.stack(
                [pxc - 0.5 * (pw - 1), pyc - 0.5 * (ph - 1),
                 pxc + 0.5 * (pw - 1), pyc + 0.5 * (ph - 1)], axis=1)
        boxes = jnp.stack([
            jnp.clip(boxes[:, 0], 0, im_w - 1),
            jnp.clip(boxes[:, 1], 0, im_h - 1),
            jnp.clip(boxes[:, 2], 0, im_w - 1),
            jnp.clip(boxes[:, 3], 0, im_h - 1)], axis=1)
        ms = rpn_min_size * im_scale
        ok = ((boxes[:, 2] - boxes[:, 0] + 1 >= ms)
              & (boxes[:, 3] - boxes[:, 1] + 1 >= ms))
        # the reference (FilterBox) only zeroes undersized boxes' scores;
        # they sort last but remain real boxes with valid coordinates
        eff = jnp.where(ok, scores, 0.0)
        order = jnp.argsort(-eff, stable=True)[:pre_n]
        cb = boxes[order]                             # (pre_n, 4)
        cs = eff[order]
        iou = _iou_matrix_jnp(cb, cb)

        def sweep(carry, i):
            alive, n_kept = carry
            keep_i = alive[i] & (n_kept < post_n)
            suppress = keep_i & (iou[i] > threshold) & (
                jnp.arange(pre_n) > i)
            return (alive & ~suppress, n_kept + keep_i), keep_i

        (_, _), kept = lax.scan(sweep, (jnp.ones(pre_n, bool),
                                        jnp.asarray(0, jnp.int32)),
                                jnp.arange(pre_n))
        pack = jnp.argsort(~kept, stable=True)        # kept first, in order
        n_kept = jnp.sum(kept)
        # first post_n survivors; pad by repeating the first kept proposal
        idx = pack[jnp.arange(post_n)]
        idx = jnp.where(jnp.arange(post_n) < n_kept, idx, pack[0])
        return cb[idx], cs[idx]

    rois_b, scores_b = jax.vmap(one_batch)(cls_prob, bbox_pred, im_info)
    bidx = jnp.repeat(jnp.arange(B, dtype=jnp.float32), post_n)
    rois = jnp.concatenate([bidx[:, None],
                            rois_b.reshape(B * post_n, 4)], axis=1)
    scores = scores_b.reshape(B * post_n, 1)
    rois = rois.astype(jnp.float32)
    scores = scores.astype(jnp.float32)
    return (rois, scores) if output_score else rois


@register("_contrib_PSROIPooling", aliases=("PSROIPooling",))
def psroi_pooling(data, rois, spatial_scale: float = 0.0625,
                  output_dim: int = 0, pooled_size: int = 7,
                  group_size: int = 0):
    """Position-sensitive ROI pooling (reference psroi_pooling.cc, the
    R-FCN head).  data (B, output_dim*g*g, H, W), rois (R, 5)
    [batch, x1, y1, x2, y2 in image coords] → (R, output_dim, g, g).

    Differentiable jnp composition: each output bin averages a spatial
    window of its own (c, i, j) channel slice — runs on-device so R-FCN
    heads train without host round-trips.
    """
    p = int(pooled_size)
    g = int(group_size) if group_size else p
    B, CD, H, W = data.shape
    R = rois.shape[0]
    od = int(output_dim) if output_dim else CD // (g * g)

    batch_idx = rois[:, 0].astype(jnp.int32)
    # reference psroi_pooling.cc: start = round(x1)*scale,
    # end = (round(x2)+1)*scale
    x1 = jnp.round(rois[:, 1]) * spatial_scale
    y1 = jnp.round(rois[:, 2]) * spatial_scale
    x2 = (jnp.round(rois[:, 3]) + 1.0) * spatial_scale
    y2 = (jnp.round(rois[:, 4]) + 1.0) * spatial_scale
    rw = jnp.maximum(x2 - x1, 0.1)
    rh = jnp.maximum(y2 - y1, 0.1)
    bin_w = rw / p
    bin_h = rh / p

    feat = data.reshape(B, od, g, g, H, W)[batch_idx]  # (R, od, g, g, H, W)
    cols = jnp.arange(W, dtype=jnp.float32)
    rows_ = jnp.arange(H, dtype=jnp.float32)

    outs = []
    for i in range(p):          # static p×p loop: unrolled, fully batched
        row_out = []
        for j in range(p):
            # output bin (i, j) reads group channel (gh, gw) =
            # floor(i*g/p), floor(j*g/p) — reference psroi_pooling.cc:94
            gh = (i * g) // p
            gw = (j * g) // p
            bx1 = jnp.floor(x1 + j * bin_w)
            bx2 = jnp.ceil(x1 + (j + 1) * bin_w)
            by1 = jnp.floor(y1 + i * bin_h)
            by2 = jnp.ceil(y1 + (i + 1) * bin_h)
            mx = ((cols[None, :] >= bx1[:, None])
                  & (cols[None, :] < bx2[:, None])).astype(data.dtype)
            my = ((rows_[None, :] >= by1[:, None])
                  & (rows_[None, :] < by2[:, None])).astype(data.dtype)
            mask = my[:, :, None] * mx[:, None, :]          # (R, H, W)
            count = jnp.maximum(mask.sum(axis=(1, 2)), 1.0)  # (R,)
            sl = feat[:, :, gh, gw]                          # (R, od, H, W)
            pooled = (sl * mask[:, None]).sum(axis=(2, 3)) / count[:, None]
            row_out.append(pooled)
        outs.append(jnp.stack(row_out, axis=-1))             # (R, od, p)
    return jnp.stack(outs, axis=-2)                          # (R, od, p, p)
