"""Contrib operators: CTC loss, SSD detection ops, Faster-RCNN proposals,
FFT, quantization.

TPU-native designs of `src/operator/contrib/`: the CTC forward recursion is
a ``lax.scan`` in log space (gradients via jax AD instead of warp-ctc's
hand-written alpha-beta kernels), box matching/NMS are dense IoU matrices +
masked scans (static shapes, no dynamic-size host loops), FFT rides
``jnp.fft`` with the reference's interleaved re/im packing, and quantize
mirrors the uint8 range-quantization contract.
"""
from __future__ import annotations

import numpy as np

from ..attrs import Param, ParamSchema
from ..registry import OpDef, register_op, simple_compute

_NEG = -1e30  # log-space "minus infinity" that survives bf16/f32 arithmetic


# ---------------------------------------------------------------------------
# CTC loss
# ---------------------------------------------------------------------------

def _ctc_loss(attrs, data, label):
    """Connectionist temporal classification negative log-likelihood.

    data: (T, N, A) activations (A includes the blank at index 0);
    label: (N, L) target ids in 1..A-1, 0-padded.
    Output: (N,) loss.  Forward-only alpha recursion over the extended
    blank-interleaved label, scanned over time in log space; jax AD through
    the scan supplies the gradient (the reference vendors warp-ctc kernels,
    ctc_loss.cc).
    """
    import jax.numpy as jnp
    from jax import lax, nn

    t_len, n, alphabet = data.shape
    l_len = label.shape[1]
    logp = nn.log_softmax(data.astype(jnp.float32), axis=-1)

    lab = label.astype(jnp.int32)                       # (N, L)
    lengths = (lab > 0).sum(axis=1)                     # true label lengths
    s = 2 * l_len + 1

    # extended label: blank, l1, blank, l2, ... blank
    ext = jnp.zeros((n, s), jnp.int32)
    ext = ext.at[:, 1::2].set(lab)

    # a state s may skip from s-2 when both are non-blank and different
    prev_lab = jnp.pad(ext, ((0, 0), (2, 0)))[:, :s]
    can_skip = (ext != 0) & (ext != prev_lab)

    positions = jnp.arange(s)
    valid = positions[None, :] < (2 * lengths + 1)[:, None]

    init = jnp.full((n, s), _NEG, jnp.float32)
    init = init.at[:, 0].set(0.0).at[:, 1].set(0.0)
    # alpha_0 must respect emission at t=0
    emit0 = jnp.take_along_axis(logp[0], ext, axis=1)
    init = jnp.where(valid, init + emit0, _NEG)
    init = init.at[:, 2:].set(_NEG)

    def step(alpha, logp_t):
        stay = alpha
        from_prev = jnp.pad(alpha, ((0, 0), (1, 0)),
                            constant_values=_NEG)[:, :s]
        from_skip = jnp.pad(alpha, ((0, 0), (2, 0)),
                            constant_values=_NEG)[:, :s]
        from_skip = jnp.where(can_skip, from_skip, _NEG)
        merged = jnp.logaddexp(jnp.logaddexp(stay, from_prev), from_skip)
        emit = jnp.take_along_axis(logp_t, ext, axis=1)
        alpha = jnp.where(valid, merged + emit, _NEG)
        return alpha, None

    alpha, _ = lax.scan(step, init, logp[1:])
    # final states: last blank or last symbol of each sequence
    last = 2 * lengths
    a_end = jnp.take_along_axis(alpha, last[:, None], axis=1)[:, 0]
    a_end2 = jnp.take_along_axis(
        alpha, jnp.maximum(last - 1, 0)[:, None], axis=1)[:, 0]
    loglike = jnp.logaddexp(a_end, jnp.where(lengths > 0, a_end2, _NEG))
    return (-loglike).astype(data.dtype)


def _ctc_shape(attrs, in_shapes, aux_shapes):
    dshape = in_shapes[0]
    return in_shapes, [(dshape[1],)], []


# ---------------------------------------------------------------------------
# box helpers
# ---------------------------------------------------------------------------

def _iou_matrix(a, b):
    """Pairwise IoU of corner-format boxes: a (A,4) x b (B,4) -> (A,B)."""
    import jax.numpy as jnp

    lt = jnp.maximum(a[:, None, :2], b[None, :, :2])
    rb = jnp.minimum(a[:, None, 2:], b[None, :, 2:])
    wh = jnp.maximum(rb - lt, 0.0)
    inter = wh[..., 0] * wh[..., 1]
    area_a = jnp.maximum((a[:, 2] - a[:, 0]) * (a[:, 3] - a[:, 1]), 0.0)
    area_b = jnp.maximum((b[:, 2] - b[:, 0]) * (b[:, 3] - b[:, 1]), 0.0)
    union = area_a[:, None] + area_b[None, :] - inter
    return jnp.where(union > 0, inter / union, 0.0)


def _corner_to_center(boxes):
    import jax.numpy as jnp

    w = boxes[..., 2] - boxes[..., 0]
    h = boxes[..., 3] - boxes[..., 1]
    return jnp.stack([boxes[..., 0] + w / 2, boxes[..., 1] + h / 2, w, h],
                     axis=-1)


def _rank_desc(scores):
    """Each element's 0-based rank when sorting descending (rank < k
    selects the top-k) — the shared top-k-by-score primitive for mining
    and pre-NMS cuts."""
    import jax.numpy as jnp

    return jnp.argsort(jnp.argsort(-scores))


# ---------------------------------------------------------------------------
# MultiBox* (SSD)
# ---------------------------------------------------------------------------

def _multibox_prior(attrs, data):
    """Anchor boxes per feature-map cell (ref: multibox_prior.cc).

    Anchor count per cell = len(sizes) + len(ratios) - 1: all sizes at
    ratio[0], plus ratios[1:] at size[0].
    """
    import jax.numpy as jnp

    h, w = data.shape[2], data.shape[3]
    sizes = attrs["sizes"]
    ratios = attrs["ratios"]
    steps = attrs["steps"]
    offsets = attrs["offsets"]
    step_y = steps[0] if steps[0] > 0 else 1.0 / h
    step_x = steps[1] if steps[1] > 0 else 1.0 / w

    cy = (jnp.arange(h, dtype=jnp.float32) + offsets[0]) * step_y
    cx = (jnp.arange(w, dtype=jnp.float32) + offsets[1]) * step_x
    gy, gx = jnp.meshgrid(cy, cx, indexing="ij")        # (h, w)

    half = []
    for s in sizes:
        half.append((s * np.sqrt(ratios[0]) / 2, s / np.sqrt(ratios[0]) / 2))
    for r in ratios[1:]:
        half.append((sizes[0] * np.sqrt(r) / 2, sizes[0] / np.sqrt(r) / 2))
    half = jnp.asarray(half, jnp.float32)               # (K, 2) = (hw, hh)

    centers = jnp.stack([gx, gy], axis=-1).reshape(-1, 1, 2)   # (hw, 1, 2)
    mins = centers - half[None]                                 # x1 y1
    maxs = centers + half[None]
    boxes = jnp.concatenate([mins, maxs], axis=-1)      # (hw, K, 4)
    if attrs["clip"]:
        boxes = jnp.clip(boxes, 0.0, 1.0)
    return boxes.reshape(1, -1, 4)


def _prior_count(attrs):
    return len(attrs["sizes"]) + len(attrs["ratios"]) - 1


def _multibox_prior_shape(attrs, in_shapes, aux_shapes):
    h, w = in_shapes[0][2], in_shapes[0][3]
    return in_shapes, [(1, h * w * _prior_count(attrs), 4)], []


def _multibox_target(attrs, anchors, labels, cls_preds):
    """Match anchors to ground truth (ref: multibox_target.cc).

    anchors (1,A,4); labels (N,O,5) rows [cls,x1,y1,x2,y2] with cls=-1
    padding; outputs loc_target (N,A*4), loc_mask (N,A*4), cls_target (N,A)
    where class 0 = background and gt classes shift by +1.
    """
    import jax
    import jax.numpy as jnp

    iou_thresh = attrs["overlap_threshold"]
    variances = attrs["variances"]
    mining_ratio = attrs.get("negative_mining_ratio", -1.0)
    mining_thresh = attrs.get("negative_mining_thresh", 0.5)
    ignore_label = attrs.get("ignore_label", -1.0)
    anc = anchors[0]                                    # (A, 4)

    def one(lab, cls_pred):
        valid = lab[:, 0] >= 0                          # (O,)
        iou = _iou_matrix(anc, lab[:, 1:5])             # (A, O)
        iou = jnp.where(valid[None, :], iou, -1.0)
        best_o = jnp.argmax(iou, axis=1)                # (A,)
        best_iou = jnp.take_along_axis(iou, best_o[:, None], axis=1)[:, 0]
        # force-match: each gt claims its best anchor.  scatter-max (not
        # set): padding rows all argmax to anchor 0 and a duplicate-index
        # set(False) could overwrite a real gt's True
        best_a = jnp.argmax(jnp.where(valid[None, :], iou, -1.0), axis=0)
        forced = jnp.zeros(anc.shape[0], bool).at[best_a].max(valid)
        matched = forced | (best_iou >= iou_thresh)

        gt = lab[best_o]                                # (A, 5)
        if mining_ratio > 0:
            # hard-negative mining (ref multibox_target.cc:162-221): only
            # unmatched anchors with IoU below negative_mining_thresh are
            # candidates; the hardest (lowest background probability from
            # cls_pred (classes, A)) num_positive*ratio become background,
            # every other unmatched anchor gets ignore_label
            num_pos = jnp.sum(matched)
            num_neg = jnp.minimum(
                (num_pos * mining_ratio).astype(jnp.int32),
                anc.shape[0] - num_pos)
            logits = cls_pred.astype(jnp.float32)       # (C, A)
            bg_prob = jax.nn.softmax(logits, axis=0)[0]  # (A,)
            cand = (~matched) & (best_iou < mining_thresh)
            hardness = jnp.where(cand, -bg_prob, -jnp.inf)
            rank = _rank_desc(hardness)
            neg = cand & (rank < num_neg)
            cls_t = jnp.where(
                matched, gt[:, 0] + 1.0,
                jnp.where(neg, 0.0, ignore_label))
        else:
            cls_t = jnp.where(matched, gt[:, 0] + 1.0, 0.0)

        a_c = _corner_to_center(anc)
        g_c = _corner_to_center(gt[:, 1:5])
        loc = jnp.stack([
            (g_c[:, 0] - a_c[:, 0]) / jnp.maximum(a_c[:, 2], 1e-8) / variances[0],
            (g_c[:, 1] - a_c[:, 1]) / jnp.maximum(a_c[:, 3], 1e-8) / variances[1],
            jnp.log(jnp.maximum(g_c[:, 2], 1e-8) /
                    jnp.maximum(a_c[:, 2], 1e-8)) / variances[2],
            jnp.log(jnp.maximum(g_c[:, 3], 1e-8) /
                    jnp.maximum(a_c[:, 3], 1e-8)) / variances[3],
        ], axis=-1)                                     # (A, 4)
        mask = matched[:, None].astype(jnp.float32)
        return (loc * mask).reshape(-1), \
            jnp.broadcast_to(mask, loc.shape).reshape(-1), cls_t

    loc_t, loc_m, cls_t = jax.vmap(one)(labels, cls_preds)
    return loc_t, loc_m, cls_t


def _multibox_target_shape(attrs, in_shapes, aux_shapes):
    a = in_shapes[0][1]
    n = in_shapes[1][0]
    return in_shapes, [(n, a * 4), (n, a * 4), (n, a)], []


def _decode_boxes(anc_c, loc, variances):
    """Inverse of the target encoding -> corner boxes (A, 4)."""
    import jax.numpy as jnp

    cx = loc[:, 0] * variances[0] * anc_c[:, 2] + anc_c[:, 0]
    cy = loc[:, 1] * variances[1] * anc_c[:, 3] + anc_c[:, 1]
    w = jnp.exp(jnp.clip(loc[:, 2] * variances[2], -10, 10)) * anc_c[:, 2]
    h = jnp.exp(jnp.clip(loc[:, 3] * variances[3], -10, 10)) * anc_c[:, 3]
    return jnp.stack([cx - w / 2, cy - h / 2, cx + w / 2, cy + h / 2],
                     axis=-1)


def _greedy_nms(boxes, scores, thresh, class_ids=None):
    """Greedy non-max suppression with static shapes.

    Sort by score, then scan: box i is kept iff no higher-scoring kept box
    overlaps it above ``thresh``.  Returns the keep mask in sorted order —
    the iterative suppression as one masked pass over the dense IoU matrix
    instead of a dynamic host loop.  With ``class_ids``, suppression only
    applies between boxes of the same class (the reference's
    force_suppress=False mode).
    """
    import jax.numpy as jnp
    from jax import lax

    order = jnp.argsort(-scores)
    sorted_boxes = boxes[order]
    overlaps = _iou_matrix(sorted_boxes, sorted_boxes) > thresh
    if class_ids is not None:
        cls = class_ids[order]
        overlaps &= cls[:, None] == cls[None, :]

    def step(keep, i):
        above = (jnp.arange(keep.shape[0]) < i) & keep & overlaps[i]
        keep = keep.at[i].set(~above.any() & keep[i])
        return keep, None

    keep0 = jnp.ones(boxes.shape[0], bool)
    keep, _ = lax.scan(step, keep0, jnp.arange(boxes.shape[0]))
    return order, keep


def _multibox_detection(attrs, cls_prob, loc_pred, anchors):
    """Decode + per-class NMS (ref: multibox_detection.cc).

    cls_prob (N, classes+1, A) with background at 0; output (N, A, 6) rows
    [cls_id, score, x1, y1, x2, y2], suppressed rows cls_id = -1.
    """
    import jax
    import jax.numpy as jnp

    thresh = attrs["threshold"]
    nms_thresh = attrs["nms_threshold"]
    variances = attrs["variances"]
    force_suppress = attrs["force_suppress"]
    nms_topk = attrs.get("nms_topk", -1)
    anc_c = _corner_to_center(anchors[0])

    def one(probs, loc):
        boxes = _decode_boxes(anc_c, loc.reshape(-1, 4), variances)
        fg = probs[1:]                                  # (classes, A)
        cls_id = jnp.argmax(fg, axis=0)                 # (A,)
        score = jnp.max(fg, axis=0)
        keep_score = score > thresh
        a = boxes.shape[0]
        if 0 < nms_topk < a:
            # only the top-k candidates by score enter NMS (ref
            # multibox_detection.cc:125-127) — and the suppression scan
            # runs over the k-row slice, not all anchors (k steps, k x k
            # IoU: the detection-scale fast path)
            order_full = jnp.argsort(
                -jnp.where(keep_score, score, -jnp.inf))
            top = order_full[:nms_topk]
            torder, tkeep = _greedy_nms(
                boxes[top], jnp.where(keep_score[top], score[top], 0.0),
                nms_thresh,
                class_ids=None if force_suppress else cls_id[top])
            sorted_ids = jnp.concatenate([top[torder],
                                          order_full[nms_topk:]])
            kept = jnp.concatenate([
                tkeep & keep_score[top][torder],
                jnp.zeros(a - nms_topk, bool)])
        else:
            torder, keep_nms = _greedy_nms(
                boxes, jnp.where(keep_score, score, 0.0), nms_thresh,
                class_ids=None if force_suppress else cls_id)
            sorted_ids = torder
            kept = keep_nms & keep_score[torder]
        out = jnp.concatenate([
            jnp.where(kept, cls_id[sorted_ids].astype(jnp.float32),
                      -1.0)[:, None],
            score[sorted_ids][:, None], boxes[sorted_ids]], axis=1)
        return out

    # vmap materializes every image's (A, A) IoU matrix at once — at SSD
    # scale (A=8732, bs 8) that is tens of GB; lax.map runs one image's
    # matrices at a time (A^2 fp32 ~ 300 MB at SSD300 scale)
    if anc_c.shape[0] > 2048:
        import jax.lax as lax

        return lax.map(lambda args: one(*args), (cls_prob, loc_pred))
    return jax.vmap(one)(cls_prob, loc_pred)


def _multibox_detection_shape(attrs, in_shapes, aux_shapes):
    n, _, a = in_shapes[0]
    return in_shapes, [(n, a, 6)], []


# ---------------------------------------------------------------------------
# Proposal (Faster-RCNN)
# ---------------------------------------------------------------------------

def _proposal(attrs, cls_prob, bbox_pred, im_info):
    """RPN proposals: anchors + deltas, clip, NMS, top-k (ref:
    src/operator/contrib/proposal.cc).  Output (rois_kept, 5) with batch
    index 0 — single-image RPN as in the reference."""
    import jax.numpy as jnp

    scales = attrs["scales"]
    ratios = attrs["ratios"]
    stride = attrs["feature_stride"]
    pre_top = attrs["rpn_pre_nms_top_n"]
    post_top = attrs["rpn_post_nms_top_n"]
    nms_thresh = attrs["threshold"]
    min_size = attrs["rpn_min_size"]

    _, _, h, w = cls_prob.shape
    k = len(scales) * len(ratios)

    # base anchors centered on each cell (vectorized meshgrid)
    base = []
    for r in ratios:
        for s in scales:
            ww = stride * s * np.sqrt(1.0 / r)
            hh = stride * s * np.sqrt(r)
            base.append([-ww / 2, -hh / 2, ww / 2, hh / 2])
    base = jnp.asarray(base, jnp.float32)               # (K, 4)
    sy = jnp.arange(h, dtype=jnp.float32) * stride
    sx = jnp.arange(w, dtype=jnp.float32) * stride
    gy, gx = jnp.meshgrid(sy, sx, indexing="ij")
    shifts = jnp.stack([gx, gy, gx, gy], axis=-1).reshape(-1, 1, 4)
    anchors = (shifts + base[None]).reshape(-1, 4)      # (h*w*K, 4)

    # deltas (1, 4K, h, w) -> (h*w*K, 4); scores: foreground half
    deltas = bbox_pred[0].reshape(k, 4, h, w).transpose(2, 3, 0, 1)
    deltas = deltas.reshape(-1, 4)
    scores = cls_prob[0, k:].transpose(1, 2, 0).reshape(-1)

    boxes = _decode_boxes(_corner_to_center(anchors), deltas,
                          (1.0, 1.0, 1.0, 1.0))
    im_h, im_w = im_info[0, 0], im_info[0, 1]
    boxes = jnp.stack([
        jnp.clip(boxes[:, 0], 0, im_w - 1), jnp.clip(boxes[:, 1], 0, im_h - 1),
        jnp.clip(boxes[:, 2], 0, im_w - 1), jnp.clip(boxes[:, 3], 0, im_h - 1),
    ], axis=-1)
    # reference scales the min-size filter by the image's resize factor
    # (proposal.cc: rpn_min_size * im_info[2])
    scaled_min = min_size * im_info[0, 2]
    big = ((boxes[:, 2] - boxes[:, 0] + 1) >= scaled_min) & \
          ((boxes[:, 3] - boxes[:, 1] + 1) >= scaled_min)
    scores = jnp.where(big, scores, 0.0)

    # pre-NMS cut: only the rpn_pre_nms_top_n highest-scoring candidates
    # enter NMS (ref proposal.cc:295-296)
    if pre_top > 0:
        pre_rank = _rank_desc(jnp.where(scores > 0, scores, -jnp.inf))
        scores = jnp.where(pre_rank < pre_top, scores, 0.0)

    order, keep = _greedy_nms(boxes, scores, nms_thresh)
    # survivors in score order; short outputs cycle the kept boxes, the
    # reference's padding rule (proposal.cc: keep[i % out_size]) so
    # downstream ROI consumers never see uninitialized rows
    valid = keep & (scores[order] > 0)
    rank = jnp.argsort(~valid, stable=True)
    nkept = jnp.maximum(jnp.sum(valid), 1)
    pos = jnp.arange(post_top) % nkept
    top = order[rank][pos]
    out = jnp.concatenate([jnp.zeros((post_top, 1), boxes.dtype),
                           boxes[top]], axis=1)
    return out


def _proposal_shape(attrs, in_shapes, aux_shapes):
    return in_shapes, [(attrs.get("rpn_post_nms_top_n", 300), 5)], []


# ---------------------------------------------------------------------------
# fft / ifft / quantization
# ---------------------------------------------------------------------------

def _fft(attrs, data):
    """Real -> interleaved re/im complex, matching contrib/fft.cc packing:
    (..., d) -> (..., 2d) with out[..., 2i]=Re, out[..., 2i+1]=Im."""
    import jax.numpy as jnp

    spec = jnp.fft.fft(data.astype(jnp.complex64), axis=-1)
    out = jnp.stack([spec.real, spec.imag], axis=-1)
    return out.reshape(*data.shape[:-1], -1).astype(jnp.float32)


def _ifft(attrs, data):
    """Interleaved re/im -> real inverse FFT: (..., 2d) -> (..., d).

    Matches contrib/ifft.cc: no 1/d normalization (the reference leaves
    scaling to the caller)."""
    import jax.numpy as jnp

    pairs = data.reshape(*data.shape[:-1], -1, 2)
    spec = pairs[..., 0] + 1j * pairs[..., 1]
    return (jnp.fft.ifft(spec, axis=-1).real *
            pairs.shape[-2]).astype(jnp.float32)


def _quantize(attrs, data, min_range, max_range):
    """Affine uint8 quantization over [min_range, max_range]
    (ref: contrib/quantize.cc)."""
    import jax.numpy as jnp

    lo = min_range.reshape(())
    hi = max_range.reshape(())
    scale = 255.0 / jnp.maximum(hi - lo, 1e-8)
    q = jnp.clip(jnp.round((data - lo) * scale), 0, 255).astype(jnp.uint8)
    return q, lo, hi


def _dequantize(attrs, data, min_range, max_range):
    import jax.numpy as jnp

    lo = min_range.reshape(())
    hi = max_range.reshape(())
    scale = jnp.maximum(hi - lo, 1e-8) / 255.0
    return data.astype(jnp.float32) * scale + lo


# ---------------------------------------------------------------------------
# registration
# ---------------------------------------------------------------------------

def register_all():
    register_op(OpDef(
        "CTCLoss", simple_compute(_ctc_loss),
        num_inputs=2, arguments=["data", "label"],
        infer_shape=_ctc_shape, hint="ctcloss",
        doc="CTC negative log-likelihood; blank=0, labels 0-padded "
            "(ref: src/operator/contrib/ctc_loss.cc)."),
        aliases=("_contrib_CTCLoss", "ctc_loss"))

    register_op(OpDef(
        "MultiBoxPrior", simple_compute(_multibox_prior),
        schema=ParamSchema(
            Param("sizes", "float_tuple", default=(1.0,)),
            Param("ratios", "float_tuple", default=(1.0,)),
            Param("clip", bool, default=False),
            Param("steps", "float_tuple", default=(-1.0, -1.0)),
            Param("offsets", "float_tuple", default=(0.5, 0.5))),
        num_inputs=1, arguments=["data"],
        infer_shape=_multibox_prior_shape, hint="multiboxprior",
        doc="SSD anchor generation "
            "(ref: src/operator/contrib/multibox_prior.cc)."),
        aliases=("_contrib_MultiBoxPrior",))

    register_op(OpDef(
        "MultiBoxTarget", simple_compute(_multibox_target, num_outputs=3),
        schema=ParamSchema(
            Param("overlap_threshold", float, default=0.5),
            Param("ignore_label", float, default=-1.0),
            Param("negative_mining_ratio", float, default=-1.0),
            Param("negative_mining_thresh", float, default=0.5),
            Param("variances", "float_tuple", default=(0.1, 0.1, 0.2, 0.2))),
        num_inputs=3, num_outputs=3,
        arguments=["anchor", "label", "cls_pred"],
        outputs=["loc_target", "loc_mask", "cls_target"],
        infer_shape=_multibox_target_shape, hint="multiboxtarget",
        doc="SSD anchor-to-ground-truth matching "
            "(ref: src/operator/contrib/multibox_target.cc)."),
        aliases=("_contrib_MultiBoxTarget",))

    register_op(OpDef(
        "MultiBoxDetection", simple_compute(_multibox_detection),
        schema=ParamSchema(
            Param("threshold", float, default=0.01),
            Param("nms_threshold", float, default=0.5),
            Param("force_suppress", bool, default=False),
            Param("variances", "float_tuple", default=(0.1, 0.1, 0.2, 0.2)),
            Param("nms_topk", int, default=-1)),
        num_inputs=3, arguments=["cls_prob", "loc_pred", "anchor"],
        infer_shape=_multibox_detection_shape, hint="multiboxdetection",
        doc="SSD decode + NMS "
            "(ref: src/operator/contrib/multibox_detection.cc)."),
        aliases=("_contrib_MultiBoxDetection",))

    register_op(OpDef(
        "Proposal", simple_compute(_proposal),
        schema=ParamSchema(
            Param("scales", "float_tuple", default=(4.0, 8.0, 16.0, 32.0)),
            Param("ratios", "float_tuple", default=(0.5, 1.0, 2.0)),
            Param("feature_stride", int, default=16),
            Param("threshold", float, default=0.7),
            Param("rpn_pre_nms_top_n", int, default=6000),
            Param("rpn_post_nms_top_n", int, default=300),
            Param("rpn_min_size", int, default=16)),
        num_inputs=3, arguments=["cls_prob", "bbox_pred", "im_info"],
        infer_shape=_proposal_shape, hint="proposal",
        doc="RPN region proposals: decode anchors + NMS + top-k "
            "(ref: src/operator/contrib/proposal.cc)."),
        aliases=("_contrib_Proposal",))

    register_op(OpDef(
        "fft", simple_compute(_fft), num_inputs=1,
        infer_shape=lambda a, i, x: (i, [i[0][:-1] + (2 * i[0][-1],)], []),
        hint="fft",
        doc="FFT along the last axis, interleaved re/im output "
            "(ref: src/operator/contrib/fft.cc)."),
        aliases=("_contrib_fft",))

    register_op(OpDef(
        "ifft", simple_compute(_ifft), num_inputs=1,
        infer_shape=lambda a, i, x: (i, [i[0][:-1] + (i[0][-1] // 2,)], []),
        hint="ifft",
        doc="Inverse FFT from interleaved re/im "
            "(ref: src/operator/contrib/ifft.cc)."),
        aliases=("_contrib_ifft",))

    f32 = np.dtype(np.float32)
    register_op(OpDef(
        "quantize", simple_compute(_quantize, num_outputs=3),
        num_inputs=3, num_outputs=3,
        arguments=["data", "min_range", "max_range"],
        outputs=["output", "min_output", "max_output"],
        infer_shape=lambda a, i, x: (i, [i[0], (), ()], []),
        infer_type=lambda a, i, x: (i, [np.dtype(np.uint8), f32, f32], x),
        hint="quantize",
        doc="uint8 range quantization "
            "(ref: src/operator/contrib/quantize.cc)."),
        aliases=("_contrib_quantize",))

    register_op(OpDef(
        "dequantize", simple_compute(_dequantize),
        num_inputs=3, arguments=["data", "min_range", "max_range"],
        infer_shape=lambda a, i, x: (i, [i[0]], []),
        infer_type=lambda a, i, x: (i, [f32], x),
        hint="dequantize",
        doc="Inverse of quantize "
            "(ref: src/operator/contrib/dequantize.cc)."),
        aliases=("_contrib_dequantize",))

    def _count_sketch(attrs, data, h, s):
        """Count-sketch projection: out[b, h[i]] += s[i] * data[b, i].

        A scatter-add over hashed indices (XLA lowers `.at[].add`
        efficiently); differentiable in data, so the compact-bilinear-
        pooling use case gets its backward from the executor's vjp."""
        import jax
        import jax.numpy as jnp

        out_dim = attrs["out_dim"]
        idx = h.reshape(-1).astype(jnp.int32)
        signed = data * s.reshape(1, -1).astype(data.dtype)
        return jax.vmap(
            lambda row: jnp.zeros((out_dim,), row.dtype).at[idx].add(row)
        )(signed)

    register_op(OpDef(
        "count_sketch", simple_compute(_count_sketch),
        schema=ParamSchema(Param("out_dim", int, required=True),
                           Param("processing_batch_size", int, default=32)),
        num_inputs=3, arguments=["data", "h", "s"],
        infer_shape=lambda a, i, x: (i, [(i[0][0], a["out_dim"])], []),
        hint="count_sketch",
        doc="Count-sketch random projection "
            "(ref: src/operator/contrib/count_sketch.cc); h = hash "
            "indices (in_dim,), s = signs (in_dim,)."),
        aliases=("_contrib_count_sketch",))
