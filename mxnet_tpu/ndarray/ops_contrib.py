"""Detection operators (reference: src/operator/contrib/ — multibox_prior.cc,
multibox_target.cc, multibox_detection.cc, bounding_box.cc, roi_align.cc;
SURVEY.md §2.2).  These back the GluonCV-style SSD/Mask-RCNN models
(BASELINE config #5).

TPU-native design: every op is static-shaped pad-and-mask — suppressed/
invalid entries are marked (score −1 / label −1) instead of shrinking the
tensor, NMS is a fixed-iteration greedy scan over a topk-pruned candidate
set (`lax.scan`), and ROIAlign is a vmapped gather+bilinear kernel.  No
dynamic shapes ever reach XLA.
"""
from __future__ import annotations

import numpy as _np

from .register import register_op


def _register():
    import jax
    import jax.numpy as jnp
    from jax import lax

    # ---- multibox_prior --------------------------------------------------
    def multibox_prior_maker(sizes=(1.0,), ratios=(1.0,), clip=False,
                             steps=(-1.0, -1.0), offsets=(0.5, 0.5)):
        sizes = tuple(float(s) for s in _astuple(sizes))
        ratios = tuple(float(r) for r in _astuple(ratios))
        steps_ = tuple(float(s) for s in _astuple(steps))
        offs = tuple(float(o) for o in _astuple(offsets))

        def fn(data):
            h, w = data.shape[2], data.shape[3]
            step_y = steps_[0] if steps_[0] > 0 else 1.0 / h
            step_x = steps_[1] if steps_[1] > 0 else 1.0 / w
            cy = (jnp.arange(h, dtype=jnp.float32) + offs[0]) * step_y
            cx = (jnp.arange(w, dtype=jnp.float32) + offs[1]) * step_x
            cyg, cxg = jnp.meshgrid(cy, cx, indexing="ij")
            # anchor set: (sizes[0], every ratio) + (sizes[1:], ratios[0]) —
            # reference ordering: size-ratio pairs (s_i, r_0) first, then
            # (s_0, r_j>0): multibox_prior.cc uses sizes-first enumeration
            whs = []
            for s in sizes:
                r = ratios[0]
                whs.append((s * _np.sqrt(r), s / _np.sqrt(r)))
            for r in ratios[1:]:
                s = sizes[0]
                whs.append((s * _np.sqrt(r), s / _np.sqrt(r)))
            boxes = []
            for bw, bh in whs:
                boxes.append(jnp.stack([cxg - bw / 2, cyg - bh / 2,
                                        cxg + bw / 2, cyg + bh / 2],
                                       axis=-1))
            out = jnp.stack(boxes, axis=2).reshape(1, -1, 4)
            if clip:
                out = jnp.clip(out, 0.0, 1.0)
            return out
        return fn
    register_op("_contrib_MultiBoxPrior", multibox_prior_maker,
                aliases=("MultiBoxPrior", "multibox_prior"))

    # ---- box_iou ---------------------------------------------------------
    def _iou_corner(lhs, rhs):
        """IoU of (..., 4) corner boxes broadcast over leading dims."""
        tl = jnp.maximum(lhs[..., :2], rhs[..., :2])
        br = jnp.minimum(lhs[..., 2:], rhs[..., 2:])
        wh = jnp.clip(br - tl, 0.0)
        inter = wh[..., 0] * wh[..., 1]
        area_l = jnp.clip(lhs[..., 2] - lhs[..., 0], 0.0) * \
            jnp.clip(lhs[..., 3] - lhs[..., 1], 0.0)
        area_r = jnp.clip(rhs[..., 2] - rhs[..., 0], 0.0) * \
            jnp.clip(rhs[..., 3] - rhs[..., 1], 0.0)
        return inter / jnp.maximum(area_l + area_r - inter, 1e-12)

    def box_iou_maker(format="corner"):
        def fn(lhs, rhs):
            if format == "center":
                lhs = _center_to_corner(lhs)
                rhs = _center_to_corner(rhs)
            return _iou_corner(lhs[..., :, None, :], rhs[..., None, :, :])
        return fn

    def _center_to_corner(b):
        x, y, w, h = b[..., 0], b[..., 1], b[..., 2], b[..., 3]
        return jnp.stack([x - w / 2, y - h / 2, x + w / 2, y + h / 2],
                         axis=-1)

    def _corner_to_center(b):
        x1, y1, x2, y2 = b[..., 0], b[..., 1], b[..., 2], b[..., 3]
        return jnp.stack([(x1 + x2) / 2, (y1 + y2) / 2, x2 - x1, y2 - y1],
                         axis=-1)

    register_op("_contrib_box_iou", box_iou_maker,
                aliases=("box_iou",))

    # ---- box_nms ---------------------------------------------------------
    def box_nms_maker(overlap_thresh=0.5, valid_thresh=0.0, topk=-1,
                      coord_start=2, score_index=1, id_index=-1,
                      background_id=-1, force_suppress=False,
                      in_format="corner", out_format="corner"):
        def fn(data):
            # data: (..., N, K); returns same shape, suppressed score = -1
            shape = data.shape
            flat = data.reshape((-1,) + shape[-2:])

            def one(batch):
                n = batch.shape[0]
                scores = batch[:, score_index]
                boxes = batch[:, coord_start:coord_start + 4]
                if in_format == "center":
                    boxes = _center_to_corner(boxes)
                valid = scores > valid_thresh
                if background_id >= 0 and id_index >= 0:
                    valid &= batch[:, id_index] != background_id
                k = n if topk <= 0 else min(int(topk), n)
                order = jnp.argsort(
                    jnp.where(valid, scores, -jnp.inf))[::-1][:k]
                cand_boxes = boxes[order]
                cand_valid = valid[order]
                iou = _iou_corner(cand_boxes[:, None, :],
                                  cand_boxes[None, :, :])
                if not force_suppress and id_index >= 0:
                    ids = batch[order, id_index]
                    same = ids[:, None] == ids[None, :]
                    iou = jnp.where(same, iou, 0.0)

                # greedy: walk candidates best-first; each kept box kills
                # its high-IoU successors (fixed k iterations — jit-safe)
                def step(keep, i):
                    keep_i = keep[i]
                    kill = (iou[i] > overlap_thresh) & \
                        (jnp.arange(k) > i) & keep_i
                    return keep & ~kill, None
                keep0 = cand_valid
                keep, _ = lax.scan(step, keep0, jnp.arange(k))
                # scatter the keep decision back to original positions
                kept_full = jnp.zeros(n, dtype=bool).at[order].set(keep)
                out = batch.at[:, score_index].set(
                    jnp.where(kept_full, scores, -1.0))
                if out_format != in_format:
                    conv = _center_to_corner if out_format == "corner" \
                        else _corner_to_center
                    cs = coord_start
                    out = out.at[:, cs:cs + 4].set(
                        conv(out[:, cs:cs + 4]))
                return out
            out = jax.vmap(one)(flat)
            return out.reshape(shape)
        return fn
    register_op("_contrib_box_nms", box_nms_maker,
                aliases=("box_nms",))

    # ---- multibox_target -------------------------------------------------
    def multibox_target_maker(overlap_threshold=0.5, ignore_label=-1.0,
                              negative_mining_ratio=-1.0,
                              negative_mining_thresh=0.5,
                              minimum_negative_samples=0,
                              variances=(0.1, 0.1, 0.2, 0.2)):
        var = _np.asarray(_astuple(variances), dtype=_np.float32)

        def fn(anchor, label, cls_pred):
            # anchor (1,N,4) corner; label (B,M,5) [cls,x1,y1,x2,y2], pad=-1
            # cls_pred (B, num_class+1, N) — used for hard negative mining
            anchors = anchor.reshape(-1, 4)
            n = anchors.shape[0]

            def one(lab, cpred):
                gt_valid = lab[:, 0] >= 0
                gt_boxes = lab[:, 1:5]
                iou = _iou_corner(anchors[:, None, :],
                                  gt_boxes[None, :, :])         # (N, M)
                iou = jnp.where(gt_valid[None, :], iou, 0.0)
                best_gt = jnp.argmax(iou, axis=1)               # (N,)
                best_iou = jnp.max(iou, axis=1)
                matched = best_iou >= overlap_threshold
                # force-match: every valid GT claims its best anchor.
                # Padded GTs are routed to a sacrificial slot n so their
                # scatter can never clobber a real GT's claim on anchor 0
                best_anchor = jnp.argmax(iou, axis=0)           # (M,)
                m = gt_boxes.shape[0]
                ba = jnp.where(gt_valid, best_anchor, n)
                forced = jnp.zeros(n + 1, dtype=bool).at[ba].set(
                    True)[:n]
                forced_gt = jnp.zeros(n + 1, dtype=jnp.int32).at[ba].set(
                    jnp.arange(m, dtype=jnp.int32))[:n]
                gt_idx = jnp.where(forced, forced_gt, best_gt)
                pos = matched | forced

                g = gt_boxes[gt_idx]                            # (N,4)
                acx = (anchors[:, 0] + anchors[:, 2]) / 2
                acy = (anchors[:, 1] + anchors[:, 3]) / 2
                aw = jnp.maximum(anchors[:, 2] - anchors[:, 0], 1e-8)
                ah = jnp.maximum(anchors[:, 3] - anchors[:, 1], 1e-8)
                gcx = (g[:, 0] + g[:, 2]) / 2
                gcy = (g[:, 1] + g[:, 3]) / 2
                gw = jnp.maximum(g[:, 2] - g[:, 0], 1e-8)
                gh = jnp.maximum(g[:, 3] - g[:, 1], 1e-8)
                loc = jnp.stack([(gcx - acx) / aw / var[0],
                                 (gcy - acy) / ah / var[1],
                                 jnp.log(gw / aw) / var[2],
                                 jnp.log(gh / ah) / var[3]], axis=-1)
                loc_target = jnp.where(pos[:, None], loc, 0.0).reshape(-1)
                loc_mask = jnp.where(pos[:, None],
                                     jnp.ones((n, 4)), 0.0).reshape(-1)
                cls_target = jnp.where(
                    pos, lab[gt_idx, 0] + 1.0, 0.0)   # 0 = background
                if negative_mining_ratio > 0:
                    # hard negatives: highest background-loss negatives up
                    # to ratio×num_pos; everything else ignored
                    bg_prob = jax.nn.softmax(cpred, axis=0)[0]
                    # exclude positives AND near-misses (IoU above the
                    # mining threshold) BEFORE ranking, so ignored anchors
                    # never consume negative slots (reference
                    # multibox_target.cc candidate filtering)
                    ineligible = pos | \
                        (best_iou >= negative_mining_thresh)
                    neg_score = jnp.where(ineligible, -jnp.inf, -jnp.log(
                        jnp.maximum(bg_prob, 1e-12)))
                    num_pos = jnp.sum(pos)
                    max_neg = jnp.maximum(
                        (negative_mining_ratio * num_pos).astype(jnp.int32),
                        minimum_negative_samples)
                    rank = jnp.argsort(jnp.argsort(-neg_score))
                    keep_neg = (~ineligible) & (rank < max_neg)
                    cls_target = jnp.where(
                        pos | keep_neg, cls_target, float(ignore_label))
                return loc_target, loc_mask, cls_target
            loc_t, loc_m, cls_t = jax.vmap(one)(label, cls_pred)
            return loc_t, loc_m, cls_t
        return fn
    register_op("_contrib_MultiBoxTarget", multibox_target_maker,
                aliases=("MultiBoxTarget", "multibox_target"),
                differentiable=False)

    # ---- multibox_detection ----------------------------------------------
    def multibox_detection_maker(clip=True, threshold=0.01,
                                 background_id=0, nms_threshold=0.5,
                                 force_suppress=False,
                                 variances=(0.1, 0.1, 0.2, 0.2),
                                 nms_topk=-1):
        var = _np.asarray(_astuple(variances), dtype=_np.float32)

        def fn(cls_prob, loc_pred, anchor):
            # cls_prob (B, num_classes+1, N); loc_pred (B, N*4);
            # anchor (1, N, 4) -> out (B, N, 6) [id, score, x1,y1,x2,y2]
            anchors = anchor.reshape(-1, 4)
            n = anchors.shape[0]
            acx = (anchors[:, 0] + anchors[:, 2]) / 2
            acy = (anchors[:, 1] + anchors[:, 3]) / 2
            aw = anchors[:, 2] - anchors[:, 0]
            ah = anchors[:, 3] - anchors[:, 1]

            def one(cp, lp):
                loc = lp.reshape(n, 4)
                cx = loc[:, 0] * var[0] * aw + acx
                cy = loc[:, 1] * var[1] * ah + acy
                w = jnp.exp(loc[:, 2] * var[2]) * aw
                h = jnp.exp(loc[:, 3] * var[3]) * ah
                boxes = jnp.stack([cx - w / 2, cy - h / 2,
                                   cx + w / 2, cy + h / 2], axis=-1)
                if clip:
                    boxes = jnp.clip(boxes, 0.0, 1.0)
                # best non-background class per anchor
                fg = jnp.concatenate([cp[:background_id],
                                      cp[background_id + 1:]], axis=0)
                cls_id = jnp.argmax(fg, axis=0).astype(jnp.float32)
                score = jnp.max(fg, axis=0)
                keep = score > threshold
                out = jnp.concatenate(
                    [jnp.where(keep, cls_id, -1.0)[:, None],
                     jnp.where(keep, score, -1.0)[:, None], boxes], axis=1)
                return out
            det = jax.vmap(one)(cls_prob, loc_pred)
            nms = box_nms_maker(overlap_thresh=nms_threshold,
                                valid_thresh=0.0, topk=nms_topk,
                                coord_start=2, score_index=1, id_index=0,
                                force_suppress=force_suppress)
            return nms(det)
        return fn
    register_op("_contrib_MultiBoxDetection", multibox_detection_maker,
                aliases=("MultiBoxDetection", "multibox_detection"),
                differentiable=False)

    # ---- ROIAlign --------------------------------------------------------
    def roi_align_maker(pooled_size=(7, 7), spatial_scale=1.0,
                        sample_ratio=2, position_sensitive=False,
                        aligned=False):
        ph, pw = _astuple(pooled_size)
        sr = max(int(sample_ratio), 1)

        def fn(data, rois):
            # data (B,C,H,W); rois (R,5) [batch_idx, x1,y1,x2,y2]
            _, c, h, w = data.shape

            def one(roi):
                bidx = roi[0].astype(jnp.int32)
                img = data[bidx]                          # (C,H,W)
                off = 0.5 if aligned else 0.0
                x1 = roi[1] * spatial_scale - off
                y1 = roi[2] * spatial_scale - off
                x2 = roi[3] * spatial_scale - off
                y2 = roi[4] * spatial_scale - off
                rw = jnp.maximum(x2 - x1, 1.0 if not aligned else 1e-6)
                rh = jnp.maximum(y2 - y1, 1.0 if not aligned else 1e-6)
                bin_w = rw / pw
                bin_h = rh / ph
                # sr×sr bilinear samples per output bin, averaged
                iy = jnp.arange(ph * sr, dtype=jnp.float32)
                ix = jnp.arange(pw * sr, dtype=jnp.float32)
                sy = y1 + (iy + 0.5) * bin_h / sr         # (ph*sr,)
                sx = x1 + (ix + 0.5) * bin_w / sr         # (pw*sr,)

                def bilinear(yy, xx):
                    y0 = jnp.clip(jnp.floor(yy), 0, h - 1)
                    x0 = jnp.clip(jnp.floor(xx), 0, w - 1)
                    y1_ = jnp.clip(y0 + 1, 0, h - 1)
                    x1_ = jnp.clip(x0 + 1, 0, w - 1)
                    ly = jnp.clip(yy - y0, 0.0, 1.0)
                    lx = jnp.clip(xx - x0, 0.0, 1.0)
                    y0i, x0i = y0.astype(jnp.int32), x0.astype(jnp.int32)
                    y1i, x1i = y1_.astype(jnp.int32), x1_.astype(jnp.int32)
                    v00 = img[:, y0i, :][:, :, x0i]
                    v01 = img[:, y0i, :][:, :, x1i]
                    v10 = img[:, y1i, :][:, :, x0i]
                    v11 = img[:, y1i, :][:, :, x1i]
                    wy = ly[None, :, None]
                    wx = lx[None, None, :]
                    return (v00 * (1 - wy) * (1 - wx) +
                            v01 * (1 - wy) * wx +
                            v10 * wy * (1 - wx) + v11 * wy * wx)
                samples = bilinear(sy, sx)                # (C,ph*sr,pw*sr)
                pooled = samples.reshape(c, ph, sr, pw, sr).mean((2, 4))
                return pooled
            return jax.vmap(one)(rois)
        return fn
    register_op("_contrib_ROIAlign", roi_align_maker,
                aliases=("ROIAlign", "roi_align"))

    # ---- DeformablePSROIPooling (Deformable ConvNets; reference:
    # src/operator/contrib/deformable_psroi_pooling.cc).  Position-
    # sensitive score maps (C = output_dim*group_size^2) pooled per roi
    # bin with learned per-part offsets.  TPU-first: one vmapped
    # gather+bilinear over a static (pooled, pooled, samples) grid —
    # the same shape discipline as ROIAlign above; gradients (data,
    # rois-stop, trans) come from autodiff. ------------------------------
    def deformable_psroi_maker(spatial_scale=1.0, output_dim=1,
                               group_size=1, pooled_size=7,
                               part_size=0, sample_per_part=1,
                               trans_std=0.0, no_trans=False):
        ps = int(pooled_size)
        gs = int(group_size)
        pt = int(part_size) or ps
        sp = max(int(sample_per_part), 1)
        d_out = int(output_dim)

        def fn(data, rois, *trans_opt):
            b, c, h, w = data.shape
            trans = trans_opt[0] if trans_opt and not no_trans else None

            # bin -> position-sensitive group / offset-part index (static)
            gi = jnp.clip((jnp.arange(ps) * gs) // ps, 0, gs - 1)
            pi = jnp.clip((jnp.arange(ps) * pt) // ps, 0, pt - 1)

            def one(roi, tr):
                bidx = roi[0].astype(jnp.int32)
                # reference rounding: rois snap to the input grid with C
                # round() semantics — half-away-from-zero, which for the
                # non-negative roi coords is floor(x + 0.5); jnp.round's
                # half-to-even would shift .5-coordinate windows a pixel
                def c_round(v):
                    return jnp.floor(v + 0.5)
                x1 = c_round(roi[1]) * spatial_scale - 0.5
                y1 = c_round(roi[2]) * spatial_scale - 0.5
                x2 = (c_round(roi[3]) + 1.0) * spatial_scale - 0.5
                y2 = (c_round(roi[4]) + 1.0) * spatial_scale - 0.5
                rw = jnp.maximum(x2 - x1, 0.1)
                rh = jnp.maximum(y2 - y1, 0.1)
                bin_h, bin_w = rh / ps, rw / ps
                sub_h, sub_w = bin_h / sp, bin_w / sp

                if trans is not None:
                    # offset channel PAIRS are (x, y) per class (the
                    # reference reads trans_x at 2*class, trans_y at
                    # 2*class+1); output channel c belongs to class
                    # c // (output_dim / num_classes)
                    n_cls = tr.shape[0] // 2
                    per_cls = max(d_out // max(n_cls, 1), 1)
                    cls_of = jnp.arange(d_out) // per_cls     # (D,)
                    dx_all = tr[0::2][:, pi[:, None], pi[None, :]] \
                        * trans_std * rw                      # (ncls,ps,ps)
                    dy_all = tr[1::2][:, pi[:, None], pi[None, :]] \
                        * trans_std * rh
                    dx = dx_all[cls_of]                       # (D,ps,ps)
                    dy = dy_all[cls_of]
                else:
                    dy = jnp.zeros((d_out, ps, ps), data.dtype)
                    dx = jnp.zeros((d_out, ps, ps), data.dtype)

                iy = jnp.arange(ps, dtype=jnp.float32)
                off = jnp.arange(sp, dtype=jnp.float32)
                # (D, ps, ps, sp) sample coordinates per class and bin —
                # reference grid: wstart + iw*sub_bin (no half-sample
                # centering, unlike ROIAlign)
                ys = (y1 + iy[None, :, None, None] * bin_h
                      + dy[:, :, :, None]
                      + off[None, None, None, :] * sub_h)
                xs = (x1 + iy[None, None, :, None] * bin_w
                      + dx[:, :, :, None]
                      + off[None, None, None, :] * sub_w)
                full = (d_out, ps, ps, sp, sp)
                ysb = jnp.broadcast_to(ys[..., :, None], full)
                xsb = jnp.broadcast_to(xs[..., None, :], full)
                valid = ((ysb > -0.5) & (ysb < h - 0.5) &
                         (xsb > -0.5) & (xsb < w - 0.5))
                yc = jnp.clip(ysb, 0.0, h - 1.0)
                xc = jnp.clip(xsb, 0.0, w - 1.0)
                y0 = jnp.floor(yc)
                x0 = jnp.floor(xc)
                y0i = y0.astype(jnp.int32)
                x0i = x0.astype(jnp.int32)
                y1i = jnp.clip(y0i + 1, 0, h - 1)
                x1i = jnp.clip(x0i + 1, 0, w - 1)
                ly = (yc - y0)
                lx = (xc - x0)

                # flat channel index per (class, bin): (c*gs+gi)*gs+gj
                # — gathered DIRECTLY from (C, H, W), never materializing
                # the (D, ps, ps, H, W) per-bin map stack (which at
                # R-FCN scale would be gigabytes per roi batch)
                imgC = data[bidx]                  # (C, H, W)
                ch = ((jnp.arange(d_out)[:, None, None] * gs
                       + gi[None, :, None]) * gs
                      + gi[None, None, :])         # (D, ps, ps)
                chb = ch[:, :, :, None, None]      # (D,ps,ps,1,1)
                v00 = imgC[chb, y0i, x0i]
                v01 = imgC[chb, y0i, x1i]
                v10 = imgC[chb, y1i, x0i]
                v11 = imgC[chb, y1i, x1i]
                vals = (v00 * (1 - ly) * (1 - lx) + v01 * (1 - ly) * lx +
                        v10 * ly * (1 - lx) + v11 * ly * lx)
                vmask = valid.astype(vals.dtype)
                # count clamp makes empty bins exact zeros already
                count = jnp.maximum(vmask.sum((-1, -2)), 1.0)
                return (vals * vmask).sum((-1, -2)) / count  # (D,ps,ps)

            if trans is not None:
                return jax.vmap(one)(rois, trans)
            dummy = jnp.zeros((rois.shape[0],), data.dtype)
            return jax.vmap(lambda r, _:
                            one(r, None))(rois, dummy)
        return fn
    register_op("_contrib_DeformablePSROIPooling",
                deformable_psroi_maker,
                aliases=("DeformablePSROIPooling",))

    # ---- ROIPooling (legacy top-level op) --------------------------------
    def roi_pooling_maker(pooled_size=(7, 7), spatial_scale=1.0):
        ph, pw = _astuple(pooled_size)

        def fn(data, rois):
            _, c, h, w = data.shape

            def one(roi):
                bidx = roi[0].astype(jnp.int32)
                img = data[bidx]
                x1 = jnp.round(roi[1] * spatial_scale)
                y1 = jnp.round(roi[2] * spatial_scale)
                x2 = jnp.round(roi[3] * spatial_scale)
                y2 = jnp.round(roi[4] * spatial_scale)
                rw = jnp.maximum(x2 - x1 + 1, 1.0)
                rh = jnp.maximum(y2 - y1 + 1, 1.0)
                ys = jnp.arange(h, dtype=jnp.float32)
                xs = jnp.arange(w, dtype=jnp.float32)

                def bin_val(py, px):
                    by0 = y1 + jnp.floor(py * rh / ph)
                    by1 = y1 + jnp.ceil((py + 1) * rh / ph)
                    bx0 = x1 + jnp.floor(px * rw / pw)
                    bx1 = x1 + jnp.ceil((px + 1) * rw / pw)
                    my = (ys >= by0) & (ys < jnp.maximum(by1, by0 + 1))
                    mx = (xs >= bx0) & (xs < jnp.maximum(bx1, bx0 + 1))
                    mask = my[:, None] & mx[None, :]
                    neg = jnp.full((h, w), -jnp.inf)
                    return jnp.max(jnp.where(mask[None], img, neg),
                                   axis=(1, 2))
                pys, pxs = jnp.meshgrid(jnp.arange(ph), jnp.arange(pw),
                                        indexing="ij")
                vals = jax.vmap(jax.vmap(bin_val))(
                    pys.astype(jnp.float32), pxs.astype(jnp.float32))
                return jnp.transpose(vals, (2, 0, 1))     # (C,ph,pw)
            return jax.vmap(one)(rois)
        return fn
    register_op("ROIPooling", roi_pooling_maker)

    # ---- RPN proposal (reference: src/operator/contrib/proposal.cc) ------
    def _decode_deltas(anchors, deltas):  # noqa: F811 (module fn below)
        """Standard RCNN box transform: anchors+(dx,dy,dw,dh) -> corners."""
        aw = anchors[:, 2] - anchors[:, 0] + 1.0
        ah = anchors[:, 3] - anchors[:, 1] + 1.0
        ax = anchors[:, 0] + 0.5 * (aw - 1.0)
        ay = anchors[:, 1] + 0.5 * (ah - 1.0)
        dx, dy, dw, dh = (deltas[:, 0], deltas[:, 1], deltas[:, 2],
                          deltas[:, 3])
        cx = dx * aw + ax
        cy = dy * ah + ay
        w = jnp.exp(dw) * aw
        h = jnp.exp(dh) * ah
        return jnp.stack([cx - 0.5 * (w - 1.0), cy - 0.5 * (h - 1.0),
                          cx + 0.5 * (w - 1.0), cy + 0.5 * (h - 1.0)],
                         axis=1)

    _base_anchors = base_anchors  # module-level helper (shared with rcnn)

    def proposal_maker(rpn_pre_nms_top_n=6000, rpn_post_nms_top_n=300,
                       threshold=0.7, rpn_min_size=16,
                       scales=(4.0, 8.0, 16.0, 32.0), ratios=(0.5, 1.0, 2.0),
                       feature_stride=16, output_score=False,
                       iou_loss=False):
        scales = _astuple(scales)
        ratios = _astuple(ratios)

        def fn(cls_prob, bbox_pred, im_info):
            # cls_prob (B, 2A, H, W) — [A:] are foreground scores;
            # bbox_pred (B, 4A, H, W); im_info (B, 3) = (h, w, scale)
            B, _, H, W = cls_prob.shape
            base = jnp.asarray(_base_anchors(scales, ratios))  # (A,4)
            A = base.shape[0]
            sx = jnp.arange(W, dtype=jnp.float32) * feature_stride
            sy = jnp.arange(H, dtype=jnp.float32) * feature_stride
            shift = jnp.stack(
                jnp.meshgrid(sx, sy, indexing="xy"), axis=-1)   # (H,W,2)
            shift = jnp.tile(shift, (1, 1, 2))                  # (H,W,4)
            anchors = (shift[:, :, None, :] + base).reshape(-1, 4)

            def one(cls, deltas, info):
                scores = jnp.transpose(cls[A:], (1, 2, 0)).reshape(-1)
                d = deltas.reshape(A, 4, H, W)
                d = jnp.transpose(d, (2, 3, 0, 1)).reshape(-1, 4)
                boxes = _decode_deltas(anchors, d)
                boxes = jnp.stack([
                    jnp.clip(boxes[:, 0], 0, info[1] - 1.0),
                    jnp.clip(boxes[:, 1], 0, info[0] - 1.0),
                    jnp.clip(boxes[:, 2], 0, info[1] - 1.0),
                    jnp.clip(boxes[:, 3], 0, info[0] - 1.0)], axis=1)
                ws = boxes[:, 2] - boxes[:, 0] + 1.0
                hs = boxes[:, 3] - boxes[:, 1] + 1.0
                min_sz = rpn_min_size * info[2]
                valid = (ws >= min_sz) & (hs >= min_sz)
                scores = jnp.where(valid, scores, -jnp.inf)

                k = min(int(rpn_pre_nms_top_n), H * W * A)
                order = jnp.argsort(scores)[::-1][:k]
                cboxes = boxes[order]
                cscores = scores[order]
                iou = _iou_corner(cboxes[:, None, :], cboxes[None, :, :])

                def step(keep, i):
                    kill = (iou[i] > threshold) & \
                        (jnp.arange(k) > i) & keep[i]
                    return keep & ~kill, None
                keep, _ = lax.scan(step, cscores > -jnp.inf,
                                   jnp.arange(k))
                fscores = jnp.where(keep, cscores, -jnp.inf)
                p = min(int(rpn_post_nms_top_n), k)
                sel = jnp.argsort(fscores)[::-1][:p]
                out_boxes = cboxes[sel]
                out_scores = jnp.where(jnp.isfinite(fscores[sel]),
                                       fscores[sel], 0.0)
                live = jnp.isfinite(fscores[sel])[:, None]
                return jnp.where(live, out_boxes, 0.0), \
                    out_scores[:, None]
            boxes, scores = jax.vmap(one)(cls_prob, bbox_pred, im_info)
            p = boxes.shape[1]
            bidx = jnp.repeat(jnp.arange(B, dtype=boxes.dtype), p)
            rois = jnp.concatenate(
                [bidx[:, None], boxes.reshape(-1, 4)], axis=1)
            if output_score:
                return (rois, scores.reshape(-1, 1))
            return rois
        return fn
    register_op("_contrib_Proposal", proposal_maker,
                aliases=("_contrib_MultiProposal", "Proposal"))

    # ---- bounding_box.cc long tail: encode/decode/matching ---------------
    def box_decode_maker(std0=1.0, std1=1.0, std2=1.0, std3=1.0, clip=-1.0,
                         format="corner"):
        def fn(data, anchors):
            # data (B,N,4) deltas; anchors (1,N,4) in `format`
            a = anchors
            if format == "corner":
                wh = a[..., 2:] - a[..., :2]
                ctr = a[..., :2] + 0.5 * wh
            else:
                ctr, wh = a[..., :2], a[..., 2:]
            std = jnp.asarray([std0, std1, std2, std3], data.dtype)
            d = data * std
            xy = d[..., :2] * wh + ctr
            dwh = d[..., 2:]
            if clip > 0:
                # reference clips the dw/dh DELTA pre-exp (bounding_box.cc)
                dwh = jnp.minimum(dwh, clip)
            new_wh = jnp.exp(dwh) * wh
            half = 0.5 * new_wh
            return jnp.concatenate([xy - half, xy + half], axis=-1)
        return fn
    register_op("_contrib_box_decode", box_decode_maker,
                aliases=("box_decode",))

    def box_encode_maker(**_ignored):
        def fn(samples, matches, anchors, refs, means, stds):
            # samples (B,N) in {+1 pos, -1 neg/ignore}; matches (B,N) gt
            # index; anchors (B,N,4) corner; refs (B,M,4) corner gt;
            # means/stds (4,) — returns (targets (B,N,4), masks (B,N,4))
            gt = jnp.take_along_axis(
                refs, matches.astype(jnp.int32)[..., None]
                .clip(0, refs.shape[1] - 1).repeat(4, axis=-1), axis=1)
            awh = anchors[..., 2:] - anchors[..., :2]
            actr = anchors[..., :2] + 0.5 * awh
            gwh = gt[..., 2:] - gt[..., :2]
            gctr = gt[..., :2] + 0.5 * gwh
            eps = 1e-8
            t_xy = (gctr - actr) / (awh + eps)
            t_wh = jnp.log((gwh + eps) / (awh + eps))
            t = jnp.concatenate([t_xy, t_wh], axis=-1)
            t = (t - means.reshape(1, 1, 4)) / stds.reshape(1, 1, 4)
            mask = (samples > 0.5)[..., None].astype(t.dtype)
            return (t * mask, jnp.broadcast_to(mask, t.shape))
        return fn
    register_op("_contrib_box_encode", box_encode_maker,
                aliases=("box_encode",))

    def bipartite_matching_maker(threshold=0.5, is_ascend=False, topk=-1):
        def fn(data):
            # data (B,N,M) pairwise scores; greedy bipartite matching.
            # Returns (row_match (B,N) col idx or -1, col_match (B,M)).
            B, N, M = data.shape
            steps = min(N, M) if topk <= 0 else min(topk, min(N, M))
            sgn = -1.0 if is_ascend else 1.0

            def one(s):
                s = s * sgn  # maximize
                thr = threshold * sgn

                def step(carry, _):
                    s_cur, rows, cols = carry
                    flat = jnp.argmax(s_cur)
                    i, j = flat // M, flat % M
                    ok = s_cur[i, j] >= thr
                    rows = lax.cond(
                        ok, lambda r: r.at[i].set(j.astype(r.dtype)),
                        lambda r: r, rows)
                    cols = lax.cond(
                        ok, lambda c: c.at[j].set(i.astype(c.dtype)),
                        lambda c: c, cols)
                    s_cur = s_cur.at[i, :].set(-jnp.inf)
                    s_cur = s_cur.at[:, j].set(-jnp.inf)
                    return (s_cur, rows, cols), None
                init = (s, jnp.full((N,), -1.0, data.dtype),
                        jnp.full((M,), -1.0, data.dtype))
                (_, rows, cols), _ = lax.scan(step, init,
                                              jnp.arange(steps))
                return rows, cols
            rows, cols = jax.vmap(one)(data)
            return (rows, cols)
        return fn
    register_op("_contrib_bipartite_matching", bipartite_matching_maker,
                aliases=("bipartite_matching",))


def base_anchors(scales, ratios, base_size=16.0):
    """(A,4) corner anchors centered on a base_size cell (numpy,
    trace-time constant; reference: proposal.cc GenerateAnchors)."""
    out = []
    cx = cy = (base_size - 1.0) / 2.0
    area = base_size * base_size
    for r in ratios:
        w = _np.round(_np.sqrt(area / r))
        h = _np.round(w * r)
        for s in scales:
            ws, hs = w * s, h * s
            out.append([cx - 0.5 * (ws - 1), cy - 0.5 * (hs - 1),
                        cx + 0.5 * (ws - 1), cy + 0.5 * (hs - 1)])
    return _np.array(out, _np.float32)


def rpn_anchors(height, width, stride, scales, ratios):
    """All (H*W*A, 4) corner anchors of a feature map, (H, W, A)-ordered —
    the exact enumeration the Proposal op uses."""
    base = base_anchors(tuple(scales), tuple(ratios))
    sx = _np.arange(width, dtype=_np.float32) * stride
    sy = _np.arange(height, dtype=_np.float32) * stride
    gx, gy = _np.meshgrid(sx, sy)                   # (H,W)
    shift = _np.stack([gx, gy, gx, gy], axis=-1)    # (H,W,4)
    return (shift[:, :, None, :] + base).reshape(-1, 4)


def _astuple(v):
    if isinstance(v, (list, tuple)):
        return tuple(v)
    if isinstance(v, str):
        return tuple(float(x) for x in
                     v.strip("()[] ").split(",") if x.strip())
    return (v,)


def _register_misc():
    """Long-tail contrib ops (reference: src/operator/correlation.cc,
    src/operator/contrib/index_copy.cc, src/operator/contrib/
    count_sketch.cc — SURVEY.md §2.2 long-tail row)."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    # ---- Correlation (FlowNet cost volume) -------------------------------
    def correlation_maker(kernel_size=1, max_displacement=1, stride1=1,
                          stride2=1, pad_size=0, is_multiply=True):
        k = int(kernel_size)
        md = int(max_displacement)
        s1, s2, pad = int(stride1), int(stride2), int(pad_size)
        rad = (k - 1) // 2
        border = md + rad
        grid_rad = md // s2           # displacements per side
        D = 2 * grid_rad + 1

        def fn(data1, data2):
            # out[d][n,y,x] = mean over kxk window and channels of
            # p1 * shifted(p2) — ONE lax.scan over the D*D displacement
            # grid (graph size independent of D; FlowNet's D=21 would
            # otherwise unroll 441 ways), with the window sum as a
            # reduce_window per scan step.
            n, c, h, w = data1.shape
            p1 = jnp.pad(data1, ((0, 0), (0, 0), (pad, pad), (pad, pad)))
            p2 = jnp.pad(data2, ((0, 0), (0, 0), (pad, pad), (pad, pad)))
            ph, pw = h + 2 * pad, w + 2 * pad
            out_h = int(_np.ceil((ph - 2 * border) / float(s1)))
            out_w = int(_np.ceil((pw - 2 * border) / float(s1)))
            # static patch of data1 covering every window position
            eh = (out_h - 1) * s1 + k
            ew = (out_w - 1) * s1 + k
            lo = border - rad
            a = lax.slice(p1, (0, 0, lo, lo), (n, c, lo + eh, lo + ew))

            offs = jnp.asarray(
                [(dy * s2, dx * s2)
                 for dy in range(-grid_rad, grid_rad + 1)
                 for dx in range(-grid_rad, grid_rad + 1)], jnp.int32)

            def step(_, off):
                b = lax.dynamic_slice(
                    p2, (0, 0, lo + off[0], lo + off[1]), (n, c, eh, ew))
                q = a * b if is_multiply else jnp.abs(a - b)
                summed = lax.reduce_window(
                    q, jnp.asarray(0, q.dtype), lax.add,
                    (1, 1, k, k), (1, 1, s1, s1), "valid")
                return None, jnp.sum(summed, axis=1) / float(k * k * c)

            _, maps = lax.scan(step, None, offs)   # (D*D, n, oh, ow)
            return jnp.transpose(maps, (1, 0, 2, 3))
        return fn
    register_op("Correlation", correlation_maker,
                aliases=("correlation",))

    # ---- index_copy ------------------------------------------------------
    def index_copy_maker():
        def fn(old, idx, new):
            return old.at[idx.astype(jnp.int32)].set(new)
        return fn
    register_op("_contrib_index_copy", index_copy_maker,
                aliases=("index_copy",))

    # ---- count_sketch ----------------------------------------------------
    def count_sketch_maker(out_dim=None, processing_batch_size=32):
        if out_dim is None:
            from ..base import MXNetError
            raise MXNetError("count_sketch requires out_dim")
        od = int(out_dim)

        def fn(data, h, s):
            # h: target bucket per input dim; s: +-1 signs
            hh = h.reshape(-1).astype(jnp.int32)
            ss = s.reshape(-1).astype(data.dtype)
            signed = data * ss[None, :]
            out = jnp.zeros((data.shape[0], od), data.dtype)
            return out.at[:, hh].add(signed)
        return fn
    register_op("_contrib_count_sketch", count_sketch_maker,
                aliases=("count_sketch",), differentiable=False)


def _register_round3b():
    """Late round-3 contrib additions: adaptive pooling, position-sensitive
    ROI pooling (R-FCN, src/operator/contrib/psroi_pooling.cc), deformable
    convolution (src/operator/contrib/deformable_convolution.cc), index_array,
    allclose.  TPU-first: deformable conv is a bilinear-gather im2col followed
    by one MXU matmul; PSROIPooling is a vmapped static-shape gather."""
    import jax
    import jax.numpy as jnp

    # ---- AdaptiveAvgPooling2D -------------------------------------------
    def adaptive_avg_pool_maker(output_size=1):
        if isinstance(output_size, int):
            oh = ow = int(output_size)
        else:
            oh, ow = (int(s) for s in output_size)

        def fn(data):
            n, c, h, w = data.shape
            # static per-output-cell ranges (numpy loop unrolls at trace
            # time; output sizes are small by construction)
            rows = []
            for i in range(oh):
                y0, y1 = (i * h) // oh, -(-((i + 1) * h) // oh)
                cols = []
                for j in range(ow):
                    x0, x1 = (j * w) // ow, -(-((j + 1) * w) // ow)
                    cols.append(jnp.mean(data[:, :, y0:y1, x0:x1],
                                         axis=(2, 3)))
                rows.append(jnp.stack(cols, axis=-1))
            return jnp.stack(rows, axis=-2)
        return fn
    register_op("_contrib_AdaptiveAvgPooling2D", adaptive_avg_pool_maker,
                aliases=("AdaptiveAvgPooling2D",))

    # ---- PSROIPooling (R-FCN) -------------------------------------------
    # data channels laid out (output_dim, group_size, group_size); each
    # output bin (i,j) reads its own score-map channel.
    def psroi_pooling_maker(spatial_scale=1.0, output_dim=1, pooled_size=7,
                            group_size=0):
        ps = int(pooled_size)
        gs = int(group_size) if group_size else ps
        sr = 2   # fixed sample grid per bin (static shapes for XLA)

        def fn(data, rois):
            _, c, h, w = data.shape

            def one(roi):
                bidx = roi[0].astype(jnp.int32)
                img = data[bidx]
                x1 = roi[1] * spatial_scale
                y1 = roi[2] * spatial_scale
                x2 = roi[3] * spatial_scale
                y2 = roi[4] * spatial_scale
                rw = jnp.maximum(x2 - x1, 0.1)
                rh = jnp.maximum(y2 - y1, 0.1)
                iy = jnp.arange(ps * sr, dtype=jnp.float32)
                ix = jnp.arange(ps * sr, dtype=jnp.float32)
                sy = y1 + (iy + 0.5) * rh / (ps * sr)
                sx = x1 + (ix + 0.5) * rw / (ps * sr)
                yi = jnp.clip(jnp.floor(sy), 0, h - 1).astype(jnp.int32)
                xi = jnp.clip(jnp.floor(sx), 0, w - 1).astype(jnp.int32)
                # grid of sampled values for every channel: (C, ps*sr, ps*sr)
                sampled = img[:, yi, :][:, :, xi]
                pooled = sampled.reshape(c, ps, sr, ps, sr).mean((2, 4))
                # position-sensitive channel selection
                pooled = pooled.reshape(output_dim, gs, gs, ps, ps)
                gi = (jnp.arange(ps) * gs) // ps
                sel = pooled[:, gi[:, None], gi[None, :],
                             jnp.arange(ps)[:, None],
                             jnp.arange(ps)[None, :]]
                return sel                                 # (output_dim,ps,ps)
            return jax.vmap(one)(rois)
        return fn
    register_op("_contrib_PSROIPooling", psroi_pooling_maker,
                aliases=("PSROIPooling",))

    # ---- DeformableConvolution ------------------------------------------
    # Bilinear-gather im2col with learned offsets, then one matmul (the
    # FLOPs ride the MXU; the gather is the only scatter/gather stage).
    def deformable_conv_maker(kernel=(3, 3), stride=(1, 1), dilate=(1, 1),
                              pad=(0, 0), num_filter=1, num_group=1,
                              num_deformable_group=1, no_bias=False,
                              workspace=0, layout=None):
        kh, kw = _astuple(kernel)
        sh, sw = _astuple(stride)
        dh, dw = _astuple(dilate)
        ph, pw = _astuple(pad)
        dg = int(num_deformable_group)

        def fn(data, offset, weight, *maybe_bias):
            n, c, h, w = data.shape
            oh = (h + 2 * ph - (dh * (kh - 1) + 1)) // sh + 1
            ow = (w + 2 * pw - (dw * (kw - 1) + 1)) // sw + 1
            K = kh * kw

            # base sampling grid: (K, OH, OW)
            ky, kx = jnp.meshgrid(jnp.arange(kh), jnp.arange(kw),
                                  indexing="ij")
            base_y = (jnp.arange(oh)[None, :, None] * sh - ph
                      + (ky.reshape(-1) * dh)[:, None, None])
            base_x = (jnp.arange(ow)[None, None, :] * sw - pw
                      + (kx.reshape(-1) * dw)[:, None, None])
            base_y = jnp.broadcast_to(base_y, (K, oh, ow)).astype(jnp.float32)
            base_x = jnp.broadcast_to(base_x, (K, oh, ow)).astype(jnp.float32)

            def one(img, off):
                # img (C,H,W); off (2*dg*K, OH, OW) ordered
                # (dg, K, [y,x], OH, OW) as in the reference layout
                off = off.reshape(dg, K, 2, oh, ow)

                def sample_group(off_g, img_g):
                    # off_g (K,2,OH,OW); img_g (Cg,H,W)
                    yy = base_y + off_g[:, 0]
                    xx = base_x + off_g[:, 1]
                    y0 = jnp.floor(yy)
                    x0 = jnp.floor(xx)
                    ly = yy - y0
                    lx = xx - x0
                    # zero-pad out-of-range samples via validity masks
                    def gather(yi, xi):
                        valid = ((yi >= 0) & (yi < h) &
                                 (xi >= 0) & (xi < w))
                        yc = jnp.clip(yi, 0, h - 1).astype(jnp.int32)
                        xc = jnp.clip(xi, 0, w - 1).astype(jnp.int32)
                        vals = img_g[:, yc, xc]        # (Cg,K,OH,OW)
                        return vals * valid[None].astype(img_g.dtype)
                    v00 = gather(y0, x0)
                    v01 = gather(y0, x0 + 1)
                    v10 = gather(y0 + 1, x0)
                    v11 = gather(y0 + 1, x0 + 1)
                    wy = ly[None]
                    wx = lx[None]
                    return (v00 * (1 - wy) * (1 - wx) + v01 * (1 - wy) * wx
                            + v10 * wy * (1 - wx) + v11 * wy * wx)

                cg = c // dg
                cols = [sample_group(off[g_], img[g_ * cg:(g_ + 1) * cg])
                        for g_ in range(dg)]
                return jnp.concatenate(cols, axis=0)   # (C,K,OH,OW)

            col = jax.vmap(one)(data, offset)          # (N,C,K,OH,OW)
            wmat = weight.reshape(num_filter, -1)      # (O, C/g*K)
            g = int(num_group)
            if g == 1:
                out = jnp.einsum("ok,nkhw->nohw", wmat,
                                 col.reshape(n, c * K, oh, ow))
            else:
                cpg, opg = c // g, num_filter // g
                colg = col.reshape(n, g, cpg * K, oh, ow)
                wg = wmat.reshape(g, opg, cpg * K)
                out = jnp.einsum("gok,ngkhw->ngohw", wg, colg).reshape(
                    n, num_filter, oh, ow)
            if maybe_bias and not no_bias:
                out = out + maybe_bias[0][None, :, None, None]
            return out
        return fn
    register_op("_contrib_DeformableConvolution", deformable_conv_maker,
                aliases=("DeformableConvolution",))

    # ---- index_array -----------------------------------------------------
    def index_array_maker(axes=None):
        def fn(data):
            sel = tuple(axes) if axes is not None else \
                tuple(range(data.ndim))
            grids = jnp.meshgrid(*[jnp.arange(s) for s in data.shape],
                                 indexing="ij")
            # int32 (reference returns int64; jax truncates int64 to int32
            # under the default config, warning on every call)
            return jnp.stack([grids[a] for a in sel],
                             axis=-1).astype(jnp.int32)
        return fn
    register_op("_contrib_index_array", index_array_maker,
                aliases=("index_array",), differentiable=False)

    # ---- flash attention (kernels/flash_attention.py Pallas kernel) ------
    # DIFFERENTIABLE: the Pallas forward carries a custom VJP whose
    # backward is two Pallas kernels of its own, so neither direction
    # materializes the (Lq, Lk) score matrix.  Eager dispatch
    # (use_jit=False) keeps the Mosaic-vs-interpret choice keyed on the
    # data's actual device.
    #
    # Layouts.  Without ``num_heads`` the operands are heads-first,
    # (B*H, L, D) or (B, H, L, D), and ``valid_len`` is (B,) or (B*H,).
    # With ``num_heads=H`` they are tokens-major, (B, L, ..) as a
    # projection leaves them, a head's ``head_dim`` lanes (default: q's
    # width / H) beside the next head's; the result is (B, Lq, H*head_dim)
    # and ``valid_len`` (B,).  ``first_head=(fq, fk, fv)`` is the head of
    # its array at which q, k and v start: a fused projection is read in
    # place by passing the one array three times with (0, H, 2*H) (inside
    # a traced program its gradient then comes back as one array; on the
    # imperative tape each of the three inputs gets its own).  Which
    # kernels' form engages follows from the shapes alone
    # (``kernels.flash_attention.lane_heads`` / ``.tokens_major`` say
    # which): heads of a multiple of 128 lanes are read as lane blocks of
    # the array, an even count of 64-lane heads two to a block, and no
    # transposed copy is made; any other width or count goes through the
    # heads-first form and its transposes.  ``window`` (with ``causal``):
    # a query weighs its own key and the ``window - 1`` before it.
    # ``num_kv_heads`` (tokens-major, heads of whole lane groups): k and v
    # hold that many heads, each read in place by a group of ``num_heads /
    # num_kv_heads`` query heads in a row.
    def flash_attention_maker(causal=False, scale=None, num_heads=None,
                              head_dim=None, first_head=(0, 0, 0),
                              window=None, num_kv_heads=None):
        from ..kernels import flash_attention as _fa

        def fn(q, k, v, valid_len=None):
            # optional 4th input: per-sequence key-padding lengths
            return _fa(q, k, v, causal=causal, scale=scale,
                       valid_len=valid_len, num_heads=num_heads,
                       head_dim=head_dim, first_head=first_head,
                       window=window, num_kv_heads=num_kv_heads)
        return fn

    def flash_attention_vjp_maker(causal=False, scale=None, num_heads=None,
                                  head_dim=None, first_head=(0, 0, 0),
                                  window=None, num_kv_heads=None):
        # recording path: jax.vjp traces the op, so the Mosaic-vs-
        # interpret choice must be made HERE on the concrete arrays,
        # before tracing (the multi_sgd static-kwarg rule)
        from ..kernels import flash_attention as _fa
        from ..kernels.flash_attention import _interpret as _interp

        def wrapper(q, k, v, valid_len=None):
            def attend(a, b, c):
                return _fa(a, b, c, causal=causal, scale=scale,
                           interpret=_interp(q), valid_len=valid_len,
                           num_heads=num_heads, head_dim=head_dim,
                           first_head=first_head, window=window,
                           num_kv_heads=num_kv_heads)
            if valid_len is None:
                return jax.vjp(attend, q, k, v)
            out, vjp3 = jax.vjp(attend, q, k, v)

            def vjp4(g):
                # the tape sees 4 parents; valid_len is a mask, zero grad
                dq, dk, dv = vjp3(g)
                return dq, dk, dv, jnp.zeros_like(valid_len)
            return out, vjp4
        return wrapper
    register_op("_contrib_flash_attention", flash_attention_maker,
                aliases=("flash_attention",), use_jit=False,
                vjp_maker=flash_attention_vjp_maker)

    # ---- routed experts (parallel/moe.py, one chip's share) ---------------
    def routed_experts_maker(top_k=1, first=0, scale=1.0, norm_topk=True,
                             score="sigmoid", activation="silu"):
        from ..parallel.moe import routed_experts as _re

        def fn(x, router_w, router_b, w_gate, w_up, w_down, router_x=None):
            # optional 7th input: what the router reads, where that is not
            # the experts' input
            return _re(x, router_w, router_b, w_gate, w_up, w_down,
                       top_k=top_k, first=first, scale=scale,
                       norm_topk=norm_topk, score=score,
                       activation=activation, router_x=router_x)
        return fn
    # use_jit=False: inside a compiled step its scopes (router, dispatch,
    # experts, combine) stay the program's own, not a nested jit's
    register_op("_contrib_routed_experts", routed_experts_maker,
                aliases=("routed_experts",), use_jit=False)

    # ---- gated delta rule and its short convolution
    # (kernels/gated_delta_rule.py) -----------------------------------------
    def gated_delta_rule_maker():
        from ..kernels.gated_delta_rule import gated_delta_rule as _gdr

        def fn(q, k, v, g, beta):
            # the outputs alone: the state after the last token is the
            # kernel's second result, for the cache that will need it
            return _gdr(q, k, v, g, beta)[0]
        return fn
    # use_jit=False, as routed_experts: its scopes stay the program's own
    register_op("_contrib_gated_delta_rule", gated_delta_rule_maker,
                aliases=("gated_delta_rule",), use_jit=False)

    def causal_conv1d_maker():
        from ..kernels.gated_delta_rule import causal_conv1d as _conv
        return _conv
    register_op("_contrib_causal_conv1d", causal_conv1d_maker,
                aliases=("causal_conv1d",), use_jit=False)

    # ---- allclose --------------------------------------------------------
    def allclose_maker(rtol=1e-5, atol=1e-8, equal_nan=False):
        def fn(a, b):
            return jnp.allclose(a, b, rtol=rtol, atol=atol,
                                equal_nan=equal_nan).astype(
                jnp.float32).reshape(1)
        return fn
    register_op("_contrib_allclose", allclose_maker,
                aliases=("allclose",), differentiable=False)

    # ---- getnnz (src/operator/contrib/nnz.cc; csr there, storage-generic
    # here: the count is the same question on any layout) ------------------
    def getnnz_maker(axis=None):
        from ..base import jax_compute_dtype

        def fn(data):
            # int64 counts under enable_large_tensor(), int32 otherwise
            # (the documented contract, applied without jax's warning)
            return jnp.sum((data != 0).astype(jax_compute_dtype("int64")),
                           axis=axis)
        return fn
    register_op("_contrib_getnnz", getnnz_maker, differentiable=False)

    # ---- div_sqrt_dim (src/operator/contrib/transformer.cc): divide by
    # sqrt of the last dim — the attention-scaling helper -----------------
    def div_sqrt_dim_maker():
        def fn(data):
            return data / jnp.sqrt(jnp.asarray(data.shape[-1],
                                               data.dtype))
        return fn
    register_op("_contrib_div_sqrt_dim", div_sqrt_dim_maker,
                aliases=("div_sqrt_dim",))

    # ---- _sample_unique_zipfian (sample_op.cc, the sampled-softmax
    # candidate sampler): per batch row, n draws from Zipf(range_max) with
    # rejection-dedup; returns (samples, num_tries).  Host-side sampling
    # by design: data-dependent rejection loops do not belong under trace
    # (same stance as boolean_mask), and candidates feed CPU-side lookup
    # anyway ---------------------------------------------------------------
    def sample_unique_zipfian_maker(range_max=None, shape=None, ctx=None):
        import numpy as onp

        from ..base import MXNetError
        rm = int(range_max)
        shp = tuple(int(s) for s in shape)
        if shp[1] > rm:
            raise MXNetError(
                f"_sample_unique_zipfian: cannot draw {shp[1]} unique "
                f"candidates from range_max={rm}")
        dev = None
        if ctx is not None:
            from ..context import Context
            dev = (ctx if isinstance(ctx, Context)
                   else Context.from_str(ctx)).device

        def fn():
            # seeded from the library key stream so mx.random.seed()
            # covers this sampler like every other random op
            from .. import random as _grandom
            key_bits = onp.asarray(_grandom.next_key()).ravel()
            rng = onp.random.default_rng(key_bits.astype(onp.uint32))
            out = onp.empty(shp, onp.int64)
            tries = onp.empty(shp[0], onp.int64)
            log_rm1 = onp.log(rm + 1.0)
            for b in range(shp[0]):
                seen, t = [], 0
                seen_set = set()
                while len(seen) < shp[1]:
                    # inverse-CDF zipfian: floor(exp(u*log(rm+1)))-1
                    cand = int(onp.exp(rng.random() * log_rm1)) - 1
                    cand = min(max(cand, 0), rm - 1)
                    t += 1
                    if cand not in seen_set:
                        seen_set.add(cand)
                        seen.append(cand)
                out[b] = seen
                tries[b] = t
            o, tr = jnp.asarray(out), jnp.asarray(tries)
            if dev is not None:
                o = jax.device_put(o, dev)
                tr = jax.device_put(tr, dev)
            return o, tr
        return fn
    register_op("_sample_unique_zipfian", sample_unique_zipfian_maker,
                differentiable=False, use_jit=False)

    # ---- backward_gradientmultiplier (gradient_multiplier_op.cc): the
    # explicit backward of gradientmultiplier — a scalar scale ------------
    def backward_gradmult_maker(scalar=1.0):
        def fn(x):
            return x * jnp.asarray(scalar, x.dtype)
        return fn
    register_op("_contrib_backward_gradientmultiplier",
                backward_gradmult_maker)


_register()
_register_misc()
_register_round3b()
