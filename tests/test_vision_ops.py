"""Tests for the round-2 op sweep: CTCLoss, Correlation, SyncBatchNorm,
DeformableConvolution, PSROIPooling, fft/ifft, Proposal.

Oracles: torch.nn.functional.ctc_loss (CTC), numpy re-implementations of the
reference CPU kernels (correlation / psroi / proposal NMS), numpy.fft, and
plain Convolution (deformable with zero offsets).
"""
import math

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import nd
from mxnet_tpu.ndarray import invoke


# ------------------------------------------------------------------ CTC loss

def _torch_ctc(data, label, input_lengths, target_lengths, blank):
    import torch
    import torch.nn.functional as F
    logp = F.log_softmax(torch.from_numpy(data), dim=-1)
    return F.ctc_loss(logp, torch.from_numpy(label),
                      torch.from_numpy(input_lengths),
                      torch.from_numpy(target_lengths),
                      blank=blank, reduction="none").numpy()


def test_ctc_loss_matches_torch_blank_first():
    rng = np.random.RandomState(0)
    T, N, C = 12, 4, 6
    data = rng.randn(T, N, C).astype(np.float32)
    # blank_label='first': labels are 1..C-1, 0 is blank/padding
    label = np.array([[1, 2, 3, 0], [2, 2, 0, 0], [5, 4, 3, 2],
                      [1, 0, 0, 0]], np.int32)
    lens = np.array([3, 2, 4, 1], np.int64)
    out = invoke("CTCLoss", [nd.array(data), nd.array(label)], {})
    want = _torch_ctc(data, label, np.full(N, T, np.int64), lens, blank=0)
    np.testing.assert_allclose(out.asnumpy(), want, rtol=1e-4, atol=1e-5)


def test_ctc_loss_blank_last():
    rng = np.random.RandomState(1)
    T, N, C = 10, 3, 5
    data = rng.randn(T, N, C).astype(np.float32)
    # blank_label='last': labels 0..C-2, padding -1, blank channel C-1
    label = np.array([[0, 1, 2], [3, 3, -1], [2, -1, -1]], np.int32)
    lens = np.array([3, 2, 1], np.int64)
    out = invoke("CTCLoss", [nd.array(data), nd.array(label)],
                 {"blank_label": "last"})
    tlabel = np.where(label < 0, 0, label).astype(np.int32)
    want = _torch_ctc(data, tlabel, np.full(N, T, np.int64), lens, blank=C - 1)
    np.testing.assert_allclose(out.asnumpy(), want, rtol=1e-4, atol=1e-5)


def test_ctc_loss_variable_data_lengths():
    rng = np.random.RandomState(2)
    T, N, C = 14, 3, 7
    data = rng.randn(T, N, C).astype(np.float32)
    label = np.array([[1, 2, 0], [4, 5, 6], [2, 0, 0]], np.int32)
    lab_lens = np.array([2, 3, 1], np.int64)
    dat_lens = np.array([14, 9, 6], np.int32)
    out = invoke("CTCLoss",
                 [nd.array(data), nd.array(label),
                  nd.array(dat_lens), nd.array(lab_lens.astype(np.int32))],
                 {"use_data_lengths": True, "use_label_lengths": True})
    want = _torch_ctc(data, label, dat_lens.astype(np.int64), lab_lens, blank=0)
    np.testing.assert_allclose(out.asnumpy(), want, rtol=1e-4, atol=1e-5)


def test_ctc_loss_empty_label_matrix():
    # L=0: the only valid path is all blanks
    rng = np.random.RandomState(20)
    T, N, C = 5, 2, 4
    data = rng.randn(T, N, C).astype(np.float32)
    label = np.zeros((N, 0), np.int32)
    out = invoke("CTCLoss", [nd.array(data), nd.array(label)], {}).asnumpy()
    import torch
    import torch.nn.functional as F
    logp = F.log_softmax(torch.from_numpy(data), dim=-1)
    want = -logp[:, :, 0].sum(dim=0).numpy()
    np.testing.assert_allclose(out, want, rtol=1e-5, atol=1e-6)


def test_hybridized_block_symbol_input():
    # a hybridized block must still trace symbolically (review regression)
    import mxnet_tpu.gluon as gluon
    net = gluon.nn.Dense(3)
    net.initialize()
    net(nd.zeros((2, 4)))
    net.hybridize()
    net(nd.zeros((2, 4)))
    s = net(mx.sym.Variable("data"))
    assert type(s).__name__ == "Symbol"


def test_ctc_loss_gradient_flows():
    import jax
    import jax.numpy as jnp
    from mxnet_tpu.ops.registry import get_op
    op = get_op("CTCLoss")
    data = jnp.asarray(np.random.RandomState(3).randn(6, 2, 4), jnp.float32)
    label = jnp.asarray([[1, 2], [3, 0]], jnp.int32)

    def total(d):
        return jnp.sum(op.fcompute({}, d, label))

    g = jax.grad(total)(data)
    assert g.shape == data.shape
    assert bool(jnp.all(jnp.isfinite(g)))
    assert float(jnp.max(jnp.abs(g))) > 0


# --------------------------------------------------------------- correlation

def _np_correlation(d1, d2, K, md, s1, s2, pad, is_multiply):
    """Direct port of the reference CPU loop (correlation.cc:40-82)."""
    N, C, H, W = d1.shape
    kr = (K - 1) // 2
    border = md + kr
    Hp, Wp = H + 2 * pad, W + 2 * pad
    top_h = int(math.ceil((Hp - 2 * border) / s1))
    top_w = int(math.ceil((Wp - 2 * border) / s1))
    gr = md // s2
    D = 2 * gr + 1
    big = np.zeros((2, N, Hp + 2 * md + K, Wp + 2 * md + K, C), np.float64)
    big[0, :, pad:pad + H, pad:pad + W] = d1.transpose(0, 2, 3, 1)
    big[1, :, pad:pad + H, pad:pad + W] = d2.transpose(0, 2, 3, 1)
    out = np.zeros((N, D * D, top_h, top_w))
    for n in range(N):
        for i in range(top_h):
            for j in range(top_w):
                y1, x1 = i * s1 + md, j * s1 + md
                for tc in range(D * D):
                    s2o = (tc % D - gr) * s2
                    s2p = (tc // D - gr) * s2
                    y2, x2 = y1 + s2p, x1 + s2o
                    p1 = big[0, n, y1:y1 + K, x1:x1 + K]
                    p2 = big[1, n, y2:y2 + K, x2:x2 + K]
                    v = (p1 * p2).sum() if is_multiply else np.abs(p1 - p2).sum()
                    out[n, tc, i, j] = v / (K * K * C)
    return out


@pytest.mark.parametrize("K,md,s1,s2,pad,mult", [
    (1, 2, 1, 1, 2, True),
    (3, 2, 2, 2, 3, True),
    (1, 1, 1, 1, 1, False),
])
def test_correlation_matches_reference_loop(K, md, s1, s2, pad, mult):
    rng = np.random.RandomState(4)
    d1 = rng.randn(2, 3, 8, 9).astype(np.float32)
    d2 = rng.randn(2, 3, 8, 9).astype(np.float32)
    out = invoke("Correlation", [nd.array(d1), nd.array(d2)],
                 {"kernel_size": K, "max_displacement": md, "stride1": s1,
                  "stride2": s2, "pad_size": pad, "is_multiply": mult})
    want = _np_correlation(d1, d2, K, md, s1, s2, pad, mult)
    assert out.shape == want.shape
    np.testing.assert_allclose(out.asnumpy(), want, rtol=1e-4, atol=1e-5)


# ------------------------------------------------------------ sync batchnorm

def test_sync_batch_norm_single_device_matches_bn():
    rng = np.random.RandomState(5)
    x = rng.randn(4, 3, 5, 5).astype(np.float32)
    args = [nd.array(x), nd.ones((3,)), nd.zeros((3,)),
            nd.zeros((3,)), nd.ones((3,))]
    with mx.autograd.train_mode():
        a = invoke("_contrib_SyncBatchNorm", args, {"fix_gamma": False})
        b = invoke("BatchNorm", args, {"fix_gamma": False})
    np.testing.assert_allclose(a[0].asnumpy(), b[0].asnumpy(),
                               rtol=1e-4, atol=1e-5)


def test_sync_batch_norm_cross_device_stats():
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh, PartitionSpec as P
    from jax import shard_map
    from mxnet_tpu.ops.registry import get_op
    op = get_op("_contrib_SyncBatchNorm")
    devs = np.array(jax.devices("cpu")[:4])
    mesh = Mesh(devs, ("dp",))
    rng = np.random.RandomState(6)
    x = jnp.asarray(rng.randn(8, 3, 4, 4), jnp.float32)
    gamma = jnp.ones((3,)); beta = jnp.zeros((3,))
    mm = jnp.zeros((3,)); mv = jnp.ones((3,))
    attrs = {"_training": True, "fix_gamma": False}

    def shard_fn(xs):
        out, mean, invstd = op.fcompute(attrs, xs, gamma, beta, mm, mv)
        return out, mean, invstd

    out, mean, invstd = shard_map(
        shard_fn, mesh=mesh, in_specs=(P("dp"),),
        out_specs=(P("dp"), P(), P()))(x)
    # the synchronized stats must equal the GLOBAL batch stats; the third
    # output is the reference's inverse std (batch_norm.cc:140-154)
    want_mean = x.mean(axis=(0, 2, 3))
    want_var = x.var(axis=(0, 2, 3))
    np.testing.assert_allclose(np.asarray(mean), np.asarray(want_mean),
                               rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(np.asarray(invstd),
                               1.0 / np.sqrt(np.asarray(want_var) + 1e-3),
                               rtol=1e-4, atol=1e-5)
    ref_out, _, _ = op.fcompute(attrs, x, gamma, beta, mm, mv)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref_out),
                               rtol=1e-4, atol=1e-5)


# ------------------------------------------------------ deformable conv

def test_deformable_conv_zero_offset_is_conv():
    rng = np.random.RandomState(7)
    x = rng.randn(2, 4, 7, 7).astype(np.float32)
    w = rng.randn(6, 4, 3, 3).astype(np.float32)
    b = rng.randn(6).astype(np.float32)
    off = np.zeros((2, 2 * 9, 7, 7), np.float32)
    out = invoke("_contrib_DeformableConvolution",
                 [nd.array(x), nd.array(off), nd.array(w), nd.array(b)],
                 {"kernel": (3, 3), "pad": (1, 1), "num_filter": 6})
    want = invoke("Convolution", [nd.array(x), nd.array(w), nd.array(b)],
                  {"kernel": (3, 3), "pad": (1, 1), "num_filter": 6})
    np.testing.assert_allclose(out.asnumpy(), want.asnumpy(),
                               rtol=1e-4, atol=1e-4)


def test_deformable_conv_integer_offset_shifts():
    # an integer offset of (0, +1) everywhere equals convolving data shifted
    # left by one pixel (with zero fill at the border)
    rng = np.random.RandomState(8)
    x = rng.randn(1, 2, 6, 6).astype(np.float32)
    w = rng.randn(3, 2, 1, 1).astype(np.float32)
    off = np.zeros((1, 2, 6, 6), np.float32)
    off[:, 1] = 1.0  # dx = +1
    out = invoke("_contrib_DeformableConvolution",
                 [nd.array(x), nd.array(off), nd.array(w)],
                 {"kernel": (1, 1), "num_filter": 3, "no_bias": True})
    shifted = np.zeros_like(x)
    shifted[..., :-1] = x[..., 1:]
    want = invoke("Convolution", [nd.array(shifted), nd.array(w)],
                  {"kernel": (1, 1), "num_filter": 3, "no_bias": True})
    np.testing.assert_allclose(out.asnumpy(), want.asnumpy(),
                               rtol=1e-4, atol=1e-5)


def test_deformable_conv_groups_and_stride():
    rng = np.random.RandomState(9)
    x = rng.randn(2, 4, 9, 9).astype(np.float32)
    w = rng.randn(8, 2, 3, 3).astype(np.float32)  # num_group=2
    off = np.zeros((2, 2 * 2 * 9, 5, 5), np.float32)  # ndg=2, stride 2
    out = invoke("_contrib_DeformableConvolution",
                 [nd.array(x), nd.array(off), nd.array(w)],
                 {"kernel": (3, 3), "stride": (2, 2), "pad": (1, 1),
                  "num_filter": 8, "num_group": 2, "num_deformable_group": 2,
                  "no_bias": True})
    want = invoke("Convolution", [nd.array(x), nd.array(w)],
                  {"kernel": (3, 3), "stride": (2, 2), "pad": (1, 1),
                   "num_filter": 8, "num_group": 2, "no_bias": True})
    np.testing.assert_allclose(out.asnumpy(), want.asnumpy(),
                               rtol=1e-4, atol=1e-4)


# --------------------------------------------------------------- psroi pool

def _np_psroi(data, rois, scale, out_dim, pooled, gs):
    """Direct port of PSROIPoolForwardCPU (psroi_pooling.cc)."""
    N, C, H, W = data.shape
    R = rois.shape[0]
    out = np.zeros((R, out_dim, pooled, pooled))
    for r in range(R):
        b = int(rois[r, 0])
        sw = round(rois[r, 1]) * scale
        sh = round(rois[r, 2]) * scale
        ew = (round(rois[r, 3]) + 1.0) * scale
        eh = (round(rois[r, 4]) + 1.0) * scale
        rw = max(ew - sw, 0.1)
        rh = max(eh - sh, 0.1)
        bh, bw = rh / pooled, rw / pooled
        for ct in range(out_dim):
            for ph in range(pooled):
                for pw in range(pooled):
                    hs = min(max(int(np.floor(ph * bh + sh)), 0), H)
                    he = min(max(int(np.ceil((ph + 1) * bh + sh)), 0), H)
                    ws = min(max(int(np.floor(pw * bw + sw)), 0), W)
                    we = min(max(int(np.ceil((pw + 1) * bw + sw)), 0), W)
                    gh = min(max(ph * gs // pooled, 0), gs - 1)
                    gw = min(max(pw * gs // pooled, 0), gs - 1)
                    c = (ct * gs + gh) * gs + gw
                    if he <= hs or we <= ws:
                        continue
                    patch = data[b, c, hs:he, ws:we]
                    out[r, ct, ph, pw] = patch.sum() / ((he - hs) * (we - ws))
    return out


def test_psroi_pooling_matches_reference_loop():
    rng = np.random.RandomState(10)
    out_dim, gs = 3, 2
    data = rng.randn(2, out_dim * gs * gs, 10, 12).astype(np.float32)
    rois = np.array([[0, 1, 1, 8, 9], [1, 0, 2, 11, 7], [0, 3, 3, 4, 4]],
                    np.float32)
    out = invoke("_contrib_PSROIPooling", [nd.array(data), nd.array(rois)],
                 {"spatial_scale": 1.0, "output_dim": out_dim,
                  "pooled_size": gs, "group_size": gs})
    want = _np_psroi(data, rois, 1.0, out_dim, gs, gs)
    np.testing.assert_allclose(out.asnumpy(), want, rtol=1e-4, atol=1e-5)


def test_psroi_pooling_scaled():
    rng = np.random.RandomState(11)
    out_dim, pooled = 2, 3
    data = rng.randn(1, out_dim * pooled * pooled, 8, 8).astype(np.float32)
    rois = np.array([[0, 2, 2, 13, 11]], np.float32)
    out = invoke("_contrib_PSROIPooling", [nd.array(data), nd.array(rois)],
                 {"spatial_scale": 0.5, "output_dim": out_dim,
                  "pooled_size": pooled})
    want = _np_psroi(data, rois, 0.5, out_dim, pooled, pooled)
    np.testing.assert_allclose(out.asnumpy(), want, rtol=1e-4, atol=1e-5)


# --------------------------------------------------------------------- fft

def test_fft_matches_numpy():
    rng = np.random.RandomState(12)
    for shape in ((5, 8), (2, 3, 4, 6)):
        x = rng.randn(*shape).astype(np.float32)
        out = invoke("_contrib_fft", [nd.array(x)], {}).asnumpy()
        ref = np.fft.fft(x, axis=-1)
        want = np.stack([ref.real, ref.imag], -1).reshape(shape[:-1] + (-1,))
        np.testing.assert_allclose(out, want, rtol=1e-3, atol=1e-4)


def test_ifft_roundtrip():
    rng = np.random.RandomState(13)
    x = rng.randn(4, 10).astype(np.float32)
    freq = invoke("_contrib_fft", [nd.array(x)], {})
    back = invoke("_contrib_ifft", [freq], {}).asnumpy()
    # unnormalized inverse: ifft(fft(x)) = d * x
    np.testing.assert_allclose(back, x * 10, rtol=1e-3, atol=1e-3)


def test_contrib_namespace_fft():
    from mxnet_tpu.contrib import ndarray as C
    x = nd.array(np.random.RandomState(14).randn(3, 4).astype(np.float32))
    assert C.fft(x).shape == (3, 8)
    assert C.ifft(C.fft(x)).shape == (3, 4)


def test_gluon_ctc_loss_delegates_to_op():
    # reference gluon CTCLoss semantics: blank_label='last', NTC layout
    rng = np.random.RandomState(19)
    N, T, C = 2, 8, 5
    pred = rng.randn(N, T, C).astype(np.float32)
    label = np.array([[0, 1, 2], [3, 3, -1]], np.float32)
    lens = np.array([3, 2], np.int64)
    loss = mx.gluon.loss.CTCLoss()
    out = loss(nd.array(pred), nd.array(label)).asnumpy()
    tlabel = np.where(label < 0, 0, label).astype(np.int32)
    want = _torch_ctc(pred.transpose(1, 0, 2), tlabel,
                      np.full(N, T, np.int64), lens, blank=C - 1)
    np.testing.assert_allclose(out, want, rtol=1e-4, atol=1e-5)


def test_count_sketch():
    from mxnet_tpu.contrib import ndarray as C
    rng = np.random.RandomState(18)
    data = rng.randn(3, 6).astype(np.float32)
    h = np.array([[0, 1, 1, 3, 0, 2]], np.float32)
    s = np.array([[1, -1, 1, 1, -1, 1]], np.float32)
    out = C.count_sketch(nd.array(data), nd.array(h), nd.array(s),
                         out_dim=4).asnumpy()
    want = np.zeros((3, 4), np.float32)
    for i in range(6):
        want[:, int(h[0, i])] += s[0, i] * data[:, i]
    np.testing.assert_allclose(out, want, rtol=1e-5)


# ---------------------------------------------------------------- proposal

def _np_nms_keep(boxes, scores, thresh, post_n):
    order = np.argsort(-scores, kind="stable")
    boxes = boxes[order]
    supp = np.zeros(len(boxes), bool)
    keep = []
    area = (boxes[:, 2] - boxes[:, 0] + 1) * (boxes[:, 3] - boxes[:, 1] + 1)
    for i in range(len(boxes)):
        if supp[i]:
            continue
        keep.append(i)
        if len(keep) >= post_n:
            break
        for j in range(i + 1, len(boxes)):
            if supp[j]:
                continue
            xx1 = max(boxes[i, 0], boxes[j, 0])
            yy1 = max(boxes[i, 1], boxes[j, 1])
            xx2 = min(boxes[i, 2], boxes[j, 2])
            yy2 = min(boxes[i, 3], boxes[j, 3])
            inter = max(0.0, xx2 - xx1 + 1) * max(0.0, yy2 - yy1 + 1)
            if inter / (area[i] + area[j] - inter) > thresh:
                supp[j] = True
    return boxes, keep


def test_proposal_shapes_and_validity():
    rng = np.random.RandomState(15)
    H, W, A = 4, 5, 3
    cls = rng.uniform(size=(1, 2 * A, H, W)).astype(np.float32)
    bbox = (rng.randn(1, 4 * A, H, W) * 0.1).astype(np.float32)
    im_info = np.array([[64.0, 80.0, 1.0]], np.float32)
    post = 8
    out, score = invoke(
        "_contrib_Proposal", [nd.array(cls), nd.array(bbox), nd.array(im_info)],
        {"rpn_pre_nms_top_n": 20, "rpn_post_nms_top_n": post,
         "threshold": 0.7, "rpn_min_size": 4, "feature_stride": 16,
         "scales": (2.0,), "ratios": (0.5, 1.0, 2.0), "output_score": True})
    o = out.asnumpy()
    assert o.shape == (post, 5)
    assert score.asnumpy().shape == (post, 1)
    assert np.all(o[:, 0] == 0)             # batch index
    assert np.all(o[:, 1] >= 0) and np.all(o[:, 3] <= 80 - 1)
    assert np.all(o[:, 2] >= 0) and np.all(o[:, 4] <= 64 - 1)
    assert np.all(o[:, 3] >= o[:, 1]) and np.all(o[:, 4] >= o[:, 2])


def test_proposal_nms_matches_numpy_oracle():
    # large threshold -> no suppression -> proposals are just the top-score
    # transformed anchors; exercise score ordering end-to-end
    rng = np.random.RandomState(16)
    H, W, A = 3, 3, 2
    cls = rng.uniform(size=(1, 2 * A, H, W)).astype(np.float32)
    bbox = np.zeros((1, 4 * A, H, W), np.float32)   # deltas=0: boxes=anchors
    im_info = np.array([[48.0, 48.0, 1.0]], np.float32)
    attrs = {"rpn_pre_nms_top_n": H * W * A, "rpn_post_nms_top_n": 5,
             "threshold": 0.6, "rpn_min_size": 1, "feature_stride": 16,
             "scales": (1.0, 2.0), "ratios": (1.0,), "output_score": True}
    out, score = invoke("_contrib_Proposal",
                        [nd.array(cls), nd.array(bbox), nd.array(im_info)],
                        attrs)
    # oracle: rebuild anchors + scores, NMS in numpy
    from mxnet_tpu.ops.contrib_ops import _generate_anchors
    base = _generate_anchors(16, (1.0,), (1.0, 2.0))
    boxes, scores_all = [], []
    for h in range(H):
        for w in range(W):
            for a in range(A):
                bx = base[a] + np.array([w * 16, h * 16, w * 16, h * 16])
                boxes.append(np.clip(bx, 0, 47))
                scores_all.append(cls[0, A + a, h, w])
    boxes = np.asarray(boxes, np.float32)
    scores_all = np.asarray(scores_all, np.float32)
    sboxes, keep = _np_nms_keep(boxes, scores_all, 0.6, 5)
    want = np.stack([sboxes[keep[i % len(keep)]] for i in range(5)])
    np.testing.assert_allclose(out.asnumpy()[:, 1:], want, rtol=1e-4, atol=1e-3)


def test_proposal_batched():
    rng = np.random.RandomState(17)
    cls = rng.uniform(size=(2, 4, 3, 3)).astype(np.float32)
    bbox = (rng.randn(2, 8, 3, 3) * 0.05).astype(np.float32)
    im_info = np.tile(np.array([[48.0, 48.0, 1.0]], np.float32), (2, 1))
    out = invoke("_contrib_Proposal",
                 [nd.array(cls), nd.array(bbox), nd.array(im_info)],
                 {"rpn_post_nms_top_n": 4, "rpn_min_size": 1,
                  "scales": (2.0,), "ratios": (1.0, 2.0)})
    o = out.asnumpy()
    assert o.shape == (8, 5)
    assert np.all(o[:4, 0] == 0) and np.all(o[4:, 0] == 1)


def test_deconvolution_adj_dilate_match_scatter_reference():
    """Deconvolution with adj/dilate against a first-principles scatter-add
    (reference deconvolution-inl.h semantics: out = (i-1)s + (k-1)d + 1
    - 2p + adj, adj widening the trailing side only — applying adj to
    both sides was a real bug this pins)."""
    def ref_deconv(x, w, s, p, adj, d):
        B, Ci, H, W = x.shape
        _, Co, K, _ = w.shape
        OH = (H - 1) * s + (K - 1) * d + 1 - 2 * p + adj
        OW = (W - 1) * s + (K - 1) * d + 1 - 2 * p + adj
        out = np.zeros((B, Co, OH + 2 * p, OW + 2 * p), np.float64)
        for b in range(B):
            for ci in range(Ci):
                for co in range(Co):
                    for i in range(H):
                        for j in range(W):
                            for ki in range(K):
                                for kj in range(K):
                                    out[b, co, i * s + ki * d,
                                        j * s + kj * d] += \
                                        x[b, ci, i, j] * w[ci, co, ki, kj]
        return out[:, :, p:p + OH, p:p + OW]

    rng = np.random.RandomState(0)
    for (s, p, adj, d) in [(2, 1, 1, 1), (2, 0, 0, 2), (3, 1, 2, 1),
                           (2, 1, 1, 2)]:
        x = rng.randn(2, 3, 4, 4).astype(np.float32)
        w = rng.randn(3, 5, 3, 3).astype(np.float32)
        want = ref_deconv(x.astype(np.float64), w.astype(np.float64),
                          s, p, adj, d)
        got = nd.Deconvolution(nd.array(x), nd.array(w), kernel=(3, 3),
                               stride=(s, s), pad=(p, p), adj=(adj, adj),
                               dilate=(d, d), num_filter=5,
                               no_bias=True).asnumpy()
        np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4,
                                   err_msg=str((s, p, adj, d)))


def test_avg_pooling_full_convention_clipped_divisor():
    """avg Pooling with pooling_convention='full': the divisor is the
    window area clipped to the padded extent [-p, i+p) — padding cells
    count, the ceil-extra region does not (reference pool.h:273-286).
    Dividing ceil-mode edge windows by the full kernel size was a real
    bug this pins."""
    def ref_avg_full(x, k, s, p):
        H = x.shape[2]
        O = int(np.ceil((H + 2 * p - k) / s)) + 1
        out = np.zeros((1, 1, O, O), np.float64)
        for i in range(O):
            for j in range(O):
                hs, ws = i * s - p, j * s - p
                he = min(hs + k, H + p)
                we = min(ws + k, H + p)
                size = (he - hs) * (we - ws)  # clipped to padded extent
                hs_, ws_ = max(hs, 0), max(ws, 0)
                he_, we_ = min(he, H), min(we, H)
                out[0, 0, i, j] = x[0, 0, hs_:he_, ws_:we_].sum() / size
        return out

    rng = np.random.RandomState(1)
    for (k, s, p) in [(2, 2, 0), (3, 2, 1), (2, 3, 1)]:
        x = rng.rand(1, 1, 5, 5).astype(np.float32)
        got = nd.Pooling(nd.array(x), kernel=(k, k), stride=(s, s),
                         pad=(p, p), pool_type="avg",
                         pooling_convention="full").asnumpy()
        want = ref_avg_full(x.astype(np.float64), k, s, p)
        assert got.shape == want.shape, (k, s, p, got.shape, want.shape)
        np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6,
                                   err_msg=str((k, s, p)))


def test_box_nms_matches_reference_docstring_example():
    """box_nms output contract (bounding_box.cc:70-77's own example):
    sorted by score descending, survivors first, suppressed rows filled
    entirely with -1 at the end."""
    x = np.array([[0, 0.5, 0.1, 0.1, 0.2, 0.2],
                  [1, 0.4, 0.1, 0.1, 0.2, 0.2],
                  [0, 0.3, 0.1, 0.1, 0.14, 0.14],
                  [2, 0.6, 0.5, 0.5, 0.7, 0.8]], np.float32)
    out = nd._contrib_box_nms(nd.array(x), overlap_thresh=0.1,
                              coord_start=2, score_index=1, id_index=0,
                              force_suppress=True).asnumpy()
    want = np.array([[2, 0.6, 0.5, 0.5, 0.7, 0.8],
                     [0, 0.5, 0.1, 0.1, 0.2, 0.2],
                     [-1, -1, -1, -1, -1, -1],
                     [-1, -1, -1, -1, -1, -1]], np.float32)
    np.testing.assert_allclose(out, want)


def test_bilinear_sampler_zero_pads_out_of_boundary():
    """Out-boundary sample points are ZERO, and partially-outside lerps
    keep only the in-bounds corners' shares (bilinear_sampler.cc:61-67;
    clamping to the edge value was a real divergence this pins)."""
    data = nd.array(np.arange(16, dtype=np.float32).reshape(1, 1, 4, 4))
    grid = np.zeros((1, 2, 2, 2), np.float32)
    grid[0, 0] = [[-2.0, 0.0], [0.5, 2.0]]
    grid[0, 1] = [[0.0, 0.0], [0.5, 0.0]]
    out = nd.BilinearSampler(data, nd.array(grid)).asnumpy().ravel()
    np.testing.assert_allclose(out, [0.0, 7.5, 11.25, 0.0], atol=1e-6)
    grid2 = np.zeros((1, 2, 1, 1), np.float32)
    grid2[0, 0] = [[1.1]]
    out2 = nd.BilinearSampler(data, nd.array(grid2)).asnumpy().ravel()
    np.testing.assert_allclose(out2, [7.65], atol=1e-5)
