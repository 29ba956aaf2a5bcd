"""Round-3 API-surface additions (reference: paddle.nn / paddle.vision
gaps found by a surface sweep): unpooling, fractional pooling, RNNT loss
(numpy-DP golden), adaptive log softmax, pairwise distance, unflatten,
perspective transform."""
import numpy as np
import pytest
import jax
import jax.numpy as jnp

import paddle_tpu as paddle
import paddle_tpu.nn as nn
import paddle_tpu.nn.functional as F


def test_max_unpool2d_roundtrip():
    """pool(return_mask) -> unpool puts every max back in place."""
    rng = np.random.RandomState(0)
    x = rng.randn(2, 3, 8, 8).astype("f4")
    out, idx = F.max_pool2d(paddle.to_tensor(x), 2, stride=2,
                            return_mask=True)
    rec = F.max_unpool2d(out, idx, 2, stride=2)
    assert tuple(rec.shape) == (2, 3, 8, 8)
    # per-plane (paddle mask convention): every pooled value lands at
    # its argmax position within its own (n, c) plane
    rec_p = rec.numpy().reshape(6, -1)
    idx_p = idx.numpy().reshape(6, -1).astype("i8")
    out_p = out.numpy().reshape(6, -1)
    for pl in range(6):
        np.testing.assert_allclose(rec_p[pl][idx_p[pl]], out_p[pl])
        mask = np.zeros(rec_p.shape[1], bool)
        mask[idx_p[pl]] = True
        assert (rec_p[pl][~mask] == 0).all()
    # custom (larger) output_size places values consistently per plane
    rec_big = F.max_unpool2d(out, idx, 2, stride=2, output_size=(10, 10))
    assert tuple(rec_big.shape) == (2, 3, 10, 10)
    # layer wrapper
    rec2 = nn.MaxUnPool2D(2, stride=2)(out, idx)
    np.testing.assert_allclose(rec2.numpy(), rec.numpy())


def test_fractional_max_pool2d():
    rng = np.random.RandomState(1)
    x = rng.randn(1, 2, 9, 9).astype("f4")
    out = F.fractional_max_pool2d(paddle.to_tensor(x), output_size=4,
                                  random_u=0.3)
    assert tuple(out.shape) == (1, 2, 4, 4)
    # every output is the max of SOME region -> must appear in the input
    for v in out.numpy().reshape(-1):
        assert (np.abs(x - v) < 1e-6).any()
    # disjoint regions cover the input: global max must survive
    assert out.numpy().max() == pytest.approx(x.max())
    out_m, idx = F.fractional_max_pool2d(paddle.to_tensor(x), 4,
                                         random_u=0.3, return_mask=True)
    xp = x.reshape(2, -1)
    for pl in range(2):
        np.testing.assert_allclose(
            xp[pl][idx.numpy().reshape(2, -1)[pl].astype('i8')],
            out_m.numpy().reshape(2, -1)[pl])


def _rnnt_golden(lp, lab, blank):
    """Numpy log-space forward DP (Graves 2012), single example."""
    T, U1, V = lp.shape
    U = U1 - 1
    alpha = np.full((T, U1), -np.inf)
    for t in range(T):
        for u in range(U1):
            if t == 0 and u == 0:
                alpha[0, 0] = 0.0
                continue
            cands = []
            if t > 0:
                cands.append(alpha[t - 1, u] + lp[t - 1, u, blank])
            if u > 0:
                cands.append(alpha[t, u - 1] + lp[t, u - 1, lab[u - 1]])
            alpha[t, u] = np.logaddexp.reduce(cands) if cands else -np.inf
    return -(alpha[T - 1, U] + lp[T - 1, U, blank])


def test_rnnt_loss_matches_numpy_dp():
    rng = np.random.RandomState(2)
    B, T, U, V = 2, 5, 3, 6
    logits = rng.randn(B, T, U + 1, V).astype("f4")
    labels = rng.randint(1, V, (B, U)).astype("i4")
    il = np.asarray([T, T - 1], "i4")
    ll = np.asarray([U, U - 1], "i4")
    loss = F.rnnt_loss(paddle.to_tensor(logits), paddle.to_tensor(labels),
                       il, ll, blank=0, fastemit_lambda=0.0,
                       reduction="none")
    lp = np.asarray(jax.nn.log_softmax(jnp.asarray(logits), -1))
    for b in range(B):
        ref = _rnnt_golden(lp[b, :il[b], :ll[b] + 1], labels[b], 0)
        assert float(loss.numpy()[b]) == pytest.approx(ref, rel=1e-4), b
    # grads flow
    x = paddle.to_tensor(logits, stop_gradient=False)
    F.rnnt_loss(x, paddle.to_tensor(labels), il, ll).backward()
    assert np.isfinite(x.grad.numpy()).all()
    # layer wrapper
    l2 = nn.RNNTLoss(blank=0, fastemit_lambda=0.0, reduction="none")(
        paddle.to_tensor(logits), paddle.to_tensor(labels), il, ll)
    np.testing.assert_allclose(l2.numpy(), loss.numpy(), rtol=1e-6)


def test_adaptive_log_softmax_with_loss():
    paddle.seed(3)
    rng = np.random.RandomState(3)
    B, D, NC = 16, 8, 20
    m = nn.AdaptiveLogSoftmaxWithLoss(D, NC, cutoffs=[4, 10])
    x = paddle.to_tensor(rng.randn(B, D).astype("f4"))
    y = paddle.to_tensor(rng.randint(0, NC, (B,)).astype("i4"))
    out, loss = m(x, y)
    assert tuple(out.shape) == (B,)
    # log-probs: all <= 0, loss = -mean
    assert (out.numpy() <= 1e-5).all()
    assert float(loss) == pytest.approx(-out.numpy().mean(), rel=1e-5)
    # full distribution sums to 1: check via exhaustive label sweep on
    # one sample
    probs = []
    for c in range(NC):
        o, _ = m(x[:1], paddle.to_tensor(np.asarray([c], "i4")))
        probs.append(float(np.exp(o.numpy()[0])))
    assert sum(probs) == pytest.approx(1.0, rel=1e-4)


def test_misc_layers_r3():
    rng = np.random.RandomState(4)
    # Unflatten
    x = paddle.to_tensor(rng.randn(2, 12).astype("f4"))
    assert tuple(nn.Unflatten(1, (3, 4))(x).shape) == (2, 3, 4)
    # PairwiseDistance
    a = paddle.to_tensor(rng.randn(5, 7).astype("f4"))
    b = paddle.to_tensor(rng.randn(5, 7).astype("f4"))
    d = nn.PairwiseDistance()(a, b).numpy()
    ref = np.linalg.norm(a.numpy() - b.numpy() + 1e-6, axis=-1)
    np.testing.assert_allclose(d, ref, rtol=1e-5)
    # ChannelShuffle
    x = paddle.to_tensor(np.arange(8, dtype="f4").reshape(1, 8, 1, 1))
    out = nn.ChannelShuffle(2)(x).numpy().reshape(-1)
    np.testing.assert_allclose(out, [0, 4, 1, 5, 2, 6, 3, 7])
    # AdaptiveMaxPool1D/3D
    x = paddle.to_tensor(rng.randn(1, 2, 12).astype("f4"))
    assert tuple(nn.AdaptiveMaxPool1D(4)(x).shape) == (1, 2, 4)
    x = paddle.to_tensor(rng.randn(1, 2, 8, 8, 8).astype("f4"))
    assert tuple(nn.AdaptiveMaxPool3D(2)(x).shape) == (1, 2, 2, 2, 2)
    # TripletMarginWithDistanceLoss (default L2 == TripletMarginLoss eps0)
    anc = paddle.to_tensor(rng.randn(4, 6).astype("f4"))
    pos = paddle.to_tensor(rng.randn(4, 6).astype("f4"))
    neg = paddle.to_tensor(rng.randn(4, 6).astype("f4"))
    l1 = nn.TripletMarginWithDistanceLoss()(anc, pos, neg)
    dp = np.linalg.norm(anc.numpy() - pos.numpy(), axis=-1)
    dn = np.linalg.norm(anc.numpy() - neg.numpy(), axis=-1)
    ref = np.maximum(dp - dn + 1.0, 0).mean()
    assert float(l1) == pytest.approx(ref, rel=1e-4)
    # RNNCellBase exported
    assert issubclass(nn.LSTMCell, nn.RNNCellBase)


def test_perspective_transform_identity():
    from paddle_tpu.vision import transforms as T
    img = np.random.RandomState(5).rand(8, 8, 3).astype("f4")
    pts = [[0, 0], [7, 0], [7, 7], [0, 7]]
    out = T.perspective(img, pts, pts)   # identity homography
    np.testing.assert_allclose(out, img, atol=1e-5)


def test_distributed_surface_r3():
    """gather / object lists / get_backend / split / batch_isend_irecv
    (reference: paddle.distributed API; TPU mapping: ppermute)."""
    import paddle_tpu.distributed as dist
    from jax.sharding import Mesh, PartitionSpec as P
    from jax import shard_map
    smap = lambda f, m, i, o: shard_map(f, mesh=m, in_specs=i,
                                        out_specs=o)

    assert dist.get_backend() == "XLA"
    objs = [{"a": 1}]
    dist.broadcast_object_list(objs, src=0)
    assert objs == [{"a": 1}]
    out = []
    world = dist.get_world_size()
    dist.scatter_object_list(out, [[f"obj{i}"] for i in range(world)],
                             src=0)
    assert out and out[0][0].startswith("obj")
    g2 = dist.new_group(list(range(4)), axis_name=None)
    with pytest.raises(ValueError):
        dist.scatter_object_list([], [["too"], ["few"]], src=0, group=g2)

    # batch_isend_irecv as ring shift on the 8-device mesh
    dist.init_parallel_env()
    g = dist.new_group(list(range(8)), axis_name="g")
    mesh = Mesh(np.asarray(jax.devices()[:8]), ("g",))
    axis = "g"

    from paddle_tpu.framework.core import Tensor

    def ring(v):
        t = Tensor(v)
        recv_buf = Tensor(jnp.zeros_like(v))
        ops = [dist.P2POp(dist.isend, t, 1, g),
               dist.P2POp(dist.irecv, recv_buf, 7, g)]
        dist.batch_isend_irecv(ops)
        return recv_buf._value

    x = jnp.arange(8, dtype=jnp.float32).reshape(8, 1)
    shifted = smap(ring, mesh, P(axis), P(axis))(x)
    # every rank sent to rank+1: result is a ring rotation
    np.testing.assert_allclose(np.asarray(shifted).reshape(-1),
                               np.roll(np.arange(8), 1))

    # gather inside the trace
    def gat(v):
        lst = []
        dist.gather(Tensor(v), lst, dst=0, group=g)
        return jnp.stack([t._value if hasattr(t, "_value") else t
                          for t in lst])
    got = smap(gat, mesh, P(axis), P(axis))(x)
    np.testing.assert_allclose(np.asarray(got).reshape(8, 8)[0],
                               np.arange(8))


def test_random_ops_r3():
    paddle.seed(0)
    n = paddle.to_tensor(np.full((5000,), 20, "i4"))
    p = paddle.to_tensor(np.full((5000,), 0.3, "f4"))
    b = paddle.binomial(n, p).numpy()
    assert b.min() >= 0 and b.max() <= 20
    assert abs(b.mean() - 6.0) < 0.3          # E = np = 6
    ln = paddle.log_normal(mean=0.0, std=0.5, shape=[5000]).numpy()
    assert (ln > 0).all()
    assert abs(np.log(ln).mean()) < 0.1
    x = paddle.zeros([1000])
    paddle.cauchy_(x, loc=2.0, scale=1.0)
    assert abs(float(np.median(x.numpy())) - 2.0) < 0.3


def test_triplet_with_distance_grads_flow():
    """Review r3: the default-distance path must keep the tape (it used
    to rebuild raw Tensors and silently zero all gradients)."""
    rng = np.random.RandomState(6)
    a = paddle.to_tensor(rng.randn(4, 6).astype("f4"), stop_gradient=False)
    p = paddle.to_tensor(rng.randn(4, 6).astype("f4"), stop_gradient=False)
    n = paddle.to_tensor(rng.randn(4, 6).astype("f4"), stop_gradient=False)
    loss = F.triplet_margin_with_distance_loss(a, p, n, swap=True)
    loss.backward()
    assert a.grad is not None and p.grad is not None and n.grad is not None
    assert np.abs(a.grad.numpy()).sum() > 0


def test_tensor_inplace_methods_r3():
    """In-place Tensor method family (reference: paddle.Tensor.*_):
    rebind semantics keep the autograd tape intact."""
    x = paddle.to_tensor(np.ones((2,), "f4"), stop_gradient=False)
    y = x * 3.0
    y.add_(paddle.to_tensor(np.ones((2,), "f4")))
    y.scale_(2.0)
    y.sum().backward()
    np.testing.assert_allclose(x.grad.numpy(), [6.0, 6.0])
    t = paddle.to_tensor(np.ones((2, 2), "f4"))
    t.fill_(5.0)
    assert (t.numpy() == 5.0).all()
    t.zero_()
    assert (t.numpy() == 0.0).all()
    t.uniform_(0.0, 1.0)
    assert ((t.numpy() >= 0) & (t.numpy() <= 1)).all()
    assert t.element_size() == 4 and t.nbytes == 16
    t.detach_()
    assert t.stop_gradient


def test_incubate_segment_and_graph_ops():
    import paddle_tpu.incubate as inc
    x = paddle.to_tensor(np.asarray([[1., 2.], [3., 4.], [5., 6.]], "f4"),
                         stop_gradient=False)
    ids = paddle.to_tensor(np.asarray([0, 0, 1], "i4"))
    s = inc.segment_sum(x, ids)
    np.testing.assert_allclose(s.numpy(), [[4., 6.], [5., 6.]])
    np.testing.assert_allclose(inc.segment_mean(x, ids).numpy(),
                               [[2., 3.], [5., 6.]])
    np.testing.assert_allclose(inc.segment_max(x, ids).numpy(),
                               [[3., 4.], [5., 6.]])
    np.testing.assert_allclose(inc.segment_min(x, ids).numpy(),
                               [[1., 2.], [5., 6.]])
    # differentiable
    s.sum().backward()
    np.testing.assert_allclose(x.grad.numpy(), np.ones((3, 2)))
    out = inc.graph_send_recv(
        x, paddle.to_tensor(np.asarray([0, 1, 2], "i4")),
        paddle.to_tensor(np.asarray([1, 1, 0], "i4")), "mean")
    np.testing.assert_allclose(out.numpy(), [[5., 6.], [2., 3.], [0., 0.]])
    m = inc.softmax_mask_fuse_upper_triangle(
        paddle.to_tensor(np.zeros((1, 1, 4, 4), "f4")))
    np.testing.assert_allclose(m.numpy()[0, 0, 0], [1, 0, 0, 0], atol=1e-6)
    assert float(inc.identity_loss(x, "mean")) == pytest.approx(3.5)


def test_incubate_lookahead_and_model_average():
    from paddle_tpu.incubate.optimizer import LookAhead, ModelAverage
    paddle.seed(0)
    lin = nn.Linear(4, 4)
    inner = paddle.optimizer.SGD(0.1, parameters=lin.parameters())
    opt = LookAhead(inner, alpha=0.5, k=2)
    w0 = lin.weight.numpy().copy()
    for _ in range(2):
        loss = lin(paddle.ones([2, 4])).sum()
        loss.backward()
        opt.step()
        opt.clear_grad()
    # after k steps the weight is slow + alpha*(fast - slow)
    assert not np.allclose(lin.weight.numpy(), w0)
    ma = ModelAverage(parameters=lin.parameters())
    v1 = lin.weight.numpy().copy()
    ma.step()
    lin.weight._value = lin.weight._value + 1.0
    ma.step()
    with ma.apply():
        np.testing.assert_allclose(lin.weight.numpy(), v1 + 0.5,
                                   rtol=1e-6)
    np.testing.assert_allclose(lin.weight.numpy(), v1 + 1.0, rtol=1e-6)


def test_static_extras_r3():
    import paddle_tpu.static as static
    x = paddle.to_tensor(np.asarray([3.0], "f4"), stop_gradient=False)
    y = (x * x).sum()
    (g,) = static.gradients([y], [x])
    np.testing.assert_allclose(g.numpy(), [6.0])
    r = static.py_func(lambda a: a * 2 + 1,
                       paddle.to_tensor(np.asarray([1., 2.], "f4")),
                       paddle.zeros([2]))
    np.testing.assert_allclose(r.numpy(), [3., 5.])
    p = static.create_parameter([2, 2], "float32")
    assert not p.stop_gradient and p.is_parameter
    ema = static.ExponentialMovingAverage(0.5)
    p._value = jnp.ones((2, 2))
    ema.update([p])
    p._value = jnp.full((2, 2), 3.0)
    ema.update([p])
    with ema.apply():
        np.testing.assert_allclose(p.numpy(), np.full((2, 2), 2.0))
    np.testing.assert_allclose(p.numpy(), np.full((2, 2), 3.0))
    attr = static.WeightNormParamAttr(dim=0)
    assert attr.dim == 0


def test_misc_surface_r3():
    """iinfo/finfo/flops/rng aliases/amp queries/device stream shims."""
    assert paddle.iinfo("int32").max == 2**31 - 1
    assert paddle.finfo("bfloat16").max > 3e38
    st = paddle.get_cuda_rng_state()
    paddle.set_cuda_rng_state(st)
    import paddle_tpu.amp as amp
    assert amp.is_bfloat16_supported() and amp.is_float16_supported()
    amp.debugging.check_numerics(paddle.to_tensor(np.ones(3, "f4")))
    with pytest.raises(FloatingPointError):
        amp.debugging.check_numerics(
            paddle.to_tensor(np.asarray([np.inf], "f4")))
    import paddle_tpu.device as device
    s = device.Stream()
    s.synchronize()
    with device.stream_guard(s):
        assert device.current_stream() is s
    assert "cpu" in device.get_all_device_type() or \
        "tpu" in device.get_all_device_type()


def test_flops_via_cost_analysis():
    """paddle.flops reads XLA's compiled cost analysis; LeNet@28x28 is
    ~0.7 MFLOP/img at batch 1 (conv+fc macs x2)."""
    from paddle_tpu.vision.models import LeNet
    fl = paddle.flops(LeNet(), [1, 1, 28, 28])
    assert 3e5 < fl < 3e6, fl


def test_review_fixes_r3b():
    """Review follow-ups: NHWC mask indices, int segment dtype,
    create_parameter init, py_func backward, dtype-stable perspective."""
    import paddle_tpu.static as static
    import paddle_tpu.incubate as inc
    # create_parameter must NOT be all zeros (Xavier init applied)
    p = static.create_parameter([16, 16], "float32")
    assert np.abs(p.numpy()).sum() > 0
    # NHWC mask: spatial index must exclude the channel stride
    x = np.zeros((1, 2, 2, 2), "f4")      # NHWC
    x[0, 1, 1, 0] = 5.0                    # ch0 max at spatial (1,1) -> 3
    x[0, 0, 0, 1] = 7.0                    # ch1 max at spatial (0,0) -> 0
    _, idx = F.max_pool2d(paddle.to_tensor(x), 2, stride=2,
                          return_mask=True, data_format="NHWC")
    assert sorted(idx.numpy().reshape(-1).tolist()) == [0, 3]
    # int segments keep dtype; empty segments fill 0
    xi = paddle.to_tensor(np.asarray([[4], [2]], "i4"))
    ids = paddle.to_tensor(np.asarray([0, 2], "i4"))   # segment 1 empty
    out = inc.segment_max(xi, ids)
    assert str(out.dtype).endswith("int32"), out.dtype
    np.testing.assert_array_equal(out.numpy(), [[4], [0], [2]])
    # py_func custom backward
    # paddle contract: backward_func(*inputs, *outputs, *out_grads)
    xs = paddle.to_tensor(np.asarray([1., 2.], "f4"), stop_gradient=False)
    r2 = static.py_func(lambda a: a * 2, xs, paddle.zeros([2]),
                        backward_func=lambda a, out, g: g * 3)
    r2.sum().backward()
    np.testing.assert_allclose(xs.grad.numpy(), [3., 3.])
    # skip_vars_in_backward_input drops the input from the bwd call
    xs2 = paddle.to_tensor(np.asarray([1., 2.], "f4"), stop_gradient=False)
    r3 = static.py_func(lambda a: a * 2, xs2, paddle.zeros([2]),
                        backward_func=lambda out, g: g * 5,
                        skip_vars_in_backward_input=[xs2])
    r3.sum().backward()
    np.testing.assert_allclose(xs2.grad.numpy(), [5., 5.])
    # RandomPerspective keeps dtype
    from paddle_tpu.vision import transforms as T
    img8 = (np.random.RandomState(0).rand(8, 8, 3) * 255).astype("uint8")
    out8 = T.RandomPerspective(prob=1.0)(img8)
    assert out8.dtype == np.uint8


def test_geometric_namespace():
    """paddle.geometric send_u_recv / send_ue_recv / send_uv parity."""
    import paddle_tpu.geometric as G
    x = paddle.to_tensor(np.asarray([[1., 2.], [3., 4.], [5., 6.]], "f4"),
                         stop_gradient=False)
    e = paddle.to_tensor(np.asarray([[10., 10.], [20., 20.]], "f4"))
    src = np.asarray([0, 1], "i4")
    dst = np.asarray([1, 2], "i4")
    out = G.send_u_recv(x, src, dst, "sum")
    np.testing.assert_allclose(out.numpy(), [[0., 0.], [1., 2.], [3., 4.]])
    out2 = G.send_ue_recv(x, e, src, dst, "add", "sum")
    np.testing.assert_allclose(out2.numpy(),
                               [[0., 0.], [11., 12.], [23., 24.]])
    out3 = G.send_uv(x, x, src, dst, "mul")
    np.testing.assert_allclose(out3.numpy(), [[3., 8.], [15., 24.]])
    out2.sum().backward()
    assert np.isfinite(x.grad.numpy()).all()


def test_lookahead_slow_weights_seeded_and_saved():
    """Review r3b: slow weights seed from the construction-time params
    (first round interpolates toward them) and persist in state_dict."""
    from paddle_tpu.incubate.optimizer import LookAhead
    paddle.seed(1)
    lin = nn.Linear(2, 2)
    w0 = lin.weight.numpy().copy()
    opt = LookAhead(paddle.optimizer.SGD(0.5, parameters=lin.parameters()),
                    alpha=0.5, k=1)
    lin(paddle.ones([1, 2])).sum().backward()
    opt.step()
    # one step, k=1: w = (w0 + w_fast)/2 — NOT w_fast
    fast = w0 - 0.5 * np.ones((2, 2), "f4") * 0  # grad of sum wrt weight = x
    assert not np.allclose(lin.weight.numpy(), w0)
    sd = opt.state_dict()
    assert any(k.startswith("lookahead_slow_") for k in sd)
    # roundtrip keeps the slow copy
    opt2 = LookAhead(paddle.optimizer.SGD(0.5,
                                          parameters=lin.parameters()),
                     alpha=0.5, k=1)
    opt2.set_state_dict(sd)
    assert opt2._steps == 1


def test_inplace_leaf_guard_and_cauchy_detach():
    """Review r3c: grad-requiring leaf in-place raises (paddle
    contract); cauchy_ detaches the producing node like other fillers."""
    w = paddle.to_tensor(np.ones((2,), "f4"), stop_gradient=False)
    with pytest.raises(RuntimeError, match="[Ll]eaf"):
        w.add_(paddle.to_tensor(np.ones((2,), "f4")))
    # no_grad context allows it (manual update loops)
    with paddle.no_grad():
        w.add_(paddle.to_tensor(np.ones((2,), "f4")))
    np.testing.assert_allclose(w.numpy(), [2.0, 2.0])
    # cauchy_ on a derived tensor cuts the tape
    x = paddle.to_tensor(np.ones((4,), "f4"), stop_gradient=False)
    y = x * 2.0
    y.cauchy_()
    y.sum().backward()
    assert x.grad is None


def test_model_average_two_window():
    """ModelAverage window roll: right after max_average_window the
    average still spans the previous window."""
    from paddle_tpu.incubate.optimizer import ModelAverage
    lin = nn.Linear(2, 2)
    ma = ModelAverage(parameters=lin.parameters(), max_average_window=3)
    with paddle.no_grad():
        for v in (1.0, 2.0, 3.0, 10.0):   # 4th step rolls the window
            lin.weight.fill_(v)
            ma.step()
    with ma.apply():
        # average spans ALL 4 samples (old window 1,2,3 + live 10)
        np.testing.assert_allclose(lin.weight.numpy(),
                                   np.full((2, 2), 4.0), rtol=1e-6)


def _lattice_np(lpb, lpe):
    """Numpy transducer DP parameterized by the blank/emit lattices
    directly (single example, full lengths) — the FastEmit surrogate
    reference: L~ = L(lpb, lpe) + lam * L(frozen lpb, lpe)."""
    T, U1 = lpb.shape
    U = U1 - 1
    alpha = np.full((T, U1), -np.inf)
    alpha[0, 0] = 0.0
    for t in range(T):
        for u in range(U1):
            if t == 0 and u == 0:
                continue
            cands = []
            if t > 0:
                cands.append(alpha[t - 1, u] + lpb[t - 1, u])
            if u > 0:
                cands.append(alpha[t, u - 1] + lpe[t, u - 1])
            alpha[t, u] = np.logaddexp.reduce(cands)
    return -(alpha[T - 1, U] + lpb[T - 1, U])


def test_rnnt_fastemit_gradient_finite_difference():
    """FastEmit (VERDICT r3 #7): grad of rnnt_loss(fastemit_lambda=lam)
    must equal the exact gradient of the surrogate
    L + lam * L(stop_grad(blank), emit), finite-differenced in f64."""
    rng = np.random.RandomState(5)
    T, U, V, lam = 3, 2, 4, 0.3
    z0 = rng.randn(T, U + 1, V).astype("f8")
    labels = rng.randint(1, V, (U,)).astype("i4")

    def lsm(z):
        m = z - z.max(-1, keepdims=True)
        return m - np.log(np.exp(m).sum(-1, keepdims=True))

    def split(z):
        lp = lsm(z)
        lpb = lp[:, :, 0]
        lpe = np.stack([lp[:, u, labels[u]] for u in range(U)], 1)
        return lpb, lpe

    lpb0, lpe0 = split(z0)

    def f_full(z):                      # L(lpb(z), lpe(z))
        return _lattice_np(*split(z))

    def f_frozen(z):                    # L(sg(lpb), lpe(z))
        return _lattice_np(lpb0, split(z)[1])

    eps = 1e-5
    ref = np.zeros_like(z0)
    for i in np.ndindex(z0.shape):
        zp, zm = z0.copy(), z0.copy()
        zp[i] += eps
        zm[i] -= eps
        ref[i] = ((f_full(zp) - f_full(zm))
                  + lam * (f_frozen(zp) - f_frozen(zm))) / (2 * eps)

    x = paddle.to_tensor(z0[None].astype("f4"), stop_gradient=False)
    loss = F.rnnt_loss(x, paddle.to_tensor(labels[None]),
                       np.asarray([T], "i4"), np.asarray([U], "i4"),
                       blank=0, fastemit_lambda=lam, reduction="none")
    loss.backward()
    np.testing.assert_allclose(x.grad.numpy()[0], ref, rtol=2e-3,
                               atol=2e-4)
    # identity forward: regularizer must not move the loss value
    plain = F.rnnt_loss(paddle.to_tensor(z0[None].astype("f4")),
                        paddle.to_tensor(labels[None]),
                        np.asarray([T], "i4"), np.asarray([U], "i4"),
                        blank=0, fastemit_lambda=0.0, reduction="none")
    np.testing.assert_allclose(loss.numpy(), plain.numpy(), rtol=1e-6)
    # lam > 0 must actually change the gradient
    x2 = paddle.to_tensor(z0[None].astype("f4"), stop_gradient=False)
    F.rnnt_loss(x2, paddle.to_tensor(labels[None]), np.asarray([T], "i4"),
                np.asarray([U], "i4"), blank=0, fastemit_lambda=0.0,
                reduction="none").backward()
    assert np.abs(x.grad.numpy() - x2.grad.numpy()).max() > 1e-4


def test_segment_ops_traced_ids_num_segments_hint():
    """ADVICE r3: traced segment_ids need an explicit num_segments (XLA
    static shapes); without it the error must be clear, not a
    ConcretizationTypeError."""
    import paddle_tpu.incubate as inc
    x = np.asarray([[1.0, 2.0], [3.0, 4.0], [5.0, 6.0]], "f4")
    ids = np.asarray([0, 0, 1], "i4")

    def traced(v, i):
        return inc.segment_sum(paddle.to_tensor(v), paddle.to_tensor(i),
                               num_segments=2)._value

    out = jax.jit(traced)(jnp.asarray(x), jnp.asarray(ids))
    np.testing.assert_allclose(np.asarray(out), [[4.0, 6.0], [5.0, 6.0]])

    # mean/max/min take the hint too
    def traced_mean(v, i):
        return inc.segment_mean(paddle.to_tensor(v), paddle.to_tensor(i),
                                num_segments=2)._value
    np.testing.assert_allclose(
        np.asarray(jax.jit(traced_mean)(jnp.asarray(x), jnp.asarray(ids))),
        [[2.0, 3.0], [5.0, 6.0]])

    with pytest.raises(ValueError, match="num_segments"):
        jax.jit(lambda v, i: inc.segment_sum(
            paddle.to_tensor(v), paddle.to_tensor(i))._value)(
            jnp.asarray(x), jnp.asarray(ids))


def test_batch_isend_irecv_rejects_inconsistent_shift():
    """ADVICE r3: a batch whose send and recv peers imply different
    rotations must be rejected (the SPMD lowering can only bake one
    uniform shift), not silently mistraced."""
    import paddle_tpu.distributed as dist
    from jax.sharding import Mesh, PartitionSpec as P
    from jax import shard_map as smap
    from paddle_tpu.framework.core import Tensor

    dist.init_parallel_env()
    g = dist.new_group(list(range(8)), axis_name="g2")
    mesh = Mesh(np.asarray(jax.devices()[:8]), ("g2",))

    def bad(v):
        t = Tensor(v)
        recv_buf = Tensor(jnp.zeros_like(v))
        # send to rank+1 but claim to receive from rank+2
        ops = [dist.P2POp(dist.isend, t, 1, g),
               dist.P2POp(dist.irecv, recv_buf, 2, g)]
        dist.batch_isend_irecv(ops)
        return recv_buf._value

    x = jnp.arange(8, dtype=jnp.float32).reshape(8, 1)
    with pytest.raises(ValueError, match="uniform shift|same rotation"):
        smap(bad, mesh=mesh, in_specs=P("g2"), out_specs=P("g2"))(x)


def test_py_func_skip_vars_backward_shapes():
    """ADVICE r3 (adjudicated): skip_vars_in_backward_input only trims
    the backward CALL; backward_func still returns one gradient per
    forward input in forward order — the reference contract (its docs'
    tanh example skips x yet returns dx).  Multi-input + mixed shapes
    exercise the declared callback shapes."""
    from paddle_tpu import static
    x = paddle.to_tensor(np.asarray([1.0, 2.0], "f4"), stop_gradient=False)
    y = paddle.to_tensor(np.asarray([[3.0], [4.0], [5.0]], "f4"),
                         stop_gradient=False)  # different shape than x

    def fwd(a, b):
        return a * float(b.sum())

    # backward sees only x (y skipped) but returns (gx, gy)
    def bwd(a, out, gout):
        return gout * 12.0, np.zeros((3, 1), "f4") + float(
            (gout * a).sum())

    r = static.py_func(fwd, [x, y], paddle.zeros([2]), backward_func=bwd,
                       skip_vars_in_backward_input=[y])
    r.sum().backward()
    np.testing.assert_allclose(x.grad.numpy(), [12.0, 12.0])
    np.testing.assert_allclose(y.grad.numpy(), np.full((3, 1), 3.0))


def test_batch_isend_irecv_bidirectional_pairs_by_shift():
    """Send/recv ops pair by implied shift, not declaration order: a
    bidirectional exchange declared sends-first must work."""
    import paddle_tpu.distributed as dist
    from jax.sharding import Mesh, PartitionSpec as P
    from jax import shard_map as smap
    from paddle_tpu.framework.core import Tensor

    dist.init_parallel_env()
    g = dist.new_group(list(range(8)), axis_name="g3")
    mesh = Mesh(np.asarray(jax.devices()[:8]), ("g3",))

    def bidir(v):
        t = Tensor(v)
        fwd_buf = Tensor(jnp.zeros_like(v))
        bwd_buf = Tensor(jnp.zeros_like(v))
        ops = [dist.P2POp(dist.isend, t, 1, g),          # to rank+1
               dist.P2POp(dist.isend, Tensor(v * 10.0), 7, g),  # to rank-1
               dist.P2POp(dist.irecv, fwd_buf, 7, g),    # from rank-1
               dist.P2POp(dist.irecv, bwd_buf, 1, g)]    # from rank+1
        dist.batch_isend_irecv(ops)
        return fwd_buf._value + bwd_buf._value

    x = jnp.arange(8, dtype=jnp.float32).reshape(8, 1)
    out = smap(bidir, mesh=mesh, in_specs=P("g3"),
               out_specs=P("g3"))(x)
    expect = np.roll(np.arange(8.0), 1) + 10.0 * np.roll(np.arange(8.0), -1)
    np.testing.assert_allclose(np.asarray(out).reshape(-1), expect)


def test_segment_num_segments_traced_hint_rejected():
    import paddle_tpu.incubate as inc
    x = np.asarray([[1.0], [2.0]], "f4")
    ids = np.asarray([0, 1], "i4")
    with pytest.raises(ValueError, match="static"):
        jax.jit(lambda v, i, m: inc.segment_sum(
            paddle.to_tensor(v), paddle.to_tensor(i),
            num_segments=paddle.to_tensor(m))._value)(
            jnp.asarray(x), jnp.asarray(ids), jnp.asarray(2))
