"""Each configuration's plain float32 reference against the gluon network
it describes, at small widths on the CPU: logits, and the loss and every
gradient of one training step. Plus the operation counts that ``mfu``
rests on, against counts made by hand."""
import os

import numpy as np
import pytest

import chipbench_toy as toy


def _load(config, **changes):
    from chipbench.harness import bench as hbench

    folder = os.path.join(toy.BENCH, "configs", config)
    cfg = hbench.load_json(os.path.join(folder, "config.json"))
    cfg.update(changes)
    return cfg, hbench.load_module(os.path.join(folder, "model.py"))


def _system_grads(net, model, cfg, x, y):
    """Loss and gradients of one training-mode pass through gluon's own
    autograd, keyed like ``layout``."""
    import mxnet_tpu as mx
    from mxnet_tpu import autograd

    loss_fn = model.loss(cfg)
    with autograd.record(train_mode=True):
        loss = loss_fn(net(x), y).mean()
    loss.backward()
    grads = {}
    for (name, _, _), p in zip(model.layout(cfg),
                               net.collect_params().values()):
        if p.grad_req != "null":
            grads[name] = p.grad().asnumpy().astype(np.float32)
    del mx
    return float(loss.asscalar()), grads


def _reference_grads(model, cfg, params, x, y, trainable):
    import jax

    def loss_of(tr):
        merged = dict(params, **tr)
        return model.reference(cfg, merged, (x, y), train=True)["loss"]

    tr = {k: params[k] for k in trainable}
    loss, grads = jax.value_and_grad(loss_of)(tr)
    return float(loss), {k: np.asarray(v) for k, v in grads.items()}


def _compare_step(model, cfg, net, x_nd, y_nd, x, y):
    params = model.export_params(net, cfg)
    loss, grads = _system_grads(net, model, cfg, x_nd, y_nd)
    ref_loss, ref_grads = _reference_grads(model, cfg, params, x, y,
                                           list(grads))
    assert loss == pytest.approx(ref_loss, rel=2e-5)
    assert set(grads) == set(ref_grads)
    # a bias in front of a BatchNorm has a gradient of exactly zero in
    # training mode: both sides then hold rounding noise, so no tensor is
    # held to less than 1e-4 of the largest gradient in the network
    floor = 1e-4 * max(float(np.abs(g).max()) for g in ref_grads.values())
    for name, g in grads.items():
        scale = max(float(np.abs(ref_grads[name]).max()), floor)
        err = float(np.abs(g - ref_grads[name]).max()) / scale
        assert err < 2e-3, (name, err)


def test_resnet_reference_matches_gluon():
    import mxnet_tpu as mx
    from mxnet_tpu import autograd

    cfg, model = _load("resnet50_v1", dtype="float32", **toy.TOY_RESNET)
    net = model.build(cfg, mx.cpu(), 11)
    x = model.check_inputs(cfg, 11, 4)
    y = np.array([1, 3, 5, 7], np.float32)
    with autograd.pause(train_mode=False):
        got = net(mx.nd.array(x)).asnumpy()
    want = np.asarray(model.reference(
        cfg, model.export_params(net, cfg), (x, None))["logits"])
    assert got.shape == want.shape == (4, cfg["classes"])
    # float32 against float32: the two differ by summation order only
    assert np.abs(got - want).max() <= 2e-5 * np.abs(want).max()
    _compare_step(model, cfg, net, mx.nd.array(x), mx.nd.array(y), x, y)


def test_bert_reference_matches_gluon():
    import mxnet_tpu as mx
    from mxnet_tpu import autograd

    # dropout off on the system's side: the reference has none, and a
    # gradient under a random mask compares with nothing
    cfg, model = _load("bert_base", dtype="float32",
                       hidden_dropout_prob=0.0, **toy.TOY_BERT)
    net = model.build(cfg, mx.cpu(), 12)
    x = model.check_inputs(cfg, 12, 3, seq_len=24)
    y = np.array([[0, 5], [7, 7], [20, 23]], np.float32)
    x_nd = mx.nd.array(x, dtype="int32")
    with autograd.pause(train_mode=False):
        got = net(x_nd).asnumpy()
    want = np.asarray(model.reference(
        cfg, model.export_params(net, cfg), (x, None))["logits"])
    assert got.shape == want.shape == (3, 2, 24)
    assert np.abs(got - want).max() <= 2e-5 * np.abs(want).max()
    _compare_step(model, cfg, net, x_nd, mx.nd.array(y), x, y)


def test_layouts_match_the_published_sizes():
    """25.6 M parameters for ResNet-50 (He et al.); for BERT-base the
    published 110 M less the pooler (590 k, unused by a span head) plus
    the span head - the cut-free configurations, counted from layout."""
    cfg, model = _load("resnet50_v1")
    n = sum(int(np.prod(s)) for name, s, _ in model.layout(cfg)
            if not name.endswith((".mean", ".var")))
    assert abs(n - 25.6e6) / 25.6e6 < 0.005
    cfg, model = _load("bert_base")
    n = sum(int(np.prod(s)) for _, s, _ in model.layout(cfg))
    assert n == 109_482_240 - (768 * 768 + 768) + (2 * 768 + 2)


def test_resnet50_flops_against_a_hand_count():
    """Forward multiply-accumulates of ResNet-50 v1 at 224x224 (stride on
    the first 1x1), layer by layer by hand."""
    cfg, model = _load("resnet50_v1")
    stem = 112 * 112 * 64 * 3 * 49
    # (spatial, in, mid, out, blocks); the first block of a stage reads
    # `in` channels and adds a projection, the rest read `out`
    stages = [(56, 64, 64, 256, 3), (28, 256, 128, 512, 4),
              (14, 512, 256, 1024, 6), (7, 1024, 512, 2048, 3)]
    body = 0
    for hw, cin, mid, cout, blocks in stages:
        px = hw * hw
        first = px * (cin * mid + 9 * mid * mid + mid * cout + cin * cout)
        rest = px * (cout * mid + 9 * mid * mid + mid * cout)
        body += first + (blocks - 1) * rest
    by_hand = stem + body + 2048 * 1000
    assert by_hand == 3_857_973_248
    assert model.forward_macs(cfg) == pytest.approx(by_hand, rel=0.01)
    train = model.flops_per_sample(cfg, {"kind": "train"})
    assert train == pytest.approx(6 * by_hand, rel=0.01)
    assert model.flops_per_sample(cfg, {"kind": "serve"}) == \
        pytest.approx(2 * by_hand, rel=0.01)


def test_bert_base_flops_against_six_n_tokens_plus_attention():
    cfg, model = _load("bert_base")
    s = 384
    n = 12 * (4 * 768 * 768 + 2 * 768 * 3072) + 2 * 768
    assert model.matmul_params(cfg) == n
    attention = 12 * 2 * s * 768 * s          # QK^T and PV, per sequence
    want = 6 * n * s + 6 * attention
    got = model.flops_per_sample(cfg, {"kind": "train", "seq_len": s})
    assert got == pytest.approx(want, rel=1e-9)
    # 6.8 TFLOP a step at batch 32
    assert 32 * got == pytest.approx(6.79e12, rel=0.01)
