"""Quantization tier (fluid/contrib/slim/quantization roles): fake-quant
ops + STE gradients, QAT module swap + training, PTQ weight packing."""
import numpy as np
import pytest

import paddle_tpu as paddle
import paddle_tpu.nn as nn
import paddle_tpu.nn.functional as F
from paddle_tpu.quantization import (ImperativeQuantAware,
                                     MovingAverageAbsMaxObserver,
                                     QuantizedLinear, dequant_weights,
                                     fake_channel_wise_quantize_dequantize_abs_max,
                                     fake_quantize_dequantize_abs_max,
                                     quant_post_weights)


class TestFakeQuant:
    def test_abs_max_values(self):
        x = np.array([-1.0, 0.3, 0.5, 1.27], np.float32)
        out = fake_quantize_dequantize_abs_max(
            paddle.to_tensor(x)).numpy()
        scale = 1.27
        exp = np.round(x / scale * 127) / 127 * scale
        np.testing.assert_allclose(out, exp, rtol=1e-6)
        # 8-bit grid: at most 255 distinct levels
        assert np.abs(out - x).max() <= scale / 127

    def test_ste_gradient_is_identity(self):
        x = paddle.to_tensor(np.linspace(-1, 1, 16).astype(np.float32))
        x.stop_gradient = False
        y = fake_quantize_dequantize_abs_max(x)
        y.sum().backward()
        np.testing.assert_allclose(x.grad.numpy(), np.ones(16), rtol=1e-6)

    def test_channel_wise_scales(self):
        w = np.stack([np.linspace(-1, 1, 8),
                      np.linspace(-100, 100, 8)]).astype(np.float32)
        out = fake_channel_wise_quantize_dequantize_abs_max(
            paddle.to_tensor(w), quant_axis=0).numpy()
        # each row quantized against its own scale → both rows accurate
        assert np.abs(out[0] - w[0]).max() <= 1 / 127 + 1e-6
        assert np.abs(out[1] - w[1]).max() <= 100 / 127 + 1e-6

    def test_moving_average_observer(self):
        obs = MovingAverageAbsMaxObserver(rate=0.5)
        obs.update(np.array([2.0], np.float32))
        assert obs.scale == 2.0
        obs.update(np.array([4.0], np.float32))
        assert abs(obs.scale - 3.0) < 1e-6


class TestQAT:
    def test_module_swap(self):
        class Net(nn.Layer):
            def __init__(self):
                super().__init__()
                self.fc1 = nn.Linear(4, 8)
                self.inner = nn.Sequential(nn.Linear(8, 8), nn.ReLU())
                self.fc2 = nn.Linear(8, 2)

            def forward(self, x):
                return self.fc2(self.inner(F.relu(self.fc1(x))))

        net = ImperativeQuantAware().quantize(Net())
        assert isinstance(net.fc1, QuantizedLinear)
        assert isinstance(net.fc2, QuantizedLinear)
        assert isinstance(net.inner[0], QuantizedLinear)

    @pytest.mark.slow      # heavy for the 870 s tier-1 cap (PR 21): -m slow
    def test_qat_trains(self):
        paddle.seed(0)
        net = ImperativeQuantAware().quantize(
            nn.Sequential(nn.Linear(4, 16), nn.ReLU(), nn.Linear(16, 2)))
        opt = paddle.optimizer.Adam(learning_rate=0.05,
                                    parameters=net.parameters())
        rng = np.random.default_rng(0)
        x = rng.standard_normal((64, 4)).astype(np.float32)
        y = (x[:, 0] > 0).astype(np.int64)
        losses = []
        for _ in range(30):
            loss = F.cross_entropy(net(paddle.to_tensor(x)),
                                   paddle.to_tensor(y))
            loss.backward()
            opt.step()
            opt.clear_grad()
            losses.append(float(loss))
        assert losses[-1] < losses[0] * 0.3, losses


class TestPTQ:
    def test_weight_pack_roundtrip(self):
        paddle.seed(1)
        net = nn.Sequential(nn.Linear(6, 8), nn.ReLU(), nn.Linear(8, 3))
        packed = quant_post_weights(net)
        assert len(packed) == 2
        for name, d in packed.items():
            assert d["int"].dtype == np.int8
        deq = dequant_weights(packed)
        for name, w in deq.items():
            orig = dict(net.named_parameters())[name].numpy()
            assert np.abs(w - orig).max() <= np.abs(orig).max() / 127 + 1e-6

    def test_ptq_forward_close_to_fp32(self):
        paddle.seed(2)
        net = nn.Sequential(nn.Linear(8, 16), nn.ReLU(), nn.Linear(16, 4))
        rng = np.random.default_rng(3)
        x = rng.standard_normal((32, 8)).astype(np.float32)
        ref = net(paddle.to_tensor(x)).numpy()
        packed = quant_post_weights(net)
        for name, w in dequant_weights(packed).items():
            dict(net.named_parameters())[name].set_value(w)
        out = net(paddle.to_tensor(x)).numpy()
        rel = np.abs(out - ref).max() / (np.abs(ref).max() + 1e-9)
        assert rel < 0.05, rel


def test_int8_inference_execution_parity():
    """The deploy tier executes int8 matmuls (not just packs weights):
    per-channel weight scales + dynamic per-tensor activation scale must
    stay within ~2% of the float forward on a small MLP."""
    from paddle_tpu.quantization import convert_to_int8_inference

    paddle.seed(1)
    net = nn.Sequential(nn.Linear(32, 64), nn.ReLU(), nn.Linear(64, 8))
    x = paddle.to_tensor(
        np.random.default_rng(3).standard_normal((16, 32))
        .astype("float32"))
    ref = net(x).numpy()
    qnet = convert_to_int8_inference(net)
    out = qnet(x).numpy()
    rel = np.abs(out - ref).max() / np.abs(ref).max()
    assert rel < 0.03, rel


def test_int8_inference_under_capture():
    from paddle_tpu.jit import to_static
    from paddle_tpu.quantization import convert_to_int8_inference

    paddle.seed(2)
    net = convert_to_int8_inference(nn.Sequential(nn.Linear(8, 4)))
    x = paddle.to_tensor(np.ones((2, 8), np.float32))
    eager = net(x).numpy()
    jitted = to_static(net)(x).numpy()
    np.testing.assert_allclose(eager, jitted, rtol=1e-6)


def test_int8_conv2d_execution_parity():
    """Int8InferenceConv2D must match a hand-computed s8 conv: quantize
    activations per-tensor, weights per-out-channel, integer conv,
    dequant epilogue — and stay within ~3% of the float conv."""
    from paddle_tpu.quantization import (Int8InferenceConv2D,
                                         _quantize_weight)

    rng = np.random.default_rng(5)
    w = rng.standard_normal((8, 3, 3, 3)).astype(np.float32)
    b = rng.standard_normal((8,)).astype(np.float32)
    x = rng.standard_normal((2, 3, 8, 8)).astype(np.float32)

    conv = nn.Conv2D(3, 8, 3, stride=1, padding=1)
    conv.weight._data = paddle.to_tensor(w)._data
    conv.bias._data = paddle.to_tensor(b)._data
    ref = conv(paddle.to_tensor(x)).numpy()

    q, scale = _quantize_weight(w, out_axis=0)
    qconv = Int8InferenceConv2D(q, scale, b, stride=1, padding=1)
    out = qconv(paddle.to_tensor(x)).numpy()
    assert out.shape == ref.shape
    rel = np.abs(out - ref).max() / np.abs(ref).max()
    assert rel < 0.03, rel

    # exactness of the integer pipeline itself: recompute in numpy
    s_x = max(np.abs(x).max(), 1e-8) / 127.0
    a_q = np.clip(np.round(x / s_x), -127, 127).astype(np.int64)
    import itertools
    acc = np.zeros((2, 8, 8, 8), np.int64)
    xp = np.pad(a_q, ((0, 0), (0, 0), (1, 1), (1, 1)))
    for oc, ic, kh, kw in itertools.product(range(8), range(3),
                                            range(3), range(3)):
        acc[:, oc] += (xp[:, ic, kh:kh + 8, kw:kw + 8]
                       * int(q[oc, ic, kh, kw]))
    want = acc.astype(np.float32) * (s_x * scale)[None, :, None, None] \
        + b[None, :, None, None]
    np.testing.assert_allclose(out, want, rtol=1e-4, atol=1e-4)


@pytest.mark.slow      # heavy for the 870 s tier-1 cap (PR 21): -m slow
def test_int8_conv_deploy_pass_on_resnet18():
    """convert_to_int8_inference over the vision zoo: every Conv2D and
    Linear swapped, predictions stay aligned with the float model."""
    from paddle_tpu.quantization import (Int8InferenceConv2D,
                                         Int8InferenceLinear,
                                         convert_to_int8_inference)
    from paddle_tpu.vision.models import resnet18

    paddle.seed(0)
    net = resnet18(num_classes=10)
    net.eval()
    x = paddle.to_tensor(np.random.default_rng(7)
                         .standard_normal((4, 3, 32, 32))
                         .astype(np.float32))
    ref = net(x).numpy()
    qnet = convert_to_int8_inference(net)

    def count(m, cls):
        n = int(isinstance(m, cls))
        for _, c in m._sub_layers.items():
            n += count(c, cls)
        return n

    assert count(qnet, Int8InferenceConv2D) == 20   # resnet18's convs
    assert count(qnet, Int8InferenceLinear) == 1
    assert count(qnet, nn.Conv2D) == 0
    out = qnet(x).numpy()
    # top-1 agreement on the logits (the accuracy-delta proxy shape)
    assert (out.argmax(1) == ref.argmax(1)).mean() >= 0.75
    rel = np.abs(out - ref).max() / np.abs(ref).max()
    assert rel < 0.25, rel       # int8 conv stack on 32x32 random init
