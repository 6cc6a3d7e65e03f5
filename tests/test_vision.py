"""Vision/text surface tests: model zoo forward+train, transforms,
datasets, detection ops, hapi integration (reference tier:
python/paddle/tests/test_vision_models.py, test_transforms.py)."""
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import nn, optimizer
from paddle_tpu.parallel import make_mesh, set_mesh
from paddle_tpu.vision import models, transforms
from paddle_tpu.vision.datasets import DatasetFolder, FakeData
from paddle_tpu.text.datasets import FakeTextDataset, UCIHousing


@pytest.fixture(autouse=True)
def mesh():
    set_mesh(make_mesh({"dp": 1}))
    yield


def _fwd(model, shape=(2, 3, 64, 64)):
    x = paddle.to_tensor(np.random.default_rng(0).standard_normal(
        shape).astype(np.float32))
    model.eval()
    return model(x)


def test_lenet_forward():
    out = _fwd(models.LeNet(), (2, 1, 28, 28))
    assert out.shape == [2, 10]


def test_resnet18_forward():
    out = _fwd(models.resnet18(num_classes=7))
    assert out.shape == [2, 7]


@pytest.mark.slow      # heavy for the 870 s tier-1 cap (PR 21): -m slow
def test_resnet50_forward():
    out = _fwd(models.resnet50(num_classes=5))
    assert out.shape == [2, 5]


def test_vgg11_forward():
    out = _fwd(models.vgg11(num_classes=4))
    assert out.shape == [2, 4]


@pytest.mark.slow      # heavy for the 870 s tier-1 cap (PR 21): -m slow
def test_mobilenet_forwards():
    assert _fwd(models.mobilenet_v1(num_classes=3)).shape == [2, 3]
    assert _fwd(models.mobilenet_v2(num_classes=3)).shape == [2, 3]


def test_pretrained_raises():
    with pytest.raises(ValueError):
        models.resnet18(pretrained=True)


def test_lenet_trains_on_fakedata():
    from paddle_tpu.io import DataLoader
    from paddle_tpu.jit import TrainStep
    model = models.LeNet()
    opt = optimizer.Adam(learning_rate=1e-3,
                         parameters=model.parameters())
    class SeparableData(FakeData):
        # label signal injected into the image so the loss can drop
        def __getitem__(self, idx):
            img, label = super().__getitem__(idx)
            img[0, :4, :4] = float(label)
            return img, label

    ds = SeparableData(num_samples=64, image_shape=(1, 28, 28))
    loader = DataLoader(ds, batch_size=32, shuffle=True, num_workers=0)
    loss_fn = nn.CrossEntropyLoss()
    step = TrainStep(model, lambda m, x, y: loss_fn(m(x), y), opt)
    losses = []
    for _ in range(6):
        for x, y in loader:
            losses.append(float(step(x, y)))
    assert np.isfinite(losses).all() and losses[-1] < losses[0]


def test_transforms_pipeline():
    t = transforms.Compose([
        transforms.Resize(36),
        transforms.RandomCrop(32),
        transforms.RandomHorizontalFlip(0.5),
        transforms.ToTensor(),
        transforms.Normalize(mean=[0.5, 0.5, 0.5], std=[0.5, 0.5, 0.5]),
    ])
    img = (np.random.default_rng(0).random((48, 40, 3)) * 255).astype(
        np.uint8)
    out = t(img)
    # host-side contract: the per-sample pipeline yields a numpy array
    # (never a per-sample device tensor — the collate owns the device
    # transfer at batch granularity)
    assert isinstance(out, np.ndarray) and out.dtype == np.float32
    assert tuple(out.shape) == (3, 32, 32)
    assert abs(float(out.mean())) < 2.0
    dev = transforms.ToTensor(out="tensor")(img)
    assert not isinstance(dev, np.ndarray)      # opt-in Tensor path


def test_transforms_resize_bilinear_values():
    img = np.arange(16, dtype=np.float32).reshape(4, 4, 1)
    out = transforms.resize(img, (2, 2))
    assert out.shape == (2, 2, 1)
    np.testing.assert_allclose(out[..., 0],
                               [[2.5, 4.5], [10.5, 12.5]], atol=1e-5)


def test_color_transforms():
    img = (np.random.default_rng(1).random((16, 16, 3)) * 255).astype(
        np.uint8)
    for t in (transforms.BrightnessTransform(0.4),
              transforms.ContrastTransform(0.4),
              transforms.SaturationTransform(0.4),
              transforms.HueTransform(0.2),
              transforms.ColorJitter(0.4, 0.4, 0.4, 0.2),
              transforms.Grayscale(3)):
        out = t(img)
        assert out.shape == (16, 16, 3) and out.dtype == np.uint8


def test_dataset_folder(tmp_path):
    for cls in ("cat", "dog"):
        d = tmp_path / cls
        d.mkdir()
        for i in range(3):
            np.save(d / f"{i}.npy",
                    np.zeros((4, 4, 3), np.float32))
    ds = DatasetFolder(str(tmp_path))
    assert len(ds) == 6
    img, label = ds[0]
    assert img.shape == (4, 4, 3) and label in (0, 1)


def test_dataset_missing_file_raises():
    from paddle_tpu.vision.datasets import MNIST
    with pytest.raises(RuntimeError, match="no network egress"):
        MNIST(image_path="/nonexistent/path.gz")


def test_fake_text_dataset():
    ds = FakeTextDataset(num_samples=10, seq_len=16, vocab_size=50,
                         num_classes=2)
    ids, label = ds[3]
    assert ids.shape == (16,) and 0 <= label < 2
    # deterministic
    ids2, _ = ds[3]
    np.testing.assert_array_equal(ids, ids2)


def test_detection_ops():
    from paddle_tpu.vision import ops
    boxes = paddle.to_tensor(np.asarray(
        [[0, 0, 10, 10], [1, 1, 11, 11], [50, 50, 60, 60]], np.float32))
    scores = paddle.to_tensor(np.asarray([0.9, 0.8, 0.7], np.float32))
    keep = ops.nms(boxes, scores, iou_threshold=0.5)
    assert keep.tolist() == [0, 2]
    iou = ops.box_iou(boxes, boxes)
    np.testing.assert_allclose(np.diag(iou.numpy()), 1.0, rtol=1e-5)

    x = paddle.to_tensor(np.random.default_rng(0).standard_normal(
        (1, 4, 16, 16)).astype(np.float32))
    rois = paddle.to_tensor(np.asarray([[0, 0, 8, 8], [4, 4, 12, 12]],
                                       np.float32))
    out = ops.roi_align(x, rois, output_size=4)
    assert out.shape == [2, 4, 4, 4]


def test_hapi_model_fit_lenet():
    from paddle_tpu.hapi.model import Model
    from paddle_tpu.metric import Accuracy
    net = models.LeNet()
    model = Model(net)
    model.prepare(optimizer.Adam(learning_rate=1e-3,
                                 parameters=net.parameters()),
                  nn.CrossEntropyLoss(), Accuracy())
    ds = FakeData(num_samples=64, image_shape=(1, 28, 28))
    model.fit(ds, epochs=1, batch_size=32, verbose=0)
    res = model.evaluate(ds, batch_size=32, verbose=0)
    assert "loss" in res
