"""Transforms (parity: python/paddle/vision/transforms/transforms.py +
functional.py).

Numpy-first AND host-side: images are HWC uint8/float arrays (CHW
float32 after ToTensor) and STAY numpy through the whole per-sample
pipeline — a per-sample device tensor costs one host->device transfer
per IMAGE (1.5 img/s vs 22 img/s at batch granularity when last
measured, before PR 1), so the device conversion belongs to the
loader's collate / the ingest pipeline's transfer stage, at batch
granularity.  ``to_tensor``/``ToTensor`` therefore return a host
ndarray by default (``out="tensor"`` restores the reference's
per-sample Tensor for code that needs it).  ``resize`` routes uint8
images through PIL's SIMD resize when PIL is present (~3x the numpy
path); crop/flip/color ops are pure numpy, so the same code runs
inside DataLoader worker processes.
"""
from __future__ import annotations

import numbers
import random
from typing import List, Sequence

import numpy as np

from paddle_tpu.core import Tensor

__all__ = ["Compose", "BaseTransform", "ToTensor", "Normalize", "Resize",
           "RandomHorizontalFlip", "RandomVerticalFlip", "RandomCrop",
           "CenterCrop", "RandomResizedCrop", "Pad", "Transpose",
           "BrightnessTransform", "ContrastTransform", "SaturationTransform",
           "HueTransform", "ColorJitter", "Grayscale", "RandomRotation",
           "to_tensor", "normalize", "resize", "hflip", "vflip", "crop",
           "center_crop", "pad"]


def _as_hwc(img):
    if isinstance(img, Tensor):
        img = img.numpy()
    img = np.asarray(img)
    if img.ndim == 2:
        img = img[:, :, None]
    return img


# -- functional -------------------------------------------------------------


def to_tensor(img, data_format="CHW", out="numpy"):
    """HWC image -> float32 in [0,1], CHW by default.

    ``out="numpy"`` (default) returns a HOST ndarray — the per-sample
    pipeline must never mint a device tensor (one host->device RPC per
    image; the loader's collate owns the transfer at batch
    granularity).  ``out="tensor"`` restores the reference's per-sample
    device Tensor."""
    img = _as_hwc(img)
    if img.dtype == np.uint8:
        img = img.astype(np.float32) / 255.0
    else:
        img = img.astype(np.float32)
    if data_format == "CHW":
        img = img.transpose(2, 0, 1)
    return Tensor(img) if out == "tensor" else img


def normalize(img, mean, std, data_format="CHW", to_rgb=False):
    arr = img.numpy() if isinstance(img, Tensor) else np.asarray(
        img, np.float32)
    mean = np.asarray(mean, np.float32)
    std = np.asarray(std, np.float32)
    if data_format == "CHW":
        arr = (arr - mean[:, None, None]) / std[:, None, None]
    else:
        arr = (arr - mean) / std
    return Tensor(arr) if isinstance(img, Tensor) else arr


def resize(img, size, interpolation="bilinear"):
    """Nearest/bilinear resize in numpy (HWC)."""
    img = _as_hwc(img)
    h, w = img.shape[:2]
    if isinstance(size, int):
        if h <= w:
            oh, ow = size, int(size * w / h)
        else:
            oh, ow = int(size * h / w), size
    else:
        oh, ow = size
    if (oh, ow) == (h, w):
        return img
    if img.dtype == np.uint8 and img.shape[-1] in (1, 3, 4) and \
            interpolation in ("bilinear", "nearest"):
        # PIL's SIMD resize (the reference transforms operate on PIL
        # images, functional.py _interp); ~3x the numpy path per image
        # on the ingest host
        try:
            from PIL import Image
            mode_img = img[:, :, 0] if img.shape[-1] == 1 else img
            pim = Image.fromarray(mode_img)
            res = pim.resize((ow, oh), Image.BILINEAR if
                             interpolation == "bilinear" else Image.NEAREST)
            out = np.asarray(res)
            if img.shape[-1] == 1:
                out = out[:, :, None]
            return out
        except ImportError:
            pass
    if interpolation == "nearest":
        ri = (np.arange(oh) * h / oh).astype(int).clip(0, h - 1)
        ci = (np.arange(ow) * w / ow).astype(int).clip(0, w - 1)
        return img[ri][:, ci]
    # bilinear
    ys = (np.arange(oh) + 0.5) * h / oh - 0.5
    xs = (np.arange(ow) + 0.5) * w / ow - 0.5
    y0 = np.floor(ys).astype(int).clip(0, h - 1)
    x0 = np.floor(xs).astype(int).clip(0, w - 1)
    y1 = (y0 + 1).clip(0, h - 1)
    x1 = (x0 + 1).clip(0, w - 1)
    wy = (ys - y0).clip(0, 1)[:, None, None]
    wx = (xs - x0).clip(0, 1)[None, :, None]
    f = img.astype(np.float32)
    top = f[y0][:, x0] * (1 - wx) + f[y0][:, x1] * wx
    bot = f[y1][:, x0] * (1 - wx) + f[y1][:, x1] * wx
    out = top * (1 - wy) + bot * wy
    return out.astype(img.dtype) if img.dtype == np.uint8 else out


def hflip(img):
    return _as_hwc(img)[:, ::-1]


def vflip(img):
    return _as_hwc(img)[::-1]


def crop(img, top, left, height, width):
    return _as_hwc(img)[top:top + height, left:left + width]


def center_crop(img, output_size):
    img = _as_hwc(img)
    if isinstance(output_size, int):
        output_size = (output_size, output_size)
    h, w = img.shape[:2]
    th, tw = output_size
    top = max(0, (h - th) // 2)
    left = max(0, (w - tw) // 2)
    return crop(img, top, left, th, tw)


def pad(img, padding, fill=0, padding_mode="constant"):
    img = _as_hwc(img)
    if isinstance(padding, int):
        padding = (padding,) * 4  # left, top, right, bottom
    if len(padding) == 2:
        padding = (padding[0], padding[1], padding[0], padding[1])
    l, t, r, b = padding
    mode = {"constant": "constant", "edge": "edge",
            "reflect": "reflect", "symmetric": "symmetric"}[padding_mode]
    kwargs = {"constant_values": fill} if mode == "constant" else {}
    return np.pad(img, ((t, b), (l, r), (0, 0)), mode=mode, **kwargs)


# -- transform classes ------------------------------------------------------


class BaseTransform:
    def __init__(self, keys=None):
        self.keys = keys

    def __call__(self, img):
        return self._apply_image(img)

    def _apply_image(self, img):
        raise NotImplementedError


class Compose:
    def __init__(self, transforms: Sequence):
        self.transforms = list(transforms)

    def __call__(self, data):
        for t in self.transforms:
            data = t(data)
        return data


class ToTensor(BaseTransform):
    """float32 [0,1] CHW conversion — host-side by default (see
    :func:`to_tensor`): the output is a numpy array the collate stage
    batches into ONE device transfer; ``out="tensor"`` restores the
    per-sample device Tensor."""

    def __init__(self, data_format="CHW", keys=None, out="numpy"):
        super().__init__(keys)
        self.data_format = data_format
        self.out = out

    def _apply_image(self, img):
        return to_tensor(img, self.data_format, out=self.out)


class Normalize(BaseTransform):
    def __init__(self, mean=0.0, std=1.0, data_format="CHW", to_rgb=False,
                 keys=None):
        super().__init__(keys)
        if isinstance(mean, numbers.Number):
            mean = [mean, mean, mean]
        if isinstance(std, numbers.Number):
            std = [std, std, std]
        self.mean, self.std = mean, std
        self.data_format = data_format

    def _apply_image(self, img):
        return normalize(img, self.mean, self.std, self.data_format)


class Resize(BaseTransform):
    def __init__(self, size, interpolation="bilinear", keys=None):
        super().__init__(keys)
        self.size = size
        self.interpolation = interpolation

    def _apply_image(self, img):
        return resize(img, self.size, self.interpolation)


class RandomHorizontalFlip(BaseTransform):
    def __init__(self, prob=0.5, keys=None):
        super().__init__(keys)
        self.prob = prob

    def _apply_image(self, img):
        return hflip(img) if random.random() < self.prob else _as_hwc(img)


class RandomVerticalFlip(BaseTransform):
    def __init__(self, prob=0.5, keys=None):
        super().__init__(keys)
        self.prob = prob

    def _apply_image(self, img):
        return vflip(img) if random.random() < self.prob else _as_hwc(img)


class RandomCrop(BaseTransform):
    def __init__(self, size, padding=None, pad_if_needed=False, fill=0,
                 padding_mode="constant", keys=None):
        super().__init__(keys)
        self.size = (size, size) if isinstance(size, int) else size
        self.padding = padding
        self.pad_if_needed = pad_if_needed
        self.fill = fill
        self.padding_mode = padding_mode

    def _apply_image(self, img):
        img = _as_hwc(img)
        if self.padding is not None:
            img = pad(img, self.padding, self.fill, self.padding_mode)
        h, w = img.shape[:2]
        th, tw = self.size
        if self.pad_if_needed and (h < th or w < tw):
            img = pad(img, (0, 0, max(0, tw - w), max(0, th - h)),
                      self.fill, self.padding_mode)
            h, w = img.shape[:2]
        if h < th or w < tw:
            raise ValueError(
                f"RandomCrop: image ({h}x{w}) smaller than crop "
                f"({th}x{tw}); pass pad_if_needed=True")
        top = random.randint(0, max(0, h - th))
        left = random.randint(0, max(0, w - tw))
        return crop(img, top, left, th, tw)


class CenterCrop(BaseTransform):
    def __init__(self, size, keys=None):
        super().__init__(keys)
        self.size = size

    def _apply_image(self, img):
        return center_crop(img, self.size)


class RandomResizedCrop(BaseTransform):
    def __init__(self, size, scale=(0.08, 1.0), ratio=(3 / 4, 4 / 3),
                 interpolation="bilinear", keys=None):
        super().__init__(keys)
        self.size = (size, size) if isinstance(size, int) else size
        self.scale = scale
        self.ratio = ratio
        self.interpolation = interpolation

    def _apply_image(self, img):
        img = _as_hwc(img)
        h, w = img.shape[:2]
        area = h * w
        for _ in range(10):
            target = random.uniform(*self.scale) * area
            ar = random.uniform(*self.ratio)
            cw = int(round((target * ar) ** 0.5))
            ch = int(round((target / ar) ** 0.5))
            if 0 < cw <= w and 0 < ch <= h:
                top = random.randint(0, h - ch)
                left = random.randint(0, w - cw)
                return resize(crop(img, top, left, ch, cw), self.size,
                              self.interpolation)
        return resize(center_crop(img, min(h, w)), self.size,
                      self.interpolation)


class Pad(BaseTransform):
    def __init__(self, padding, fill=0, padding_mode="constant", keys=None):
        super().__init__(keys)
        self.padding, self.fill = padding, fill
        self.padding_mode = padding_mode

    def _apply_image(self, img):
        return pad(img, self.padding, self.fill, self.padding_mode)


class Transpose(BaseTransform):
    def __init__(self, order=(2, 0, 1), keys=None):
        super().__init__(keys)
        self.order = order

    def _apply_image(self, img):
        return _as_hwc(img).transpose(self.order)


def _finish_color(orig, out):
    """Preserve the input dtype/range: uint8 stays clipped uint8, float
    images stay float (reference transforms keep input dtype)."""
    if orig.dtype == np.uint8:
        return np.clip(out, 0, 255).astype(np.uint8)
    return out.astype(orig.dtype)


class BrightnessTransform(BaseTransform):
    def __init__(self, value, keys=None):
        super().__init__(keys)
        self.value = value

    def _apply_image(self, img):
        img = _as_hwc(img)
        f = 1 + random.uniform(-self.value, self.value)
        return _finish_color(img, img.astype(np.float32) * f)


class ContrastTransform(BaseTransform):
    def __init__(self, value, keys=None):
        super().__init__(keys)
        self.value = value

    def _apply_image(self, img):
        img = _as_hwc(img)
        f = 1 + random.uniform(-self.value, self.value)
        x = img.astype(np.float32)
        mean = x.mean()
        return _finish_color(img, (x - mean) * f + mean)


class SaturationTransform(BaseTransform):
    def __init__(self, value, keys=None):
        super().__init__(keys)
        self.value = value

    def _apply_image(self, img):
        img = _as_hwc(img)
        f = 1 + random.uniform(-self.value, self.value)
        x = img.astype(np.float32)
        gray = x.mean(axis=2, keepdims=True)
        return _finish_color(img, gray + (x - gray) * f)


class HueTransform(BaseTransform):
    def __init__(self, value, keys=None):
        super().__init__(keys)
        self.value = value

    def _apply_image(self, img):
        # cheap hue approximation: channel roll mix
        img = _as_hwc(img)
        f = random.uniform(-self.value, self.value)
        x = img.astype(np.float32)
        rolled = np.roll(x, 1, axis=2)
        return _finish_color(img, x * (1 - abs(f)) + rolled * abs(f))


class ColorJitter(BaseTransform):
    def __init__(self, brightness=0, contrast=0, saturation=0, hue=0,
                 keys=None):
        super().__init__(keys)
        self.ts: List[BaseTransform] = []
        if brightness:
            self.ts.append(BrightnessTransform(brightness))
        if contrast:
            self.ts.append(ContrastTransform(contrast))
        if saturation:
            self.ts.append(SaturationTransform(saturation))
        if hue:
            self.ts.append(HueTransform(hue))

    def _apply_image(self, img):
        ts = list(self.ts)
        random.shuffle(ts)
        for t in ts:
            img = t(img)
        return img


class Grayscale(BaseTransform):
    def __init__(self, num_output_channels=1, keys=None):
        super().__init__(keys)
        self.num_output_channels = num_output_channels

    def _apply_image(self, img):
        img = _as_hwc(img)
        x = img.astype(np.float32)
        if x.shape[2] >= 3:
            g = (0.299 * x[:, :, 0] + 0.587 * x[:, :, 1]
                 + 0.114 * x[:, :, 2])
        else:
            g = x[:, :, 0]
        g = g[:, :, None]
        out = np.repeat(g, self.num_output_channels, axis=2)
        return _finish_color(img, out)


class RandomRotation(BaseTransform):
    """90-degree-step random rotation, bounded by ``degrees`` (arbitrary-
    angle interpolation without an image library is round-2 scope; the
    reference uses PIL).  degrees < 90 therefore rotates by 0 — a safe
    subset, never more rotation than asked for."""

    def __init__(self, degrees, keys=None):
        super().__init__(keys)
        self.degrees = degrees if not isinstance(degrees, (tuple, list)) \
            else max(abs(degrees[0]), abs(degrees[1]))

    def _apply_image(self, img):
        img = _as_hwc(img)
        max_k = min(int(self.degrees // 90), 3)
        k = random.randint(0, max_k) if max_k > 0 else 0
        return np.rot90(img, k, axes=(0, 1)).copy()
