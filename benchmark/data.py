"""Seeded inputs: MNIST-shaped images made by the benchmark itself.

The trainers take ``datasets=(train, test)``; the benchmark hands them these
splits so that a run reads no file and the same seed gives the same rows. Rows
all differ (each has its own noise), so a feed that repeats or drops rows
shows in the loss.
"""

from __future__ import annotations

import numpy as np

# torchvision's MNIST normalisation (reference src/train.py:28-30); the LM
# trainer's tokenizer inverts it before binning.
MNIST_MEAN = 0.1307
MNIST_STD = 0.3081


def images_u8(n: int, seed: int) -> tuple[np.ndarray, np.ndarray]:
    """``n`` 28x28 uint8 images and labels 0..9: a bright bar pattern whose
    position and width follow the label, under per-image noise."""
    rng = np.random.default_rng(seed)
    labels = rng.integers(0, 10, size=n)
    yy, xx = np.mgrid[0:28, 0:28]
    cy = 6 + 2 * (labels % 5) + rng.integers(-2, 3, size=n)
    cx = 8 + 4 * (labels // 5) + rng.integers(-2, 3, size=n)
    half = 2 + labels % 3
    bar = ((np.abs(yy[None] - cy[:, None, None]) <= half[:, None, None])
           | (np.abs(xx[None] - cx[:, None, None]) <= 1))
    img = bar * rng.uniform(150, 255, size=(n, 1, 1))
    img = img + rng.normal(0.0, 12.0, size=img.shape)
    return np.clip(img, 0, 255).astype(np.uint8), labels.astype(np.int32)


def normalize(u8: np.ndarray) -> np.ndarray:
    """uint8 [N,28,28] -> float32 NHWC, normalised as the trainers expect."""
    x = (u8.astype(np.float32) / 255.0 - MNIST_MEAN) / MNIST_STD
    return x[..., None]


def pixel_tokens(images: np.ndarray, num_levels: int) -> np.ndarray:
    """Normalised NHWC images -> [N, 784] int32 gray-level ids, by the LM
    trainer's rule (un-normalise, round to ``num_levels`` uniform levels)."""
    raw = images.reshape(images.shape[0], -1) * np.float32(MNIST_STD) \
        + np.float32(MNIST_MEAN)
    return np.clip(np.round(raw * np.float32(num_levels - 1)), 0,
                   num_levels - 1).astype(np.int32)
