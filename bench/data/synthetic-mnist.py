"""Synthetic MNIST-shaped digits for LeNet-5: class-conditional 28x28x1
images, a fixed random template per class plus noise, learnable within a
few rounds.  Made in bulk, float32 images and int32 labels."""
from __future__ import annotations

import numpy as np


def make(n: int, rng: np.random.Generator) -> dict[str, np.ndarray]:
    templates = rng.standard_normal((10, 28, 28, 1), dtype=np.float32)
    labels = rng.integers(0, 10, n).astype(np.int32)
    noise = rng.standard_normal((n, 28, 28, 1), dtype=np.float32)
    images = templates[labels] + np.float32(0.8) * noise
    return {"images": images, "labels": labels}
