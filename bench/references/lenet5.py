"""Plain LeNet-5, the paper's Table II model (44,426 parameters), written
from its published description in jax.numpy: 28x28x1 input, valid 5x5
convolutions with 6 and 16 channels, tanh, 2x2 average pooling, dense
256 -> 120 -> 84 -> 10 with tanh between, softmax cross-entropy.

It imports nothing of the program.  Parameters are a dict of layers, each
``{"w", "b"}``, so ``jax.tree.flatten`` orders the leaves as the flat wire
vector does (layer name, then ``b`` before ``w``).  Also the benchmark's
source of initial weights and of the FLOPs per trained sample.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

IMAGE = (28, 28, 1)
CLASSES = 10
# (name, weight shape, fan-in); convs are HWIO
LAYERS = (
    ("conv1", (5, 5, 1, 6), 25),
    ("conv2", (5, 5, 6, 16), 150),
    ("fc1", (256, 120), 256),
    ("fc2", (120, 84), 120),
    ("fc3", (84, 10), 84),
)
PARAM_COUNT = sum(int(np.prod(s)) + s[-1] for _, s, _ in LAYERS)


def init(key: jax.Array) -> dict:
    """Normal weights scaled by 1/sqrt(fan-in), zero biases."""
    keys = jax.random.split(key, len(LAYERS))
    return {name: {"w": jax.random.normal(k, shape, jnp.float32)
                   / np.sqrt(fan_in),
                   "b": jnp.zeros((shape[-1],), jnp.float32)}
            for k, (name, shape, fan_in) in zip(keys, LAYERS)}


def _conv(x: jax.Array, layer: dict) -> jax.Array:
    """Valid convolution written as one matrix product over the kernel's
    taps: every output pixel's (kh, kw, cin) patch times the weights.
    (A TPU compiles this at ``highest`` precision in seconds, where its
    own convolution takes minutes.)"""
    kh, kw, cin, cout = layer["w"].shape
    _, h, w, _ = x.shape
    oh, ow = h - kh + 1, w - kw + 1
    patches = jnp.concatenate([x[:, i:i + oh, j:j + ow, :]
                               for i in range(kh) for j in range(kw)], axis=-1)
    y = patches @ layer["w"].reshape(kh * kw * cin, cout)
    return jnp.tanh(y + layer["b"])


def _pool(x: jax.Array) -> jax.Array:
    b, h, w, c = x.shape
    return x.reshape(b, h // 2, 2, w // 2, 2, c).mean(axis=(2, 4))


def forward(params: dict, images: jax.Array) -> jax.Array:
    x = _pool(_conv(images, params["conv1"]))
    x = _pool(_conv(x, params["conv2"]))
    x = x.reshape(x.shape[0], -1)
    x = jnp.tanh(x @ params["fc1"]["w"] + params["fc1"]["b"])
    x = jnp.tanh(x @ params["fc2"]["w"] + params["fc2"]["b"])
    return x @ params["fc3"]["w"] + params["fc3"]["b"]


def loss(params: dict, images: jax.Array, labels: jax.Array,
         weights: jax.Array | None = None) -> jax.Array:
    """Mean softmax cross-entropy; ``weights`` masks padded rows."""
    logits = forward(params, images).astype(jnp.float32)
    nll = jax.nn.logsumexp(logits, axis=-1) - jnp.take_along_axis(
        logits, labels[:, None], axis=-1)[:, 0]
    if weights is None:
        return nll.mean()
    return (nll * weights).sum() / jnp.maximum(weights.sum(), 1.0)


def forward_macs_per_layer() -> dict[str, int]:
    """Multiply-accumulates of one sample's forward pass, from the shapes."""
    h, w, _ = IMAGE
    macs = {}
    for name, shape, _ in LAYERS:
        if len(shape) == 4:
            kh, kw, cin, cout = shape
            h, w = h - kh + 1, w - kw + 1
            macs[name] = h * w * cout * kh * kw * cin
            h, w = h // 2, w // 2
        else:
            macs[name] = shape[0] * shape[1]
    return macs


def forward_flops_per_sample() -> int:
    return 2 * sum(forward_macs_per_layer().values())


def train_flops_per_sample() -> int:
    """Forward, the weight gradient of every layer, and the input gradient
    of every layer but the first (nothing consumes the image's gradient).
    Biases, activations and pooling are not counted."""
    macs = forward_macs_per_layer()
    first = LAYERS[0][0]
    return 2 * (2 * sum(macs.values())
                + sum(m for name, m in macs.items() if name != first))
