"""The paper's EMNIST CNN (arXiv:2306.11913, Appendix C): what the program
runs, and a plain reference of the same network.

Two 5x5 SAME convolutions (16 and 32 channels), each followed by ReLU and
2x2 max-pooling, a 128-wide ReLU layer and a 62-way output, trained on
per-sample cross-entropy. The configuration states float32 parameters and
activations at JAX's default matmul precision, which is what the program
runs.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

SOURCE = "arXiv:2306.11913 (EMNIST experiment, Appendix C CNN)"
REDUCED: dict = {}
ASSUMED = {
    "data": "uniform images and labels drawn from the seed",
    "lr": "0.5, the program's default server learning rate",
}


def _shapes(cfg):
    k, (c1, c2) = cfg["conv_kernel"], cfg["conv_channels"]
    flat = (cfg["image_size"] // 4) ** 2 * c2
    return {
        "conv1": ((k, k, 1, c1), k * k),
        "conv2": ((k, k, c1, c2), k * k * c1),
        "dense1": ((flat, cfg["hidden_size"]), flat),
        "b1": ((cfg["hidden_size"],), 0),
        "dense2": ((cfg["hidden_size"], cfg["num_classes"]),
                   cfg["hidden_size"]),
        "b2": ((cfg["num_classes"],), 0),
    }


def init_weights(cfg, key):
    """Seeded weights in the program's parameter layout (traced once, inside
    one jit, by the harness)."""
    shapes = _shapes(cfg)
    keys = jax.random.split(key, len(shapes))
    out = {}
    for k, (name, (shape, fan_in)) in zip(keys, sorted(shapes.items())):
        if fan_in:
            out[name] = jax.random.normal(k, shape, jnp.float32) / jnp.sqrt(
                jnp.float32(fan_in))
        else:
            out[name] = 0.01 * jax.random.normal(k, shape, jnp.float32)
    return out


def program_loss(cfg):
    """The system under test: the program's own CNN loss."""
    from repro.fed.cnn import cnn_loss

    def loss(params, batch):
        return cnn_loss(params, batch["images"], batch["labels"])

    return loss


def control_loss(cfg):
    """The program has no lower-precision path of its own for this model,
    so the control is the reference computed in bfloat16."""
    def loss(params, batch):
        cast = lambda a: a.astype(jnp.bfloat16)
        return reference_loss(
            cfg, jax.tree_util.tree_map(cast, params),
            {"images": cast(batch["images"]), "labels": batch["labels"]})

    return loss


# -- plain reference -----------------------------------------------------------


def _relu(y):
    """``max(y, 0)`` with the derivative 0 at ``y = 0``, ReLU's usual
    convention. (``jnp.maximum`` gives a tie half the gradient; a conv
    output of exactly 0 occurs now and then with the TPU's bfloat16
    operands.)"""
    return jnp.where(y > 0, y, jnp.zeros_like(y))


def _conv_relu_pool(x, w):
    y = jax.lax.conv_general_dilated(
        x, w, window_strides=(1, 1), padding="SAME",
        dimension_numbers=("NHWC", "HWIO", "NHWC"))
    y = _relu(y)
    return jax.lax.reduce_window(y, -jnp.inf, jax.lax.max,
                                 (1, 2, 2, 1), (1, 2, 2, 1), "VALID")


def reference_loss(cfg, params, batch):
    """Mean cross-entropy of the CNN, computed in the dtype of ``params``."""
    x = batch["images"][..., None]
    x = _conv_relu_pool(x, params["conv1"])
    x = _conv_relu_pool(x, params["conv2"])
    x = x.reshape(x.shape[0], -1)
    x = _relu(x @ params["dense1"] + params["b1"])
    logits = x @ params["dense2"] + params["b2"]
    logp = logits - jax.nn.logsumexp(logits, axis=-1, keepdims=True)
    picked = jnp.take_along_axis(logp, batch["labels"][:, None], axis=1)
    return -jnp.mean(picked.astype(jnp.float32))


def model_flops_per_round(cfg, data, cohort) -> float:
    """Forward plus backward FLOPs of the cohort's gradients, from shapes
    (``data``: the traffic's ``images`` sizes):
    2 per multiply-add forward, twice that backward (input and weight
    gradients), less the input gradient of the first layer."""
    s, k = cfg["image_size"], cfg["conv_kernel"]
    c1, c2 = cfg["conv_channels"]
    h, classes = cfg["hidden_size"], cfg["num_classes"]
    conv1 = s * s * k * k * 1 * c1
    conv2 = (s // 2) ** 2 * k * k * c1 * c2
    dense = (s // 4) ** 2 * c2 * h + h * classes
    per_image = 2 * (conv1 + conv2 + dense) * 3 - 2 * conv1
    return float(per_image * data["samples_per_client"] * cohort)
