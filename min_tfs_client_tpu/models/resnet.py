"""ResNet-v1.5 family (BASELINE.md config 2: ResNet50 ImageNet).

Inference-mode design for the MXU: NHWC convolutions in bfloat16 via
lax.conv_general_dilated (XLA tiles convs onto the systolic array), batch
norm folded to a per-channel affine at load time (scale/bias precomputed
from gamma/beta/mean/var — no reduction work at serve time), one fused
residual add+relu per block. The reference would serve this as a frozen
GraphDef through Session::Run (SURVEY.md §2.6); here it is a first-class
jittable function.
"""

from __future__ import annotations

from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np

from min_tfs_client_tpu.models import layers as nn


@dataclass(frozen=True)
class ResNetConfig:
    stage_sizes: tuple = (3, 4, 6, 3)          # ResNet50
    width: int = 64
    num_classes: int = 1000
    image_size: int = 224

    @staticmethod
    def resnet50(**kw) -> "ResNetConfig":
        return ResNetConfig(**kw)

    @staticmethod
    def tiny(**kw) -> "ResNetConfig":
        kw.setdefault("stage_sizes", (1, 1))
        kw.setdefault("width", 8)
        kw.setdefault("num_classes", 10)
        kw.setdefault("image_size", 32)
        return ResNetConfig(**kw)


def _conv_init(rng, kh, kw, c_in, c_out) -> dict:
    fan_in = kh * kw * c_in
    kernel = jax.random.normal(rng, (kh, kw, c_in, c_out), jnp.float32)
    return {"kernel": kernel * np.sqrt(2.0 / fan_in),
            # Folded batchnorm: y = conv(x) * scale + bias. Identity at init;
            # checkpoint import folds gamma/beta/mean/var into these.
            "scale": jnp.ones((c_out,), jnp.float32),
            "bias": jnp.zeros((c_out,), jnp.float32)}


def fold_batchnorm(conv: dict, gamma, beta, mean, var, *,
                   eps: float = 1e-5) -> dict:
    """Fold BN statistics into the conv's affine (load-time, not serve-time)."""
    scale = np.asarray(gamma) / np.sqrt(np.asarray(var) + eps)
    return {"kernel": conv["kernel"],
            "scale": jnp.asarray(scale, jnp.float32),
            "bias": jnp.asarray(beta - mean * scale, jnp.float32)}


def _conv(params: dict, x: jax.Array, *, stride: int = 1,
          relu: bool = True) -> jax.Array:
    kernel = params["kernel"].astype(nn.COMPUTE_DTYPE)
    kh = kernel.shape[0]
    pad = (kh - 1) // 2
    y = jax.lax.conv_general_dilated(
        x.astype(nn.COMPUTE_DTYPE), kernel,
        window_strides=(stride, stride),
        padding=[(pad, pad), (pad, pad)],
        dimension_numbers=("NHWC", "HWIO", "NHWC"))
    y = y * params["scale"].astype(nn.COMPUTE_DTYPE) + \
        params["bias"].astype(nn.COMPUTE_DTYPE)
    return jax.nn.relu(y) if relu else y


def init_params(rng: jax.Array, config: ResNetConfig) -> dict:
    n_blocks = sum(config.stage_sizes)
    keys = iter(jax.random.split(rng, 2 + 4 * n_blocks + len(config.stage_sizes)))
    params = {"stem": _conv_init(next(keys), 7, 7, 3, config.width),
              "stages": []}
    c_in = config.width
    for i, size in enumerate(config.stage_sizes):
        c_mid = config.width * (2 ** i)
        c_out = c_mid * 4
        stage = []
        for j in range(size):
            block = {
                "conv1": _conv_init(next(keys), 1, 1, c_in, c_mid),
                "conv2": _conv_init(next(keys), 3, 3, c_mid, c_mid),
                "conv3": _conv_init(next(keys), 1, 1, c_mid, c_out),
            }
            if j == 0:
                block["proj"] = _conv_init(next(keys), 1, 1, c_in, c_out)
            stage.append(block)
            c_in = c_out
        params["stages"].append(stage)
    params["head"] = nn.dense_init(next(keys), c_in, config.num_classes)
    return params


def forward(params: dict, config: ResNetConfig, images: jax.Array
            ) -> jax.Array:
    """(B, H, W, 3) f32 images -> (B, num_classes) f32 logits."""
    x = _conv(params["stem"], images, stride=2)
    x = jax.lax.reduce_window(
        x, -jnp.inf, jax.lax.max, (1, 3, 3, 1), (1, 2, 2, 1),
        [(0, 0), (1, 1), (1, 1), (0, 0)])
    for i, stage in enumerate(params["stages"]):
        for j, block in enumerate(stage):
            # ResNet-v1.5: the 3x3 conv carries the stride (not the 1x1).
            stride = 2 if (j == 0 and i > 0) else 1
            h = _conv(block["conv1"], x)
            h = _conv(block["conv2"], h, stride=stride)
            h = _conv(block["conv3"], h, relu=False)
            shortcut = x
            if "proj" in block:
                shortcut = _conv(block["proj"], x, stride=stride, relu=False)
            x = jax.nn.relu(h + shortcut)
    x = jnp.mean(x.astype(jnp.float32), axis=(1, 2))
    return nn.dense(params["head"], x).astype(jnp.float32)


def build_signatures(params: dict, config: ResNetConfig) -> dict:
    from min_tfs_client_tpu.servables.servable import Signature, TensorSpec

    def predict(params, inputs):
        logits = forward(params, config, jnp.asarray(inputs["images"]))
        return {"logits": logits,
                "probabilities": jax.nn.softmax(logits, axis=-1)}

    sig = Signature(
        fn=predict,
        params=params,
        inputs={"images": TensorSpec(
            np.float32,
            (None, config.image_size, config.image_size, 3))},
        outputs={"logits": TensorSpec(np.float32, (None, config.num_classes)),
                 "probabilities": TensorSpec(
                     np.float32, (None, config.num_classes))},
        batch_buckets=(1, 4, 8, 16, 32),
        # First conv casts to COMPUTE_DTYPE anyway: cast on host, halve
        # the DMA (same rounding either side of the link).
        transfer_casts={"images": nn.COMPUTE_DTYPE},
    )
    return {"serving_default": sig, "predict": sig}
