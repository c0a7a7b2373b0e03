"""ResNet v1.5 — the ImageNet-scale stretch model (BASELINE.md "Benchmark
configs to reproduce" row 5; SURVEY.md §7 build order item 8).

The reference never ships a model this size — its largest is the 5-block
CIFAR convnet (examples/Model.lua:19-45) — but the BASELINE configs call for
ResNet-50/ImageNet-class data-parallel training, which is where gradient
bucketing (distlearn_tpu.ops.flatten.make_bucket_spec) earns its keep: the
~25.6M-parameter pytree has 161 leaves, and bucketed psum + fused update
stream over HBM a few times instead of 161.

TPU-first choices:

* NHWC activations / HWIO kernels (MXU-friendly, see models/nn.py).
* v1.5 variant: the stride-2 lives on the 3x3 conv of downsampling
  bottlenecks (better accuracy AND better MXU utilization than v1's
  strided 1x1, which wastes 3/4 of its window positions).
* Kaiming-normal conv init, zero-init of each block's last BN gamma
  (torchvision defaults — the config the BASELINE numbers assume).
* ``compute_dtype=jnp.bfloat16`` runs convs on the MXU in bf16 with f32
  master weights.
"""

from __future__ import annotations

import jax.numpy as jnp
from jax import random

from distlearn_tpu.models import nn
from distlearn_tpu.models.core import Model

# depth -> (block counts per stage); bottleneck expansion is 4.
_DEPTHS = {50: (3, 4, 6, 3), 101: (3, 4, 23, 3), 152: (3, 8, 36, 3)}
_WIDTHS = (64, 128, 256, 512)
_EXPANSION = 4


def _bottleneck_init(key, in_ch: int, width: int, dtype, downsample: bool,
                     norm: str = "batch"):
    k = random.split(key, 4)
    out_ch = width * _EXPANSION
    p, s = {}, {}
    use_bn = norm == "batch"
    p["conv1"] = nn.conv2d_init(k[0], in_ch, width, 1, 1, dtype,
                                bias=not use_bn, init="he")
    p["conv2"] = nn.conv2d_init(k[1], width, width, 3, 3, dtype,
                                bias=not use_bn, init="he")
    p["conv3"] = nn.conv2d_init(k[2], width, out_ch, 1, 1, dtype,
                                bias=not use_bn, init="he")
    if use_bn:
        p["bn1"], s["bn1"] = nn.batchnorm_init(width, dtype)
        p["bn2"], s["bn2"] = nn.batchnorm_init(width, dtype)
        p["bn3"], s["bn3"] = nn.batchnorm_init(out_ch, dtype)
        # zero-init the residual branch's last gamma: each block starts as
        # identity, the torchvision zero_init_residual recipe
        p["bn3"]["scale"] = jnp.zeros_like(p["bn3"]["scale"])
    else:
        # SkipInit (De & Smith 2020): the branch is scaled by a learnable
        # scalar initialized to ZERO, so every block starts as identity —
        # the same start-as-identity property zero-gamma BN provides,
        # without any channel-statistics reductions
        p["alpha"] = jnp.zeros((), dtype)
    if downsample or in_ch != out_ch:
        p["conv_proj"] = nn.conv2d_init(k[3], in_ch, out_ch, 1, 1, dtype,
                                        bias=not use_bn, init="he")
        if use_bn:
            p["bn_proj"], s["bn_proj"] = nn.batchnorm_init(out_ch, dtype)
    return p, s


def _bottleneck_apply(p, s, x, stride, train, axis_name, bn_weight,
                      compute_dtype):
    ns = {}

    def bn(name, h):
        y, ns[name] = nn.batchnorm(p[name], s[name], h, train=train,
                                   eps=1e-5, momentum=0.1,
                                   axis_name=axis_name, weight=bn_weight)
        return y

    norm_free = "alpha" in p
    h = nn.conv2d(p["conv1"], x, compute_dtype=compute_dtype)
    h = jnp.maximum(h if norm_free else bn("bn1", h), 0)
    # v1.5: the 3x3 carries the stride
    h = nn.conv2d(p["conv2"], h, stride=(stride, stride),
                  padding=((1, 1), (1, 1)), compute_dtype=compute_dtype)
    h = jnp.maximum(h if norm_free else bn("bn2", h), 0)
    h = nn.conv2d(p["conv3"], h, compute_dtype=compute_dtype)
    if not norm_free:
        h = bn("bn3", h)
    if "conv_proj" in p:
        sc = nn.conv2d(p["conv_proj"], x, stride=(stride, stride),
                       compute_dtype=compute_dtype)
        if not norm_free:
            sc = bn("bn_proj", sc)
    else:
        sc = x.astype(h.dtype)
    if norm_free:
        h = h * p["alpha"].astype(h.dtype)
    return jnp.maximum(h + sc, 0), ns


def resnet(depth: int = 50, num_classes: int = 1000, dtype=jnp.float32,
           compute_dtype=None, image_size: int = 224,
           norm: str = "batch") -> Model:
    """Factory: ``resnet(50)`` is the flagship ResNet-50 v1.5.

    ``norm="none"`` builds the norm-free SkipInit variant (De & Smith
    2020: zero-init scalar branch gains replace BN's start-as-identity
    role; convs carry biases): no batch statistics exist at all, so BN's
    channel reductions are simply absent, and there is no cross-replica
    stats sync.  The accuracy trade is the literature's, not re-verified
    here, and no cell measures either variant on the chip."""
    if depth not in _DEPTHS:
        raise ValueError(f"depth must be one of {sorted(_DEPTHS)}")
    if norm not in ("batch", "none"):
        raise ValueError(f"norm must be 'batch' or 'none', got {norm!r}")
    blocks = _DEPTHS[depth]

    use_bn = norm == "batch"

    def init(key):
        keys = random.split(key, 2 + sum(blocks))
        params, state = {}, {}
        params["conv_stem"] = nn.conv2d_init(keys[0], 3, 64, 7, 7, dtype,
                                             bias=not use_bn, init="he")
        if use_bn:
            params["bn_stem"], state["bn_stem"] = nn.batchnorm_init(64,
                                                                    dtype)
        in_ch, ki = 64, 1
        for si, (width, n_blocks) in enumerate(zip(_WIDTHS, blocks)):
            for bi in range(n_blocks):
                downsample = (bi == 0)
                name = f"stage{si + 1}_block{bi + 1}"
                params[name], state[name] = _bottleneck_init(
                    keys[ki], in_ch, width, dtype, downsample, norm=norm)
                in_ch = width * _EXPANSION
                ki += 1
        params["fc"] = nn.dense_init(keys[ki], in_ch, num_classes, dtype)
        return params, state

    def apply(params, state, x, train=True, rng=None, axis_name=None,
              bn_weight=None):
        new_state = {}
        h = nn.conv2d(params["conv_stem"], x, stride=(2, 2),
                      padding=((3, 3), (3, 3)), compute_dtype=compute_dtype)
        if use_bn:
            h, new_state["bn_stem"] = nn.batchnorm(
                params["bn_stem"], state["bn_stem"], h, train=train,
                eps=1e-5, momentum=0.1, axis_name=axis_name,
                weight=bn_weight)
        h = jnp.maximum(h, 0)
        h = nn.max_pool2d(h, window=(3, 3), stride=(2, 2),
                          padding=((1, 1), (1, 1)))
        for si, (width, n_blocks) in enumerate(zip(_WIDTHS, blocks)):
            for bi in range(n_blocks):
                stride = 2 if (bi == 0 and si > 0) else 1
                name = f"stage{si + 1}_block{bi + 1}"
                h, new_state[name] = _bottleneck_apply(
                    params[name], state[name], h, stride, train, axis_name,
                    bn_weight, compute_dtype)
        h = jnp.mean(h, axis=(1, 2))          # global average pool
        logits = nn.dense(params["fc"], h, compute_dtype=compute_dtype)
        return nn.log_softmax(logits.astype(dtype)), new_state

    return Model(init=init, apply=apply, name=f"resnet{depth}",
                 input_shape=(image_size, image_size, 3),
                 num_classes=num_classes)


def resnet50(num_classes: int = 1000, dtype=jnp.float32, compute_dtype=None,
             image_size: int = 224, norm: str = "batch") -> Model:
    return resnet(50, num_classes, dtype, compute_dtype, image_size,
                  norm=norm)
