"""RAFT feature/context encoders (Flax, NHWC).

Single-scale s3 (1/8 resolution) after the reference
(src/models/common/encoders/raft/s3.py): 7x7 stride-2 input conv, three
residual stages (64/96/128), 1x1 output conv, optional 2D dropout.

The reference's shared-batch trick for image pairs (s3.py:53-57) is kept:
pass a tuple ``(img1, img2)`` and both are encoded in one batched pass.

Pyramid variants (p34/p35/p36) extend the residual stack with 160/192
channel stages and per-level output heads (reference raft/p36.py,
raft/common.py) returning features at 1/8..1/64.
"""

from typing import Any, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from ..blocks.raft import ResidualBlock, kaiming_normal
from ..norm import Norm2d


# The TPU compiler lays a convolution's batch on the eight sublanes of a
# tile, and rewrites every convolution whose batch is under 8 into its
# space-to-batch form (W cut in eight and folded into the batch). Through a
# stack of 3x3 convolutions that form pays for a halo exchange a layer, and
# it cannot carry an instance norm's per-sample statistics at all (mean and
# 1/sigma are broadcast to full size in float32 and relaid). Forward and
# backward on one v5e under the bf16 policy (PERF.md section 6, PR 38): a
# frozen-batch-norm encoder at 6x400x720 43.8 ms as a batch of 6 and 18.9 ms
# with two images of zeros behind it (19.8 with the conversion switched off:
# six images take a tile of eight either way); the pyramid at 6x384x704 55.4
# and 20.1.
_BATCH_TILE = 8


def _batch_shards(n):
    """Over how many chips a batch of ``n`` is split in the step being
    traced (``parallel.mesh.traced_under``: the step builders split the
    leading dimension over every mesh axis), and that mesh; ``(1, None)``
    for a single-device step."""
    from ....parallel.mesh import traced_mesh

    mesh = traced_mesh()
    if mesh is None or mesh.devices.size == 1 or n % mesh.devices.size:
        return 1, None
    return mesh.devices.size, mesh


def _fill_batch_tile(x, norm_type, train, frozen_bn):
    """``x`` with images of zeros behind it up to a full tile of the batch,
    where that is free: on the TPU, for a batch of 4 to 7 (at least half a
    tile: the zeros at most double what the encoder keeps for its backward
    pass; a 1088x1920 pair filled to 8 is twice as fast and 6.4 GiB larger),
    and unless a live batch norm would count the zeros into its statistics
    (every other norm here is per sample or frozen, and a convolution does
    not mix samples: the first ``n`` results are what they were).

    The batch that counts is a chip's: under a mesh the trace sees the
    global batch and the compiler converts the slice each chip is handed,
    so every chip's slice is filled where it lies (a ``shard_map``: nothing
    moves between chips). ``_drop_fill`` takes the zeros' results off."""
    shards, mesh = _batch_shards(x.shape[0])
    n = x.shape[0] // shards
    live_batch_stats = norm_type == "batch" and train and not frozen_bn
    if (jax.default_backend() != "tpu" or live_batch_stats
            or not _BATCH_TILE // 2 <= n < _BATCH_TILE):
        return x

    def fill(x):
        return jnp.pad(x, ((0, _BATCH_TILE - n),) + ((0, 0),) * (x.ndim - 1))

    return fill(x) if mesh is None else _on_batch_shards(fill, mesh)(x)


def _drop_fill(x, batch):
    """The results of the ``batch`` images that ``_fill_batch_tile`` was
    given, in their order."""
    shards, mesh = _batch_shards(batch)
    if mesh is None or x.shape[0] == batch:
        return x[:batch]
    n = batch // shards
    return _on_batch_shards(lambda x: x[:n], mesh)(x)


def _on_batch_shards(fn, mesh):
    lead = jax.sharding.PartitionSpec(tuple(mesh.axis_names))
    return jax.shard_map(fn, mesh=mesh, in_specs=lead, out_specs=lead)


class _Stem(nn.Module):
    """Input conv + the first three residual stages (to 1/8, 128ch)."""

    norm_type: str = "instance"
    dtype: Any = None

    @nn.compact
    def __call__(self, x, train=False, frozen_bn=False):
        dt = self.dtype
        x = nn.Conv(64, (7, 7), strides=2, padding=3, kernel_init=kaiming_normal,
                    dtype=dt)(x)
        x = Norm2d(self.norm_type, 8, dtype=dt)(x, train and not frozen_bn)
        x = nn.relu(x)

        x = ResidualBlock(64, self.norm_type, stride=1, dtype=dt)(x, train, frozen_bn)
        x = ResidualBlock(64, self.norm_type, stride=1, dtype=dt)(x, train, frozen_bn)

        x = ResidualBlock(96, self.norm_type, stride=2, dtype=dt)(x, train, frozen_bn)
        x = ResidualBlock(96, self.norm_type, stride=1, dtype=dt)(x, train, frozen_bn)

        x = ResidualBlock(128, self.norm_type, stride=2, dtype=dt)(x, train, frozen_bn)
        x = ResidualBlock(128, self.norm_type, stride=1, dtype=dt)(x, train, frozen_bn)

        return x


def _drop2d(x, rate, train):
    """Channel dropout (torch Dropout2d): broadcast over spatial dims."""
    return nn.Dropout(rate, broadcast_dims=(1, 2), deterministic=not train)(x)


class FeatureEncoderS3(nn.Module):
    """Single-scale encoder: (B, H, W, 3) → (B, H/8, W/8, output_dim)."""

    output_dim: int = 128
    norm_type: str = "instance"
    dropout: float = 0.0
    dtype: Any = None

    @nn.compact
    def __call__(self, x, train=False, frozen_bn=False):
        paired = isinstance(x, (tuple, list))
        if paired:
            n = x[0].shape[0]
            x = jnp.concatenate(x, axis=0)
        batch = x.shape[0]
        x = _fill_batch_tile(x, self.norm_type, train, frozen_bn)

        x = _Stem(self.norm_type, dtype=self.dtype)(x, train, frozen_bn)
        x = _drop_fill(nn.Conv(self.output_dim, (1, 1),
                               kernel_init=kaiming_normal,
                               dtype=self.dtype)(x), batch)
        if self.dropout > 0:
            x = _drop2d(x, self.dropout, train)

        if paired:
            return x[:n], x[n:]
        return x


class EncoderOutputNet(nn.Module):
    """Per-level output head: 3x3 conv + norm + relu + 1x1 conv
    (reference raft/common.py:6-29)."""

    output_dim: int
    intermediate_dim: int = 128
    norm_type: str = "batch"
    dtype: Any = None

    @nn.compact
    def __call__(self, x, train=False, frozen_bn=False):
        x = nn.Conv(self.intermediate_dim, (3, 3), kernel_init=kaiming_normal,
                    dtype=self.dtype)(x)
        x = Norm2d(self.norm_type, 8, dtype=self.dtype)(x, train and not frozen_bn)
        x = nn.relu(x)
        x = nn.Conv(self.output_dim, (1, 1), kernel_init=kaiming_normal,
                    dtype=self.dtype)(x)
        return x


class FeatureEncoderPyramid(nn.Module):
    """Pyramid encoder returning features at 1/8 .. 1/(8*2^(levels-1)).

    ``levels=2`` ≈ reference p34 (1/8, 1/16), ``3`` ≈ p35, ``4`` ≈ p36.
    Extra residual stages use 160/192/224 channels like the reference
    (raft/p36.py:9-61); each level gets its own output head.
    """

    output_dim: int = 128
    levels: int = 3
    norm_type: str = "instance"
    dropout: float = 0.0
    dtype: Any = None

    @nn.compact
    def __call__(self, x, train=False, frozen_bn=False) -> Tuple:
        dt = self.dtype
        paired = isinstance(x, (tuple, list))
        if paired:
            n = x[0].shape[0]
            x = jnp.concatenate(x, axis=0)
        batch = x.shape[0]
        x = _fill_batch_tile(x, self.norm_type, train, frozen_bn)

        x = _Stem(self.norm_type, dtype=dt)(x, train, frozen_bn)  # 1/8, 128ch

        stage_channels = (160, 192, 224)
        # per-level head widths grow with the pyramid: out3..out6 use
        # 160/192/224/256 intermediates (reference raft/p35.py:47-49,
        # p36.py:52-55)
        outputs = []
        for i in range(self.levels):
            out = _drop_fill(
                EncoderOutputNet(self.output_dim,
                                 intermediate_dim=160 + 32 * i,
                                 norm_type=self.norm_type,
                                 dtype=dt)(x, train, frozen_bn), batch)
            if self.dropout > 0:
                out = _drop2d(out, self.dropout, train)
            outputs.append(out)

            if i + 1 < self.levels:
                ch = stage_channels[min(i, len(stage_channels) - 1)]
                x = ResidualBlock(ch, self.norm_type, stride=2, dtype=dt)(x, train, frozen_bn)
                x = ResidualBlock(ch, self.norm_type, stride=1, dtype=dt)(x, train, frozen_bn)

        if paired:
            return (
                tuple(o[:n] for o in outputs),
                tuple(o[n:] for o in outputs),
            )
        return tuple(outputs)
