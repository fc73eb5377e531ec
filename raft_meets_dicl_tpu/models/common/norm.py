"""2D normalization with a string-typed factory, Flax edition.

Mirrors the reference factory (src/models/common/norm.py:4-16) with torch
hyperparameters (eps 1e-5, BN momentum 0.1 → flax momentum 0.9; instance
norm non-affine). Batchnorm freezing is not implemented by module surgery
like the reference (norm.py:18-32) — it's an apply-time switch: the model
wrapper passes ``train=False``-equivalent ``use_running_average`` into
``Norm2d.__call__`` (see models/model.py ``Model.apply``).
"""

from functools import partial
from typing import Any

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax import lax
from jax.ad_checkpoint import checkpoint_name

NORM_TYPES = ("group", "batch", "instance", "none")

# name of the instance norm's per-sample, per-channel statistics (mean and
# 1/sigma, f32[N,1,1,C]) for rematerialisation policies that keep by name
INSTANCE_STATS = "instance_norm_stats"


def _float32(x):
    return x.astype(jnp.promote_types(x.dtype, jnp.float32))


def _instance_stats(x32, epsilon):
    """Mean and ``1/sqrt(var + epsilon)`` over the spatial axes of an NHWC
    map as ``f32[N,1,1,C]``: float32 sums, flax's fast variance
    (``E[x²] - E[x]²`` clamped at 0)."""
    count = x32.shape[1] * x32.shape[2]
    mean = jnp.sum(x32, (1, 2), keepdims=True) / count
    mean2 = jnp.sum(x32 * x32, (1, 2), keepdims=True) / count
    var = jnp.maximum(0.0, mean2 - mean * mean)
    return mean, lax.rsqrt(var + epsilon)


@partial(jax.custom_vjp, nondiff_argnums=(1, 2))
def instance_norm(x, epsilon=1e-5, dtype=None):
    """Non-affine instance norm of an NHWC map, in the array the
    convolution wrote: statistics and arithmetic in float32 inside the
    fusions, result in ``dtype`` (default: the input's).

    The mathematics is flax ``GroupNorm(group_size=1, use_scale=False,
    use_bias=False)``'s; what differs is what the backward pass is handed:
    the input as it came, the mean and ``1/sigma``, and nothing of the
    input's size in float32 (autodiff kept flax's ``[N,H,W,C,1]`` float32
    squares and ``x - mean``). On the chip the compiler had already fused
    those away: the two forms run at the same speed there (PERF.md
    section 6, PR 38)."""
    return _instance_norm_fwd(x, epsilon, dtype)[0]


def _instance_norm_fwd(x, epsilon, dtype):
    x32 = _float32(x)
    mean, rstd = _instance_stats(x32, epsilon)
    mean = checkpoint_name(mean, INSTANCE_STATS)
    rstd = checkpoint_name(rstd, INSTANCE_STATS)
    y = (x32 - mean) * rstd
    return y.astype(dtype or x.dtype), (x, mean, rstd)


def _instance_norm_bwd(epsilon, dtype, res, g):
    x, mean, rstd = res
    g32 = _float32(g)
    xhat = (_float32(x) - mean) * rstd
    m1 = jnp.mean(g32, (1, 2), keepdims=True)
    m2 = jnp.mean(g32 * xhat, (1, 2), keepdims=True)
    dx = (g32 - m1 - xhat * m2) * rstd
    return (dx.astype(x.dtype),)


instance_norm.defvjp(_instance_norm_fwd, _instance_norm_bwd)


class Norm2d(nn.Module):
    """Dispatches to group/batch/instance/no normalization over NHWC maps.

    ``train`` only affects batch norm (running-stats update vs. use).
    ``dtype`` is the return/compute dtype; the statistics are computed in
    float32 regardless (inside the flax layers, and in ``instance_norm``).
    """

    ty: str
    num_groups: int = 8
    dtype: Any = None
    # batch norm only: compute live statistics over `splits` equal
    # leading-axis chunks instead of the whole batch. Encoders that fold
    # an (img1, img2) pair into one 2N batch for conv efficiency set
    # splits=2 when the REFERENCE runs the two images through separate
    # calls (per-image stats, sequential running-stat updates) — only
    # the norm couples the pair, so only the norm needs to split
    # (reference src/models/impls/dicl.py:277-278).
    splits: int = 1

    @nn.compact
    def __call__(self, x, train=False):
        if self.ty == "group":
            return nn.GroupNorm(
                num_groups=self.num_groups, epsilon=1e-5, dtype=self.dtype
            )(x)
        if self.ty == "batch":
            bn = nn.BatchNorm(
                use_running_average=not train, momentum=0.9, epsilon=1e-5,
                dtype=self.dtype,
            )
            if train and self.splits > 1:
                # one shared BatchNorm instance applied per chunk: same
                # parameter tree, per-chunk statistics, and the second
                # call's running-stat update reads the first's result —
                # exactly the reference's sequential per-image calls
                n = x.shape[0] // self.splits
                return jnp.concatenate(
                    [bn(x[i * n:(i + 1) * n]) for i in range(self.splits)],
                    axis=0)
            return bn(x)
        if self.ty == "instance":
            # per-sample, per-channel over spatial dims; non-affine like torch
            return instance_norm(x, 1e-5, self.dtype)
        if self.ty == "none":
            return x
        raise ValueError(f"unknown norm type '{self.ty}'")


def make_norm2d(ty, num_channels=None, num_groups=8):
    """Factory matching the reference signature; ``num_channels`` is implied
    by the input in flax and kept only for call-site compatibility."""
    if ty not in NORM_TYPES:
        raise ValueError(f"unknown norm type '{ty}'")
    return Norm2d(ty=ty, num_groups=num_groups)
