"""DICL / GA-Net building blocks (Flax, NHWC).

Behavioral equivalents of the reference blocks (src/models/common/blocks/
dicl.py): conv blocks, GA-Net 2x up/down fusion blocks, the per-displacement
MatchingNet, and the displacement-aware projection (DAP).

TPU-native layout decisions:
- Matching volumes are ``(B, du, dv, H, W, C)``; MatchingNet folds the
  displacement axes into the batch so XLA sees one big conv over
  ``B*du*dv`` maps (the reference does the same reshape trick with NCHW,
  dicl.py:93-118).
- Cost volumes are ``(B, H, W, du, dv)``; DAP flattens (du, dv) into the
  trailing channel axis, making it a plain 1x1 conv — the ideal layout for
  the TPU MXU (channels-last matmul over du*dv).
"""

from typing import Any

import flax.linen as nn
import jax
import jax.numpy as jnp

from ..norm import Norm2d
from ..util import ConvParams, identity_1x1_init


class ConvBlock(nn.Module):
    """conv → norm → relu (no conv bias, like the reference).

    Input may also be a pair ``(shared, per_item)`` with shared (B, H, W,
    C1) and per_item (B·N, H, W, C2): the conv then splits along its input
    channels — conv(concat) = conv(shared) repeated over N + conv(per_item)
    by linearity — computing the shared half once instead of N times.
    Parameters are identical to the concatenated form (kernel channels
    ordered shared-first).

    How the shared half joins the other is the TPU compiler's business as
    much as ours: it runs these convs with the item batch B·N as the minor
    (lane) dimension (32 or 96 channels do not fill 128 lanes, 486 windows
    nearly fill 512). Written as ``reshape`` + broadcast-add, the shared
    half is repeated along a *factor* of the lane dimension, which no
    fusion produces: the compiler writes it out at the activation's full
    size, transposes that to item-minor, and transposes the activation's
    gradient back to sum it over N (three copies of the activation a
    call, more device time than the halved conv). So the repeat is a
    contraction over B with a one-hot selection matrix: the compiler
    lowers it to a convolution that writes item-minor directly and takes
    the per-item half as its fused addend, and its transpose, the sum
    over N, reads the item-minor gradient as it lies. A row of the
    selection holds one 1 and the MXU accumulates in float32, so the
    selected value is the shared half to the bit
    (tests/test_matching_compile.py holds the compiler to this).
    """

    c_out: int
    kernel_size: int = 3
    stride: int = 1
    dilation: int = 1
    norm_type: str = "batch"
    num_groups: int = 8
    dtype: Any = None
    bn_splits: int = 1

    @nn.compact
    def __call__(self, x, train=False, frozen_bn=False):
        if isinstance(x, tuple):
            shared, per_item = x
            c1 = shared.shape[-1]
            kernel = ConvParams(
                self.c_out, (self.kernel_size, self.kernel_size),
                use_bias=False, name="Conv_0")(c1 + per_item.shape[-1])

            dt = self.dtype or kernel.dtype
            pad = self.dilation * (self.kernel_size // 2)

            def conv(inp, kk):
                return jax.lax.conv_general_dilated(
                    inp.astype(dt), kk.astype(dt),
                    (self.stride, self.stride), [(pad, pad), (pad, pad)],
                    rhs_dilation=(self.dilation, self.dilation),
                    dimension_numbers=("NHWC", "HWIO", "NHWC"))

            ys = conv(shared, kernel[:, :, :c1])       # (B, h', w', c_out)
            yp = conv(per_item, kernel[:, :, c1:])     # (B·N, h', w', c_out)
            b = ys.shape[0]
            sel = jnp.repeat(jnp.eye(b, dtype=ys.dtype), yp.shape[0] // b,
                             axis=0)                   # (B·N, B), one 1 a row
            # float32 operands would cross the MXU as one bfloat16 pass
            exact = (jax.lax.Precision.HIGHEST if ys.dtype == jnp.float32
                     else None)
            x = yp + jnp.einsum("nb,bhwc->nhwc", sel, ys, precision=exact,
                                preferred_element_type=ys.dtype)
        else:
            # explicit torch-convention padding (flax 'SAME' shifts strided
            # convs by one pixel on even inputs)
            x = nn.Conv(
                self.c_out,
                (self.kernel_size, self.kernel_size),
                strides=self.stride,
                kernel_dilation=self.dilation,
                padding=self.dilation * (self.kernel_size // 2),
                use_bias=False,
                dtype=self.dtype,
            )(x)
        x = Norm2d(self.norm_type, self.num_groups, dtype=self.dtype,
                   splits=self.bn_splits)(x, train and not frozen_bn)
        return nn.relu(x)


class ConvBlockTransposed(nn.Module):
    """transposed conv (2x up, k=4 s=2 p=1 torch geometry) → norm → relu.

    flax ``padding='SAME'`` reproduces torch's k4/s2/p1 exactly (out = 2·in,
    same border alignment — verified bit-exact in f64 against
    ``F.conv_transpose2d``); explicit pair padding in flax means something
    different and loses pixels.
    """

    c_out: int
    norm_type: str = "batch"
    num_groups: int = 8
    dtype: Any = None
    bn_splits: int = 1

    @nn.compact
    def __call__(self, x, train=False, frozen_bn=False):
        x = nn.ConvTranspose(
            self.c_out, (4, 4), strides=(2, 2), padding="SAME", use_bias=False,
            dtype=self.dtype,
        )(x)
        x = Norm2d(self.norm_type, self.num_groups, dtype=self.dtype,
                   splits=self.bn_splits)(x, train and not frozen_bn)
        return nn.relu(x)


class GaConv2xBlock(nn.Module):
    """Strided 3x3 downsample fused with a same-resolution skip input."""

    c_out: int
    norm_type: str = "batch"
    bn_splits: int = 1

    @nn.compact
    def __call__(self, x, res, train=False, frozen_bn=False):
        x = nn.Conv(self.c_out, (3, 3), strides=2, padding=1,
                    use_bias=False)(x)
        x = nn.relu(x)

        assert x.shape == res.shape
        x = jnp.concatenate((x, res), axis=-1)

        x = nn.Conv(self.c_out, (3, 3), use_bias=False)(x)
        x = Norm2d(self.norm_type, 8, splits=self.bn_splits)(
            x, train and not frozen_bn)
        return nn.relu(x)


class GaConv2xBlockTransposed(nn.Module):
    """2x transposed-conv upsample fused with a same-resolution skip input."""

    c_out: int
    norm_type: str = "batch"
    bn_splits: int = 1

    @nn.compact
    def __call__(self, x, res, train=False, frozen_bn=False):
        # 'SAME' = torch k4/s2/p1 geometry (see ConvBlockTransposed)
        x = nn.ConvTranspose(
            self.c_out, (4, 4), strides=(2, 2), padding="SAME", use_bias=False,
        )(x)
        x = nn.relu(x)

        assert x.shape == res.shape
        x = jnp.concatenate((x, res), axis=-1)

        x = nn.Conv(self.c_out, (3, 3), use_bias=False)(x)
        x = Norm2d(self.norm_type, 8, splits=self.bn_splits)(
            x, train and not frozen_bn)
        return nn.relu(x)


class MatchingNet(nn.Module):
    """6-layer conv hourglass applied per displacement candidate.

    Input ``(B, du, dv, H, W, C)`` (stacked feature pairs), output cost
    ``(B, H, W, du, dv)``. The displacement axes ride the batch dimension
    through the convs — one large batched conv instead of du*dv small ones.

    Alternatively input may be the pair ``(f1, window)`` with f1
    (B, H, W, C) and window (B, du, dv, H, W, C) *unstacked*: the first
    conv then splits along its input channels — the f1 half is computed
    once and repeated over displacements instead of convolving the same
    f1 values du·dv times (half the first conv's FLOPs; ``ConvBlock``
    says how the repeat is written so that the TPU compiler adds no array
    of the activation's size for it). Parameters are identical to the
    stacked form, which stays as the tests' reference.
    """

    norm_type: str = "batch"
    scale: float = 1
    dtype: Any = None

    @nn.compact
    def __call__(self, mvol, train=False, frozen_bn=False):
        dt = self.dtype
        c1 = int(self.scale * 96)
        c2 = int(self.scale * 128)
        c3 = int(self.scale * 64)
        c4 = int(self.scale * 32)

        if isinstance(mvol, tuple):
            f1, window = mvol
            b, du, dv, h, w, c = window.shape
            x = ConvBlock(c1, norm_type=self.norm_type, dtype=dt)(
                (f1, window.reshape(b * du * dv, h, w, c)), train, frozen_bn)
        else:
            b, du, dv, h, w, c = mvol.shape
            x = mvol.reshape(b * du * dv, h, w, c)
            x = ConvBlock(c1, norm_type=self.norm_type, dtype=dt)(
                x, train, frozen_bn)
        x = ConvBlock(c2, stride=2, norm_type=self.norm_type, dtype=dt)(x, train, frozen_bn)
        x = ConvBlock(c2, norm_type=self.norm_type, dtype=dt)(x, train, frozen_bn)
        x = ConvBlock(c3, norm_type=self.norm_type, dtype=dt)(x, train, frozen_bn)
        x = ConvBlockTransposed(c4, norm_type=self.norm_type, num_groups=4, dtype=dt)(x, train, frozen_bn)
        x = nn.Conv(1, (3, 3), dtype=dt)(x)  # with bias, like the reference

        # the cost volume is the readout surface (softargmax/DAP): f32
        cost = x.reshape(b, du, dv, h, w).astype(jnp.float32)
        return cost.transpose(0, 3, 4, 1, 2)  # (B, H, W, du, dv)


class DisplacementAwareProjection(nn.Module):
    """1x1 conv mixing the du*dv displacement channels of a cost volume.

    Input/output ``(B, H, W, du, dv)``. ``init='identity'`` starts as a
    no-op projection (reference dicl.py:121-150).
    """

    disp_range: tuple
    init: str = "identity"

    @nn.compact
    def __call__(self, x):
        if self.init not in ("identity", "standard"):
            raise ValueError(f"unknown init value '{self.init}'")

        b, h, w, du, dv = x.shape
        assert (du, dv) == (2 * self.disp_range[0] + 1, 2 * self.disp_range[1] + 1)

        kernel_init = (
            identity_1x1_init if self.init == "identity" else nn.initializers.lecun_normal()
        )

        x = x.reshape(b, h, w, du * dv)
        x = nn.Conv(du * dv, (1, 1), use_bias=False, kernel_init=kernel_init)(x)
        return x.reshape(b, h, w, du, dv)
