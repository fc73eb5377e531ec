"""Plain reference of ``dicl/baseline``: DICL, coarse-to-fine matching with
a learned cost and no recurrence (Wang, Zhong, Dai, Zhang, Ji, Li,
*Displacement-Invariant Matching Cost Learning for Accurate Optical Flow
Estimation*, NeurIPS 2020, arXiv:2010.14851; upstream jytime/DICL-Flow; in
qzed/raft-meets-dicl ``src/models/impls/dicl.py`` with
``cfg/model/dicl-baseline.yaml``).

Forward pass in float32 at highest matmul precision. A GA-Net hourglass
(a stem to 1/2, a ladder of strided convolutions down to 1/128, a ladder
of transposed convolutions back up that refreshes the skips, a second
ladder down fused with them, and a last ladder up) gives both frames 32
feature channels at 1/4, 1/8, 1/16, 1/32 and 1/64; every convolution is
followed by a batch norm and a relu. From 1/64 (level 6) to 1/4 (level 2):

- the coarser level's flow is doubled in resolution and value
  (align-corners bilinear) and frame two's features are *warped* by it:
  read bilinearly at ``position + flow``, zero where a tap lies outside;
- frame two's warped features are shifted by each of the (2r+1)^2 integer
  displacements (zeros shifted in), each shifted map stacked on frame
  one's features; a hypothesis whose shifted features are all zero at a
  position (outside the map, or outside the warp) is zeroed in both halves;
- every stacked pair runs through the MatchingNet (five conv-batchnorm-relu
  blocks, the second of stride 2, the fifth a transposed convolution back
  up, a last 3x3 convolution to one channel): one cost a hypothesis and
  position; the displacement-aware projection mixes a position's
  (2r+1)^2 costs with a 1x1 convolution;
- the flow is the soft-argmin of the costs (the displacements weighted by
  the softmax of their costs) plus the upsampled coarse flow;
- a dilated context network reads that flow, the normalised entropy of the
  softmax, frame one's features and frame one resized to the level, and
  adds its two output channels, times the level's ``context_scale``.

The final flow is the 1/4 flow resized to the frame (align-corners
bilinear, values scaled by 4): no recurrence, no update block, no learned
upsampling.

Departures from the paper and the source, all shared with the program:
batch norm runs on its running statistics (serving evaluates; the seeded
weights hold means and positive variances); the warp and every resize are
dense contractions with hat weights instead of ``grid_sample`` /
``interpolate`` (the same arithmetic; ``tests/test_reference_dicl.py``
holds the warp against a four-tap gather); the warp's validity mask is
"the in-range hat weights sum to one" (the source samples a map of ones,
which is that sum). The MatchingNet sees a level's (2r+1)^2 hypotheses as
one batch in one plain call: ``harness/serve_check.py`` follows one request
at a time, so the largest stack (49 x 128 x 256 x 64 at 512x1024) is 0.41
GB and the whole pass fits the chip beside nothing else, level by level.
"""

import math

import jax
import jax.numpy as jnp
from jax import lax

from . import common as C

ENCODER = "FeatureEncoderGa_0"
LEVELS = (6, 5, 4, 3, 2)            # coarsest first: 1/64 ... 1/4
_CHANNELS = (32, 48, 64, 96, 128, 160, 192)   # stem (1/2), then a stage a halving
_DEPTH = 6
EPS_MASK = 1e-5
EPS_ENTROPY = 1e-9

# (channels, dilation) of the context network's blocks, by level
_CONTEXT = {
    2: ((64, 1), (128, 2), (128, 4), (96, 8), (64, 16), (32, 1)),
    3: ((64, 1), (128, 2), (128, 4), (96, 8), (64, 16), (32, 1)),
    4: ((64, 1), (128, 2), (128, 4), (64, 8), (32, 1)),
    5: ((64, 1), (128, 2), (64, 4), (32, 1)),
    6: ((64, 1), (64, 2), (32, 1)),
}


def settings(model_cfg):
    p = model_cfg["model"].get("parameters", {})
    a = model_cfg["model"].get("arguments", {})
    if p.get("dap-init", "identity") != "identity":
        raise ValueError("the dicl reference implements dap-init=identity")
    scale = a.get("context_scale") or {}
    return {
        "features": int(p.get("feature-channels", 32)),
        "range": {lvl: tuple(int(r) for r in
                             p["displacement-range"][f"level-{lvl}"])
                  for lvl in LEVELS},
        "raw": bool(a.get("raw", False)),
        "dap": bool(a.get("dap", True)),
        "ctx": bool(a.get("ctx", True)),
        "scale": {lvl: float(scale.get(f"level-{lvl}", 1.0))
                  for lvl in LEVELS},
    }


# -- layers -------------------------------------------------------------------


def conv3(P, path, x, features, stride=1, dilation=1, bias=False):
    """3x3 convolution, padding ``dilation`` (torch's convention, also
    under a stride)."""
    kernel = P.get(f"params/{path}/kernel", (3, 3, x.shape[-1], features),
                   "kernel")
    y = lax.conv_general_dilated(
        P.q(x), P.q(kernel), (stride, stride),
        ((dilation, dilation), (dilation, dilation)),
        rhs_dilation=(dilation, dilation),
        dimension_numbers=("NHWC", "HWIO", "NHWC"), precision=C.HIGHEST)
    if bias:
        y = y + P.get(f"params/{path}/bias", (features,), "bias")
    return y


def conv_transposed(P, path, x, features):
    """Transposed convolution, kernel 4, stride 2, padding 1 (twice the
    resolution): the convolution of the input dilated by 2 and padded by
    2, kernel unflipped, the form the program's layer stores it in."""
    kernel = P.get(f"params/{path}/kernel", (4, 4, x.shape[-1], features),
                   "kernel_t")
    return lax.conv_general_dilated(
        P.q(x), P.q(kernel), (1, 1), ((2, 2), (2, 2)), lhs_dilation=(2, 2),
        dimension_numbers=("NHWC", "HWIO", "NHWC"), precision=C.HIGHEST)


def bn_relu(P, path, x):
    return jax.nn.relu(C.batch_norm_frozen(P, f"{path}/Norm2d_0", x))


def conv_block(P, path, x, features, stride=1, dilation=1):
    return bn_relu(P, path, conv3(P, f"{path}/Conv_0", x, features, stride,
                                  dilation))


def conv_block_transposed(P, path, x, features):
    return bn_relu(P, path, conv_transposed(P, f"{path}/ConvTranspose_0", x,
                                            features))


def down_fused(P, path, x, skip, features):
    """GA-Net's 2x block down: strided convolution, relu, the skip of that
    resolution stacked on it, convolution, batch norm, relu."""
    x = jax.nn.relu(conv3(P, f"{path}/Conv_0", x, features, stride=2))
    x = jnp.concatenate((x, skip), axis=-1)
    return bn_relu(P, path, conv3(P, f"{path}/Conv_1", x, features))


def up_fused(P, path, x, skip, features):
    """The same up: transposed convolution in the strided one's place."""
    x = jax.nn.relu(conv_transposed(P, f"{path}/ConvTranspose_0", x,
                                    features))
    x = jnp.concatenate((x, skip), axis=-1)
    return bn_relu(P, path, conv3(P, f"{path}/Conv_0", x, features))


def encoder(P, x, out):
    """``{level: features}`` for the levels 2..6 (1/4 ... 1/64). Index i
    of ``skip`` is the resolution 1/2^(i+1); blocks are numbered in the
    order the program creates them."""
    path = ENCODER
    x = conv_block(P, f"{path}/ConvBlock_0", x, _CHANNELS[0])
    x = conv_block(P, f"{path}/ConvBlock_1", x, _CHANNELS[0], stride=2)
    x = conv_block(P, f"{path}/ConvBlock_2", x, _CHANNELS[0])
    skip = {0: x}
    for i in range(1, _DEPTH + 1):
        x = conv_block(P, f"{path}/ConvBlock_{2 + i}", x, _CHANNELS[i],
                       stride=2)
        skip[i] = x
    for n, i in enumerate(range(_DEPTH, 0, -1)):
        x = up_fused(P, f"{path}/GaConv2xBlockTransposed_{n}", x,
                     skip[i - 1], _CHANNELS[i - 1])
        skip[i - 1] = x
    for i in range(1, _DEPTH + 1):
        x = down_fused(P, f"{path}/GaConv2xBlock_{i - 1}", x, skip[i],
                       _CHANNELS[i])
        skip[i] = x
    heads = {}
    for n, i in enumerate(range(_DEPTH, 1, -1)):
        x = up_fused(P, f"{path}/GaConv2xBlockTransposed_{_DEPTH + n}", x,
                     skip[i - 1], _CHANNELS[i - 1])
        heads[i] = conv_block(P, f"{path}/ConvBlock_{3 + _DEPTH + n}", x, out)
    return heads


# -- resizing and warping -----------------------------------------------------


def resize_bilinear(x, size):
    """Align-corners bilinear resize of (B, H, W, C) to ``size``."""
    hi, wi = x.shape[1:3]
    ho, wo = size
    if (hi, wi) == (ho, wo):
        return x
    wy = C.hat(jnp.linspace(0.0, hi - 1.0, ho), hi)      # (Ho, H)
    wx = C.hat(jnp.linspace(0.0, wi - 1.0, wo), wi)      # (Wo, W)
    x = jnp.einsum("oh,bhwc->bowc", wy, x, precision=C.HIGHEST)
    return jnp.einsum("pw,bowc->bopc", wx, x, precision=C.HIGHEST)


def resize_flow(flow, size):
    """A flow field at another resolution: values scale with the grid."""
    h, w = flow.shape[1:3]
    scale = jnp.asarray([size[1] / w, size[0] / h], jnp.float32)
    return resize_bilinear(flow, size) * scale


def warp(P, f2, flow):
    """``f2`` read bilinearly at ``position + flow``, zero wherever one of
    the four taps lies outside: the contraction with hat weights along y,
    then along x, and the mask from the weights' own sums."""
    b, h, w, _ = f2.shape
    pos = C.grid(b, h, w) + flow
    wx = C.hat(pos[..., 0], w)                           # (B, H, W, W2)
    wy = C.hat(pos[..., 1], h)                           # (B, H, W, H2)
    rows = jnp.einsum("bijh,bhwc->bijwc", wy, P.q(f2), precision=C.HIGHEST)
    out = jnp.einsum("bijwc,bijw->bijc", rows, wx, precision=C.HIGHEST)
    inside = wx.sum(axis=-1) * wy.sum(axis=-1) > 1.0 - EPS_MASK
    return out * inside[..., None]


# -- one level ----------------------------------------------------------------


def shifted_pairs(f1, f2, reach):
    """(B, du, dv, H, W, 2C): frame one's features on frame two's shifted
    by every displacement (dx major), zeros shifted in; a hypothesis whose
    shifted features sum to exactly zero at a position is zero in both
    halves there (the source's ``compute_cost``)."""
    b, h, w, c = f1.shape
    ru, rv = reach
    padded = jnp.pad(f2, ((0, 0), (rv, rv), (ru, ru), (0, 0)))
    pairs = []
    for i in range(2 * ru + 1):              # dx = i - ru
        for j in range(2 * rv + 1):          # dy = j - rv
            moved = padded[:, j:j + h, i:i + w]
            live = moved.sum(axis=-1, keepdims=True) != 0
            pairs.append(jnp.concatenate((f1 * live, moved * live), axis=-1))
    return jnp.stack(pairs, axis=1).reshape(b, 2 * ru + 1, 2 * rv + 1, h, w,
                                            2 * c)


def matching_net(P, path, pairs):
    """One cost per hypothesis and position: (B, H, W, du * dv)."""
    b, du, dv, h, w, c = pairs.shape
    x = pairs.reshape(b * du * dv, h, w, c)
    x = conv_block(P, f"{path}/ConvBlock_0", x, 96)
    x = conv_block(P, f"{path}/ConvBlock_1", x, 128, stride=2)
    x = conv_block(P, f"{path}/ConvBlock_2", x, 128)
    x = conv_block(P, f"{path}/ConvBlock_3", x, 64)
    x = conv_block_transposed(P, f"{path}/ConvBlockTransposed_0", x, 32)
    x = conv3(P, f"{path}/Conv_0", x, 1, bias=True)
    return x.reshape(b, du * dv, h, w).transpose(0, 2, 3, 1)


def displacements(reach):
    """(du * dv, 2): the displacement (dx, dy) of each cost channel."""
    dx, dy = jnp.meshgrid(
        jnp.arange(-reach[0], reach[0] + 1, dtype=jnp.float32),
        jnp.arange(-reach[1], reach[1] + 1, dtype=jnp.float32), indexing="ij")
    return jnp.stack((dx, dy), axis=-1).reshape(-1, 2)


def soft_argmin(cost, reach):
    return jnp.einsum("bhwd,dc->bhwc", jax.nn.softmax(cost, axis=-1),
                      displacements(reach), precision=C.HIGHEST)


def entropy(cost):
    """Entropy of the softmax over the hypotheses, over its largest value
    (log of their number): (B, H, W, 1)."""
    p = jax.nn.softmax(cost, axis=-1)
    plogp = -p * jnp.log(jnp.clip(p, EPS_ENTROPY, 1.0 - EPS_ENTROPY))
    return plogp.sum(axis=-1, keepdims=True) / math.log(cost.shape[-1])


def context_net(P, path, x, level):
    for i, (features, dilation) in enumerate(_CONTEXT[level]):
        x = conv_block(P, f"{path}/ConvBlock_{i}", x, features,
                       dilation=dilation)
    return conv3(P, f"{path}/Conv_0", x, 2, bias=True)


def flow_level(P, path, s, level, img1, f1, f2, coarse):
    """``(flow, raw flow)`` of one level; ``coarse`` is the flow of the
    level before, or None on the coarsest."""
    b, h, w, _ = f1.shape
    up = None
    if coarse is not None:
        up = lax.stop_gradient(resize_flow(coarse, (h, w)))
        f2 = warp(P, f2, up)
    reach = s["range"][level]
    cost = matching_net(P, f"{path}/MatchingNet_0",
                        shifted_pairs(f1, f2, reach))
    if s["dap"]:
        cost = C.conv(P, f"{path}/DisplacementAwareProjection_0/Conv_0", cost,
                      cost.shape[-1], (1, 1), bias=False, kind="identity")
    flow = soft_argmin(cost, reach)
    if up is not None:
        flow = flow + up
    raw = flow
    if s["ctx"]:
        seen = jnp.concatenate(
            (lax.stop_gradient(flow), lax.stop_gradient(entropy(cost)), f1,
             resize_bilinear(img1, (h, w))), axis=-1)
        flow = flow + s["scale"][level] * context_net(
            P, f"{path}/CtfContextNet_0", seen, level)
    return flow, raw


# -- the model ----------------------------------------------------------------


def forward(P, model_cfg, img1, img2):
    """Every level's flow at the level's resolution, finest first, each
    followed by its raw flow (before the context network) where the
    configuration's ``raw`` is set: the program's list. Images are
    normalised to the model's range already, their sides multiples of 128."""
    s = settings(model_cfg)
    b = img1.shape[0]
    both = encoder(P, jnp.concatenate((img1, img2)), s["features"])
    out, flow = [], None
    for n, level in enumerate(LEVELS):
        flow, raw = flow_level(P, f"FlowLevel_{n}", s, level, img1,
                               both[level][:b], both[level][b:], flow)
        out = [flow] + ([raw] if s["raw"] else []) + out
    return out


def final_flow(outputs):
    """The finest flow (1/4) at the frame's resolution."""
    _, h, w, _ = outputs[0].shape
    return resize_flow(lax.stop_gradient(outputs[0]), (4 * h, 4 * w))


def spec(model_cfg, shape=(128, 128)):
    """The parameter specification: every leaf's path, shape and kind."""
    P = C.Params()
    img = jax.ShapeDtypeStruct((1, *shape, 3), jnp.float32)
    jax.eval_shape(lambda a, b: forward(P, model_cfg, a, b), img, img)
    return dict(P.spec)
