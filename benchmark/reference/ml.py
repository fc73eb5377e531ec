"""Plain reference of ``raft+dicl/ml``: RAFT with DICL's learned cost on
several pyramid levels in every iteration (qzed/raft-meets-dicl,
``src/models/impls/raft_dicl_ml.py``; master thesis *RAFT meets DICL*,
Univ. Stuttgart 2022).

Forward pass and sequence loss in float32 at highest matmul precision.
RAFT's residual encoder (instance norm, 256 channels at 1/8) runs on both
frames. Frame one's features become a *stack*: ``levels`` heads at 1/8
resolution, the head of level l a 3x3 convolution of dilation 2^l, norm,
relu and a 1x1 convolution to 32 channels, with one residual block
between two heads. Frame two's become a *pyramid*: the same head
(dilation 1) at 1/8, 1/16, 1/32 and 1/64, a residual block of stride 2
(384, 576, 864 channels) between two levels. One RAFT recurrence runs at
1/8. In each iteration, on each level l, frame two's map of that level is
sampled bilinearly (zero outside) at the (2r+1)^2 integer displacements
round the current correspondence divided by 2^l: the map is 2^l times
coarser than the centres, so a displacement of one sample spans 2^l
pixels of the 1/8 grid. Each displaced map is stacked on frame one's
features of the level and run through the level's own MatchingNet (an
hourglass of five conv-batchnorm-relu blocks, the second of stride 2, the
fifth a transposed convolution back up, and a last 3x3 convolution to one
channel), and the level's displacement-aware projection mixes its
(2r+1)^2 costs with a 1x1 convolution. The levels' costs, concatenated,
feed RAFT's motion encoder, separable ConvGRU and flow head. The flow
entering an iteration carries no gradient (RAFT detaches it). Every
iterate is upsampled 8x by RAFT's convex combination; the loss is
sum_i gamma^(n-1-i) of the L1 distance to the target.

Departures from the source's file, all shared with the program's
configuration or without effect on a number:

- batch norm runs on its running statistics (the Things stage freezes
  it), and the loss averages over valid pixels only;
- the window is sampled as a dense contraction with hat weights instead
  of ``grid_sample`` (the same arithmetic; ``tests/test_reference_ml.py``
  holds it against a four-tap gather on a map coarser than its centres);
- the levels are a plain python loop, one MatchingNet call a level: the
  program evaluates them in one batched call, and this un-batched form
  is what checks it (``share-dicl: true`` reads the first level's
  parameters on every level, as the source does);
- the iterations run under ``lax.scan`` and an iteration's residuals are
  recomputed in the backward pass (``jax.checkpoint``): a float32
  backward pass that kept the MatchingNets' activations of every level
  and iteration would need tens of GB a pair;
- the soft-argmax readout of each level's cost (``readouts``), scaled by
  2^l, is part of the source's module and of no default output: the loss
  never sees it.
"""

import jax
import jax.numpy as jnp
from jax import lax

from . import common as C

FNET = "FeatureEncoderS3_0"
CNET = "FeatureEncoderS3_1"
STACK = "StackEncoder_0"
PYRAMID = "PyramidEncoder_0"
CORR = "MlCorrelationModule_0"
UPDATE = "BasicUpdateBlock_0"
UP8 = "Up8Network_0"
_STAGES = (384, 576, 864)    # channels of frame two's stages past 1/8

# what the source's module can be told and this reference does not follow
_FIXED = {"encoder-norm": "instance", "context-norm": "batch",
          "mnet-norm": "batch", "encoder-type": "raft-cnn",
          "dap-type": "separate", "corr-reg-type": "softargmax",
          "dropout": 0.0}


def settings(model_cfg):
    p = model_cfg["model"].get("parameters", {})
    a = model_cfg["model"].get("arguments", {})
    for key, value in _FIXED.items():
        if p.get(key, value) != value:
            raise ValueError(f"the ml reference implements {key}={value!r},"
                             f" not {p[key]!r}")
    levels = int(p.get("corr-levels", 4))
    if not 1 <= levels <= 1 + len(_STAGES):
        raise ValueError(f"corr-levels {levels}: between 1 and 4")
    return {
        "levels": levels,
        "radius": int(p.get("corr-radius", 4)),
        "features": int(p.get("corr-channels", 32)),
        "hidden": int(p.get("recurrent-channels", 128)),
        "context": int(p.get("context-channels", 128)),
        "share": bool(p.get("share-dicl", False)),
        "iterations": int(a.get("iterations", 12)),
        "dap": bool(a.get("dap", True)),
    }


# -- encoders -----------------------------------------------------------------


def encoder(P, path, x, kind, out):
    """RAFT's encoder: the trunk to 1/8 and a 1x1 convolution."""
    return C.conv(P, f"{path}/Conv_0", C.stem(P, f"{path}/_Stem_0", x, kind),
                  out, (1, 1))


def conv_dilated(P, path, x, features, dilation):
    """3x3 convolution with its taps ``dilation`` samples apart, padded to
    keep the size."""
    kernel = P.get(f"params/{path}/kernel", (3, 3, x.shape[-1], features),
                   "kernel")
    y = lax.conv_general_dilated(
        P.q(x), P.q(kernel), (1, 1), ((dilation, dilation),) * 2,
        rhs_dilation=(dilation, dilation),
        dimension_numbers=("NHWC", "HWIO", "NHWC"), precision=C.HIGHEST)
    return y + P.get(f"params/{path}/bias", (features,), "bias")


def output_net(P, path, x, out, dilation):
    x = conv_dilated(P, f"{path}/Conv_0", x, 128, dilation)
    x = jax.nn.relu(C.instance_norm(x))
    return C.conv(P, f"{path}/Conv_1", x, out, (1, 1))


def stack_encoder(P, x, levels, out):
    """Frame one: every level at the input's resolution, level l through
    l residual blocks and a head of dilation 2^l."""
    outs = [output_net(P, f"{STACK}/_OutputNet_0", x, out, 1)]
    for lvl in range(1, levels):
        x = C.residual_block(P, f"{STACK}/ResidualBlock_{lvl - 1}", x, 256,
                             "instance", 1)
        outs.append(output_net(P, f"{STACK}/_OutputNet_{lvl}", x, out,
                               2 ** lvl))
    return outs


def pyramid_encoder(P, x, levels, out):
    """Frame two: level l at 1/2^l of the input's resolution."""
    outs = [output_net(P, f"{PYRAMID}/_OutputNet_0", x, out, 1)]
    for lvl, channels in enumerate(_STAGES[: levels - 1]):
        x = C.residual_block(P, f"{PYRAMID}/ResidualBlock_{lvl}", x, channels,
                             "instance", 2)
        outs.append(output_net(P, f"{PYRAMID}/_OutputNet_{lvl + 1}", x, out,
                               1))
    return outs


# -- the learned cost ---------------------------------------------------------


def sample_window(P, f2, coords, radius):
    """``f2`` at the (2r+1)^2 integer displacements round ``coords``,
    bilinear with zero padding: (B, K, K, H, W, C), the first window axis
    the displacement in x. ``coords`` are in ``f2``'s own samples, on a
    grid (H, W) that need not be ``f2``'s. Every displacement shares the
    centre's fractions, so the sampling is one contraction with hat
    weights along y and one along x."""
    d = jnp.arange(-radius, radius + 1, dtype=jnp.float32)
    wx = C.hat(coords[..., 0:1] + d, f2.shape[2])        # (B, H, W, K, W2)
    wy = C.hat(coords[..., 1:2] + d, f2.shape[1])        # (B, H, W, K, H2)
    rows = jnp.einsum("bijyh,bhwc->bijywc", wy, P.q(f2), precision=C.HIGHEST)
    return jnp.einsum("bijywc,bijxw->bxyijc", rows, wx, precision=C.HIGHEST)


def conv_block(P, path, x, features, stride=1):
    x = C.conv(P, f"{path}/Conv_0", x, features, (3, 3), stride, bias=False)
    return jax.nn.relu(C.batch_norm_frozen(P, f"{path}/Norm2d_0", x))


def conv_block_transposed(P, path, x, features):
    """Transposed convolution, kernel 4, stride 2, padding 1 (twice the
    resolution), then batch norm and relu. Written as the convolution of
    the input dilated by 2 and padded by 2, kernel unflipped: the form the
    program's layer stores its kernel in."""
    kernel = P.get(f"params/{path}/ConvTranspose_0/kernel",
                   (4, 4, x.shape[-1], features), "kernel_t")
    x = lax.conv_general_dilated(
        P.q(x), P.q(kernel), (1, 1), ((2, 2), (2, 2)), lhs_dilation=(2, 2),
        dimension_numbers=("NHWC", "HWIO", "NHWC"), precision=C.HIGHEST)
    return jax.nn.relu(C.batch_norm_frozen(P, f"{path}/Norm2d_0", x))


def matching_net(P, path, f1, window):
    """One cost per displacement and position: (B, H, W, K, K)."""
    b, k, _, h, w, c = window.shape
    pair = jnp.concatenate(
        (jnp.broadcast_to(f1[:, None, None], window.shape), window), axis=-1)
    x = pair.reshape(b * k * k, h, w, 2 * c)
    x = conv_block(P, f"{path}/ConvBlock_0", x, 96)
    x = conv_block(P, f"{path}/ConvBlock_1", x, 128, stride=2)
    x = conv_block(P, f"{path}/ConvBlock_2", x, 128)
    x = conv_block(P, f"{path}/ConvBlock_3", x, 64)
    x = conv_block_transposed(P, f"{path}/ConvBlockTransposed_0", x, 32)
    x = C.conv(P, f"{path}/Conv_0", x, 1, (3, 3))
    return x.reshape(b, k, k, h, w).transpose(0, 3, 4, 1, 2)


def level_cost(P, own, f1, f2, coords, radius, dap):
    """(B, H, W, K*K) learned costs of one level, channels ordered
    (dx, dy); ``own`` numbers the level's MatchingNet and projection."""
    window = sample_window(P, f2, coords, radius)
    cost = matching_net(P, f"{CORR}/MatchingNet_{own}", f1, window)
    b, h, w, k, _ = cost.shape
    cost = cost.reshape(b, h, w, k * k)
    if dap:
        cost = C.conv(
            P, f"{CORR}/DisplacementAwareProjection_{own}/Conv_0", cost,
            k * k, (1, 1), bias=False, kind="identity")
    return cost


def soft_argmin(cost, radius):
    """DICL's flow readout: the displacements weighted by the softmax of
    their costs (the source calls the scores costs and takes the softmax
    of them as they are)."""
    d = jnp.arange(-radius, radius + 1, dtype=jnp.float32)
    dx, dy = jnp.meshgrid(d, d, indexing="ij")
    delta = jnp.stack((dx, dy), axis=-1).reshape(-1, 2)
    return jnp.einsum("bhwd,dc->bhwc", jax.nn.softmax(cost, axis=-1), delta,
                      precision=C.HIGHEST)


# -- the model ----------------------------------------------------------------


def iterates(P, model_cfg, img1, img2):
    """``(flows, readouts)``: the iterates at the image's resolution,
    (iterations, B, H, W, 2), and each level's soft-argmax readout at 1/8,
    (levels, iterations, B, H/8, W/8, 2)."""
    s = settings(model_cfg)
    f1 = stack_encoder(P, encoder(P, FNET, img1, "instance", 256),
                       s["levels"], s["features"])
    f2 = pyramid_encoder(P, encoder(P, FNET, img2, "instance", 256),
                         s["levels"], s["features"])
    ctx = encoder(P, CNET, img1, "batch", s["hidden"] + s["context"])
    h = jnp.tanh(ctx[..., : s["hidden"]])
    x = jax.nn.relu(ctx[..., s["hidden"]:])
    b, hc, wc, _ = f1[0].shape
    coords0 = C.grid(b, hc, wc)

    def body(carry, _):
        h, flow = carry
        flow = lax.stop_gradient(flow)
        costs = []
        for lvl in range(s["levels"]):      # one MatchingNet call a level
            costs.append(level_cost(
                P, 0 if s["share"] else lvl, f1[lvl], f2[lvl],
                (coords0 + flow) / 2 ** lvl, s["radius"], s["dap"]))
        readout = jnp.stack([
            flow + 2 ** lvl * soft_argmin(cost, s["radius"])
            for lvl, cost in enumerate(costs)])
        h, d = C.update_block(P, UPDATE, h, x,
                              jnp.concatenate(costs, axis=-1), flow)
        flow = flow + d
        return (h, flow), (h, flow, readout)

    start = (h, jnp.zeros((b, hc, wc, 2), jnp.float32))
    if P.values is None:   # spec mode: one iteration names every leaf
        _, out = body(start, None)
        hs, flows, readouts = (o[None] for o in out)
    else:
        _, (hs, flows, readouts) = lax.scan(
            jax.checkpoint(body), start, None, length=s["iterations"])
    n = flows.shape[0]
    up = C.convex_upsample_8x(P, UP8, hs.reshape(n * b, hc, wc, -1),
                              flows.reshape(n * b, hc, wc, 2))
    return up.reshape(n, b, 8 * hc, 8 * wc, 2), readouts.swapaxes(0, 1)


def forward(P, model_cfg, img1, img2):
    """All iterates, upsampled: (iterations, B, H, W, 2). Images are
    normalised to the model's range already, their sides multiples of
    8 * 2^(levels - 1)."""
    return iterates(P, model_cfg, img1, img2)[0]


def final_flow(outputs):
    return outputs[-1]


def loss_sum(outputs, target, valid, loss_args):
    """Sequence loss before its division by the number of valid pixels:
    sum_i gamma^(n-1-i) * sum_valid |flow_i - target|_ord."""
    gamma = float(loss_args.get("gamma", 0.8))
    ord_ = loss_args.get("ord", 1)
    n = outputs.shape[0]
    v = valid.astype(jnp.float32)
    total = 0.0
    for i in range(n):
        dist = jnp.linalg.norm(outputs[i] - target, ord=ord_, axis=-1)
        total = total + gamma ** (n - 1 - i) * jnp.sum(dist * v)
    return total


def spec(model_cfg, shape=(128, 128)):
    """The parameter specification: every leaf's path, shape and kind."""
    P = C.Params()
    img = jax.ShapeDtypeStruct((1, *shape, 3), jnp.float32)
    jax.eval_shape(lambda a, b: forward(P, model_cfg, a, b), img, img)
    return dict(P.spec)
