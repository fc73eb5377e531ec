"""Plain reference of ``raft+dicl/ctf-l3``: RAFT+DICL coarse-to-fine over
three levels (qzed/raft-meets-dicl, ``src/models/impls/raft_dicl_ctf_l3.py``;
master thesis *RAFT meets DICL*, Univ. Stuttgart 2022).

Forward pass and multi-level sequence loss in float32 at highest matmul
precision. A RAFT residual encoder is extended to a pyramid (1/8, 1/16,
1/32), one with instance norm for the features of both frames (32
channels a level) and one with batch norm for the context of frame one
(128 recurrent + 128 context channels a level). From the coarsest level
to the finest, each level runs a RAFT recurrence whose correlation lookup
is DICL's learned cost: the second frame's features are sampled
bilinearly (zero outside) at the (2r+1)^2 integer displacements round the
current correspondence, each displaced map is stacked on frame one's
features and run through the MatchingNet (an hourglass of five
conv-batchnorm-relu blocks, the second of stride 2, the fifth a
transposed convolution back up, and a last 3x3 convolution to one
channel), and the displacement-aware projection mixes the (2r+1)^2 costs
with a 1x1 convolution. The cost feeds RAFT's motion encoder, separable
ConvGRU and flow head. The flow entering an iteration carries no gradient
(RAFT detaches it); from level to level the flow is doubled in
resolution and value by align-corners bilinear interpolation, and the
hidden state starts again from the level's own context (``upsample-hidden:
none``). The finest level's iterates are upsampled 8x by RAFT's convex
combination; the loss is sum_level alpha_level sum_i gamma^(n-1-i) of the
L1 distance to the target, coarse iterates resized to the target by
align-corners bilinear interpolation with their values rescaled.

Departures from the published description, all shared with the program's
configuration file: batch norm runs on its running statistics (the Things
stage freezes it), the loss averages over valid pixels only, and the
window is sampled as a dense contraction with hat weights instead of
``grid_sample`` (the same arithmetic; ``tests/test_reference_ctf3.py``
holds it against a four-tap gather). The soft-argmin readout of the
cost (``readouts``) is part of the source's module and of no default
output: the loss never sees it.
"""

import jax
import jax.numpy as jnp
from jax import lax

from . import common as C

FNET = "FeatureEncoderPyramid_0"
CNET = "FeatureEncoderPyramid_1"
UPDATE = "BasicUpdateBlock_0"
UP8 = "Up8Network_0"
LEVELS = 3
_STAGES = (160, 192)        # channels of the residual stages past 1/8

# what the source's module can be told and this reference does not follow
_FIXED = {"encoder-norm": "instance", "context-norm": "batch",
          "mnet-norm": "batch", "encoder-type": "raft",
          "context-type": "raft", "corr-type": "dicl",
          "corr-reg-type": "softargmax", "share-dicl": False,
          "share-rnn": True, "upsample-hidden": "none"}


def settings(model_cfg):
    p = model_cfg["model"].get("parameters", {})
    a = model_cfg["model"].get("arguments", {})
    for key, value in _FIXED.items():
        if p.get(key, value) != value:
            raise ValueError(f"the ctf3 reference implements {key}={value!r},"
                             f" not {p[key]!r}")
    iterations = tuple(int(n) for n in a.get("iterations", (4, 3, 3)))
    if len(iterations) != LEVELS:
        raise ValueError(f"iterations {iterations}: one count a level")
    return {
        "radius": int(p.get("corr-radius", 4)),
        "features": int(p.get("corr-channels", 32)),
        "hidden": int(p.get("recurrent-channels", 128)),
        "context": int(p.get("context-channels", 128)),
        "iterations": iterations,
        "dap": bool(a.get("dap", True)),
    }


# -- encoders -----------------------------------------------------------------


def output_net(P, path, x, out, width, kind):
    x = C.conv(P, f"{path}/Conv_0", x, width, (3, 3))
    x = jax.nn.relu(C.norm(P, f"{path}/Norm2d_0", x, kind))
    return C.conv(P, f"{path}/Conv_1", x, out, (1, 1))


def pyramid_encoder(P, path, x, kind, out):
    """Features at 1/8, 1/16 and 1/32, finest first: RAFT's trunk, then two
    residual blocks a further level, and a head of its own on each level."""
    x = C.stem(P, f"{path}/_Stem_0", x, kind)
    outs = []
    for i in range(LEVELS):
        outs.append(output_net(P, f"{path}/EncoderOutputNet_{i}", x, out,
                               160 + 32 * i, kind))
        if i + 1 < LEVELS:
            x = C.residual_block(P, f"{path}/ResidualBlock_{2 * i}", x,
                                 _STAGES[i], kind, 2)
            x = C.residual_block(P, f"{path}/ResidualBlock_{2 * i + 1}", x,
                                 _STAGES[i], kind, 1)
    return outs


# -- the learned cost ---------------------------------------------------------


def sample_window(P, f2, coords, radius):
    """``f2`` at the (2r+1)^2 integer displacements round ``coords``,
    bilinear with zero padding: (B, K, K, H, W, C), the first window axis
    the displacement in x. Every displacement shares the centre's
    fractions, so the sampling is one contraction with hat weights along y
    and one along x."""
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


def cost_volume(P, path, f1, f2, coords, radius, dap):
    """(B, H, W, K*K) learned costs, channels ordered (dx, dy)."""
    window = sample_window(P, f2, coords, radius)
    cost = matching_net(P, f"{path}/MatchingNet_0", f1, window)
    b, h, w, k, _ = cost.shape
    cost = cost.reshape(b, h, w, k * k)
    if dap:
        cost = C.conv(P, f"{path}/DisplacementAwareProjection_0/Conv_0", cost,
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


# -- resizing -----------------------------------------------------------------


def resize_bilinear(x, size):
    """Align-corners bilinear resize of (..., H, W, C) to ``size``."""
    hi, wi = x.shape[-3], x.shape[-2]
    ho, wo = size
    if (hi, wi) == (ho, wo):
        return x
    wy = C.hat(jnp.linspace(0.0, hi - 1.0, ho), hi)      # (Ho, H)
    wx = C.hat(jnp.linspace(0.0, wi - 1.0, wo), wi)      # (Wo, W)
    x = jnp.einsum("oh,...hwc->...owc", wy, x, precision=C.HIGHEST)
    return jnp.einsum("pw,...owc->...opc", wx, x, precision=C.HIGHEST)


def resize_flow(flow, size):
    """A flow field at another resolution: values scale with the grid."""
    h, w = flow.shape[-3], flow.shape[-2]
    scale = jnp.asarray([size[1] / w, size[0] / h], jnp.float32)
    return resize_bilinear(flow, size) * scale


# -- the model ----------------------------------------------------------------


def levels(P, model_cfg, img1, img2):
    """``(flows, readouts)``, each a tuple over the levels, coarsest first:
    flows (iterations, B, h, w, 2) at the level's resolution, the finest
    level's at the image's; readouts at the level's resolution."""
    s = settings(model_cfg)
    b = img1.shape[0]
    both = pyramid_encoder(P, FNET, jnp.concatenate((img1, img2)), "instance",
                           s["features"])
    f1, f2 = [f[:b] for f in both], [f[b:] for f in both]
    ctx = pyramid_encoder(P, CNET, img1, "batch", s["hidden"] + s["context"])

    flow, flows_out, readouts_out = None, [], []
    for li in range(LEVELS):
        fine = LEVELS - 1 - li               # index into finest-first lists
        path = f"CorrelationModule_{li}"
        _, hl, wl, _ = f1[fine].shape
        coords0 = C.grid(b, hl, wl)
        flow = (jnp.zeros((b, hl, wl, 2), jnp.float32) if flow is None
                else resize_flow(flow, (hl, wl)))
        h = jnp.tanh(ctx[fine][..., : s["hidden"]])
        x = jax.nn.relu(ctx[fine][..., s["hidden"]:])

        def body(carry, _, path=path, fine=fine, x=x, coords0=coords0):
            h, flow = carry
            flow = lax.stop_gradient(flow)
            cost = cost_volume(P, path, f1[fine], f2[fine], coords0 + flow,
                               s["radius"], s["dap"])
            readout = flow + soft_argmin(cost, s["radius"])
            h, d = C.update_block(P, UPDATE, h, x, cost, flow)
            flow = flow + d
            return (h, flow), (h, flow, readout)

        if P.values is None:   # spec mode: one iteration names every leaf
            (h, flow), out = body((h, flow), None)
            hs, flows, readouts = (o[None] for o in out)
        else:
            # an iteration's residuals are recomputed in the backward
            # pass: the MatchingNet's activations over 81 displacements
            # would not fit ten times over
            (h, flow), (hs, flows, readouts) = lax.scan(
                jax.checkpoint(body), (h, flow), None,
                length=s["iterations"][li])
        if fine == 0:
            n = flows.shape[0]
            up = C.convex_upsample_8x(P, UP8, hs.reshape(n * b, hl, wl, -1),
                                      flows.reshape(n * b, hl, wl, 2))
            flows = up.reshape(n, b, 8 * hl, 8 * wl, 2)
        flows_out.append(flows)
        readouts_out.append(readouts)
    return tuple(flows_out), tuple(readouts_out)


def forward(P, model_cfg, img1, img2):
    """Every level's iterates, coarsest level first. Images are normalised
    to the model's range already, their sides multiples of 64."""
    return levels(P, model_cfg, img1, img2)[0]


def final_flow(outputs):
    return outputs[-1][-1]


def loss_sum(outputs, target, valid, loss_args):
    """The multi-level sequence loss before its division by the number of
    valid pixels: sum_l alpha_l sum_i gamma^(n-1-i) sum_valid
    |flow_li - target|_ord, flow_li resized to the target."""
    gamma = float(loss_args.get("gamma", 0.8))
    alpha = loss_args.get("alpha", (0.38, 0.6, 1.0))
    ord_ = loss_args.get("ord", 1)
    v = valid.astype(jnp.float32)
    size = target.shape[-3:-1]
    total = 0.0
    for level, a in zip(outputs, alpha, strict=True):
        n = level.shape[0]
        for i in range(n):
            dist = jnp.linalg.norm(resize_flow(level[i], size) - target,
                                   ord=ord_, axis=-1)
            total = total + float(a) * gamma ** (n - 1 - i) * jnp.sum(dist * v)
    return total


def spec(model_cfg, shape=(64, 128)):
    """The parameter specification: every leaf's path, shape and kind."""
    P = C.Params()
    img = jax.ShapeDtypeStruct((1, *shape, 3), jnp.float32)
    jax.eval_shape(lambda a, b: forward(P, model_cfg, a, b), img, img)
    return dict(P.spec)
