"""Plain reference of ``raft/baseline`` (Teed & Deng, RAFT, ECCV 2020).

Forward pass and sequence loss in float32 at highest matmul precision:
instance-norm feature encoder on both frames, batch-norm context encoder
on frame one, the all-pairs correlation volume divided by sqrt(C) and
average-pooled into a 4-level pyramid, and ``iterations`` recurrent
updates: bilinear lookup of a (2r+1)^2 window per level around the
current correspondence, motion encoder, separable ConvGRU, flow head;
every iterate is upsampled 8x by the learned convex combination. The flow
entering an iteration carries no gradient (RAFT detaches it).

Departures from the paper, all shared with the program's configuration
file: batch norm runs on its running statistics (the Things stage freezes
it), the loss averages over valid pixels only.
"""

import math

import jax
import jax.numpy as jnp
from jax import lax

from . import common as C

FNET = "FeatureEncoderS3_0"
CNET = "FeatureEncoderS3_1"
STEP = "ScanCheckpoint_RaftStep_0/BasicUpdateBlock_0"
UP8 = "Up8Network_0"


def settings(model_cfg):
    p = model_cfg["model"].get("parameters", {})
    a = model_cfg["model"].get("arguments", {})
    return {
        "levels": int(p.get("corr-levels", 4)),
        "radius": int(p.get("corr-radius", 4)),
        "corr_channels": int(p.get("corr-channels", 256)),
        "hidden": int(p.get("recurrent-channels", 128)),
        "context": int(p.get("context-channels", 128)),
        "iterations": int(a.get("iterations", 12)),
    }


def encoder(P, path, x, kind, out):
    return C.conv(P, f"{path}/Conv_0", C.stem(P, f"{path}/_Stem_0", x, kind),
                  out, (1, 1))


def pool2(v):
    """Average-pool the last two axes by 2, dropping an odd last row or
    column (``avg_pool2d``)."""
    *lead, h, w = v.shape
    v = v[..., : h // 2 * 2, : w // 2 * 2]
    return v.reshape(*lead, h // 2, 2, w // 2, 2).mean(axis=(-3, -1))


def correlation_pyramid(P, f1, f2, levels):
    corr = jnp.einsum("bijc,bklc->bijkl", P.q(f1), P.q(f2),
                      precision=C.HIGHEST) / math.sqrt(f1.shape[-1])
    pyramid = [corr]
    for _ in range(1, levels):
        corr = pool2(corr)
        pyramid.append(corr)
    return pyramid


def lookup(P, pyramid, coords, radius):
    """(B, H, W, L*(2r+1)^2) windows, channels ordered (level, dx, dy)."""
    d = jnp.arange(-radius, radius + 1, dtype=jnp.float32)
    b, h, w, _ = coords.shape
    k = 2 * radius + 1
    out = []
    for lvl, corr in enumerate(pyramid):
        centre = coords / 2 ** lvl
        wx = C.hat(centre[..., 0:1] + d, corr.shape[-1])   # (B,H,W,K,W2)
        wy = C.hat(centre[..., 1:2] + d, corr.shape[-2])   # (B,H,W,K,H2)
        rows = jnp.einsum("bijyh,bijhw->bijyw", wy, P.q(corr),
                          precision=C.HIGHEST)
        win = jnp.einsum("bijyw,bijxw->bijxy", rows, wx, precision=C.HIGHEST)
        out.append(win.reshape(b, h, w, k * k))
    return jnp.concatenate(out, axis=-1)


def forward(P, model_cfg, img1, img2):
    """All iterates, upsampled: (iterations, B, H, W, 2). Images are
    normalised to the model's range already."""
    s = settings(model_cfg)
    f1 = encoder(P, FNET, img1, "instance", s["corr_channels"])
    f2 = encoder(P, FNET, img2, "instance", s["corr_channels"])
    ctx = encoder(P, CNET, img1, "batch", s["hidden"] + s["context"])
    h = jnp.tanh(ctx[..., : s["hidden"]])
    x = jax.nn.relu(ctx[..., s["hidden"]:])

    pyramid = correlation_pyramid(P, f1, f2, s["levels"])
    b, hc, wc, _ = f1.shape
    coords0 = C.grid(b, hc, wc)

    def body(carry, _):
        h, flow = carry
        flow = lax.stop_gradient(flow)
        corr = lookup(P, pyramid, coords0 + flow, s["radius"])
        h, d = C.update_block(P, STEP, h, x, corr, flow)
        flow = flow + d
        return (h, flow), (h, flow)

    if P.values is None:   # spec mode: one iteration names every parameter
        _, (hs, flows) = body((h, jnp.zeros((b, hc, wc, 2))), None)
        hs, flows = hs[None], flows[None]
    else:
        # the iteration's residuals are recomputed in the backward pass:
        # the chip pads the small window tensors to full tiles, and twelve
        # iterations of them would not fit beside the volume
        _, (hs, flows) = lax.scan(jax.checkpoint(body),
                                  (h, jnp.zeros((b, hc, wc, 2))), None,
                                  length=s["iterations"])
    n = hs.shape[0]
    up = C.convex_upsample_8x(P, UP8, hs.reshape(n * b, hc, wc, -1),
                              flows.reshape(n * b, hc, wc, 2))
    return up.reshape(n, b, 8 * hc, 8 * wc, 2)


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


def spec(model_cfg, shape=(64, 96)):
    """The parameter specification: every leaf's path, shape and kind."""
    P = C.Params()
    img = jax.ShapeDtypeStruct((1, *shape, 3), jnp.float32)
    jax.eval_shape(lambda a, b: forward(P, model_cfg, a, b), img, img)
    return dict(P.spec)
