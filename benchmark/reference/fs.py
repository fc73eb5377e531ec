"""Plain reference of ``raft/fs``: RAFT whose lookup never builds the
all-pairs volume (qzed/raft-meets-dicl, ``src/models/impls/raft_fs.py``
with ``cfg/model/raft-fs.yaml``).

Forward pass and sequence loss in float32 at highest matmul precision:
RAFT's instance-norm feature encoder on both frames, its batch-norm
context encoder on frame one, frame two's features average-pooled
``levels - 1`` times into a pyramid, and ``iterations`` recurrent
updates. In each iteration, on each level l, the (2r+1)^2 window is
*sampled, then dotted*: the level's pooled map is sampled bilinearly
(zeros outside) at ``coords / 2^l + (dx, dy)`` and each sample is dotted
with frame one's feature of the position, with no division by sqrt(C)
(the source's ``raft_fs.py:76``). The costs, channels ordered
(level, dx, dy) as RAFT's motion encoder takes them, feed the motion
encoder, separable ConvGRU and flow head; the flow entering an iteration
carries no gradient (RAFT detaches it). Every iterate is upsampled 8x by
RAFT's convex combination; the loss is sum_i gamma^(n-1-i) of the L1
distance to the target.

All levels are computed the same way and no correlation volume exists
anywhere: the program materialises the levels whose volume fits a budget
and runs a Mosaic kernel on the others, and this un-dispatched form is
what checks the dispatch.

Departures from the source's file, all shared with the program's
configuration or without effect on a number:

- batch norm runs on its running statistics (the stage freezes it), and
  the loss averages over valid pixels only;
- the window is sampled as a dense contraction with hat weights instead
  of ``grid_sample`` (the same arithmetic; ``tests/test_reference_fs.py``
  holds it against a four-tap gather), one row of positions at a time
  (``lax.map``), so that the samples of a row, (W, K, W2, C), are all
  that exists at once: at 136x240 positions a level-0 row is 0.53 GB
  where the whole level would be 72;
- the iterations run under ``lax.scan``; the iteration's body, a row of
  the window, an iterate's upsampling and each stage of the encoders are
  recomputed in the backward pass (``jax.checkpoint``), so that a
  float32 pair at two megapixels fits a 16 GB chip.
"""

import jax
import jax.numpy as jnp
from jax import lax

from . import common as C

FNET = "FeatureEncoderS3_0"
CNET = "FeatureEncoderS3_1"
STEP = "ScanCheckpoint_FsStep_0/BasicUpdateBlock_0"
UP8 = "Up8Network_0"
_PLAN = ((64, 1), (64, 1), (96, 2), (96, 1), (128, 2), (128, 1))

# what the source's module can be told and this reference does not follow
_FIXED = {"encoder-norm": "instance", "context-norm": "batch", "dropout": 0.0}


def settings(model_cfg):
    p = model_cfg["model"].get("parameters", {})
    a = model_cfg["model"].get("arguments", {})
    for key, value in _FIXED.items():
        if p.get(key, value) != value:
            raise ValueError(f"the fs reference implements {key}={value!r},"
                             f" not {p[key]!r}")
    return {
        "levels": int(p.get("corr-levels", 4)),
        "radius": int(p.get("corr-radius", 4)),
        "corr_channels": int(p.get("corr-channels", 256)),
        "hidden": int(p.get("recurrent-channels", 128)),
        "context": int(p.get("context-channels", 128)),
        "iterations": int(a.get("iterations", 12)),
    }


def _recomputed(P, fn):
    """``fn`` with its residuals recomputed in the backward pass; as it is
    in spec mode, where nothing is differentiated."""
    return fn if P.values is None else jax.checkpoint(fn)


def encoder(P, path, x, kind, out):
    """RAFT's encoder (``common.stem`` and a 1x1 convolution), a stage at
    a time: the stem's convolution and each residual block keep their
    input alone for the backward pass."""
    stem = f"{path}/_Stem_0"

    def first(x):
        x = C.conv(P, f"{stem}/Conv_0", x, 64, (7, 7), 2)
        return jax.nn.relu(C.norm(P, f"{stem}/Norm2d_0", x, kind))

    x = _recomputed(P, first)(x)
    for i, (planes, stride) in enumerate(_PLAN):
        x = _recomputed(P, lambda x, i=i, planes=planes, stride=stride:
                        C.residual_block(P, f"{stem}/ResidualBlock_{i}", x,
                                         planes, kind, stride))(x)
    return C.conv(P, f"{path}/Conv_0", x, out, (1, 1))


def pool2(v):
    """Average-pool a feature map (B, H, W, C) by 2, dropping an odd last
    row or column (``avg_pool2d``)."""
    b, h, w, c = v.shape
    v = v[:, : h // 2 * 2, : w // 2 * 2]
    return v.reshape(b, h // 2, 2, w // 2, 2, c).mean(axis=(2, 4))


def window_costs(P, f1, f2, centres, radius):
    """(B, H, W, K*K) costs of one level, channels ordered (dx, dy): the
    map ``f2`` sampled at the (2r+1)^2 integer displacements round
    ``centres`` (in ``f2``'s own samples, on ``f1``'s grid), each sample
    dotted with ``f1`` at the position."""
    d = jnp.arange(-radius, radius + 1, dtype=jnp.float32)
    k = 2 * radius + 1
    b, h, w, _ = f1.shape
    q2 = P.q(f2)

    def row(args):
        f1_row, c_row = args                                   # (B,W,C), (B,W,2)
        wx = C.hat(c_row[..., 0:1] + d, f2.shape[2])           # (B, W, K, W2)
        wy = C.hat(c_row[..., 1:2] + d, f2.shape[1])           # (B, W, K, H2)
        rows = jnp.einsum("bjyh,bhwc->bjywc", wy, q2, precision=C.HIGHEST)
        window = jnp.einsum("bjywc,bjxw->bjxyc", rows, wx,
                            precision=C.HIGHEST)               # the samples
        return jnp.einsum("bjxyc,bjc->bjxy", P.q(window), P.q(f1_row),
                          precision=C.HIGHEST)

    costs = lax.map(_recomputed(P, row),
                    (f1.swapaxes(0, 1), centres.swapaxes(0, 1)))
    return costs.swapaxes(0, 1).reshape(b, h, w, k * k)


def lookup(P, f1, pyramid, coords, radius):
    """(B, H, W, L*(2r+1)^2), channels ordered (level, dx, dy)."""
    return jnp.concatenate([
        window_costs(P, f1, f2, coords / 2 ** lvl, radius)
        for lvl, f2 in enumerate(pyramid)], axis=-1)


def forward(P, model_cfg, img1, img2):
    """All iterates, upsampled: (iterations, B, H, W, 2). Images are
    normalised to the model's range already, their sides multiples of 8."""
    s = settings(model_cfg)
    f1 = encoder(P, FNET, img1, "instance", s["corr_channels"])
    f2 = encoder(P, FNET, img2, "instance", s["corr_channels"])
    ctx = encoder(P, CNET, img1, "batch", s["hidden"] + s["context"])
    h = jnp.tanh(ctx[..., : s["hidden"]])
    x = jax.nn.relu(ctx[..., s["hidden"]:])

    pyramid = [f2]
    for _ in range(1, s["levels"]):
        pyramid.append(pool2(pyramid[-1]))
    b, hc, wc, _ = f1.shape
    coords0 = C.grid(b, hc, wc)

    def body(carry, _):
        h, flow = carry
        flow = lax.stop_gradient(flow)
        corr = lookup(P, f1, pyramid, coords0 + flow, s["radius"])
        h, d = C.update_block(P, STEP, h, x, corr, flow)
        flow = flow + d
        return (h, flow), (h, flow)

    def up8(hf):
        return C.convex_upsample_8x(P, UP8, *hf)

    start = (h, jnp.zeros((b, hc, wc, 2), jnp.float32))
    if P.values is None:   # spec mode: one iteration names every parameter
        _, (hs, flows) = body(start, None)
        return up8((hs, flows))[None]
    _, (hs, flows) = lax.scan(jax.checkpoint(body), start, None,
                              length=s["iterations"])
    return lax.map(jax.checkpoint(up8), (hs, flows))


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
