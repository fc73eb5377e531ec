"""Shared pieces of the plain references: parameter store, layers, optimizer.

Everything here is float32 ``jax.numpy`` / ``lax.conv_general_dilated`` at
``Precision.HIGHEST``. Nothing is imported from ``raft_meets_dicl_tpu``: a
reference is an independent statement of the configuration's mathematics,
written from the published descriptions (Teed & Deng, RAFT, ECCV 2020; the
thesis' RAFT+DICL hybrids), reading and writing parameters under the names
the program's checkpoints use so that one seeded tree serves both.

``Params`` is the whole parameter machinery. In *spec* mode (``values`` is
None) every ``get`` records the requested path, shape and kind, so tracing a
forward pass with ``jax.eval_shape`` yields the complete parameter
specification; ``init`` then draws every leaf from a seed in one jitted
call. In *value* mode ``get`` returns the stored leaf.

``quant`` is the control of "How correct is decided": the same reference
with every convolution and contraction operand rounded to float8 (e4m3),
the nearest precision below the configuration's bf16 policy. The benchmark
never runs it; ``benchmark/tests/control.py`` does.
"""

import math
import zlib

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

HIGHEST = lax.Precision.HIGHEST
EPS_NORM = 1e-5


class Params:
    def __init__(self, values=None, quant=None):
        self.values = values
        self.spec = {}
        self.quant = quant

    def get(self, path, shape, kind):
        if self.values is None:
            self.spec[path] = (tuple(int(s) for s in shape), kind)
            return jnp.zeros(shape, jnp.float32)
        leaf = self.values[path]
        if tuple(leaf.shape) != tuple(shape):
            raise ValueError(f"{path}: have {leaf.shape}, want {shape}")
        return leaf

    def q(self, x):
        """Operand rounding of the control; the identity in the reference."""
        if self.quant is None:
            return x
        # per-tensor scaling into the format's range, rounding in the
        # forward pass only (the backward pass sees the identity): what a
        # lower-precision path of the program would do
        top = float(jnp.finfo(self.quant).max) / 2.0
        scale = lax.stop_gradient(jnp.maximum(jnp.max(jnp.abs(x)), 1e-30)) / top
        low = (x / scale).astype(self.quant).astype(jnp.float32) * scale
        return x + lax.stop_gradient(low - x)


def init(spec, seed):
    """Every leaf of ``spec`` from ``seed``, float32, in one jitted call.

    Kernels are He-normal over the fan-in, biases and batch-norm offsets
    small normals, batch-norm scales and running variances near one: finite
    activations through 12 recurrent iterations, and no leaf whose gradient
    is structurally zero.
    """
    paths = sorted(spec)

    def make(key):
        out = {}
        for path in paths:
            shape, kind = spec[path]
            k = jax.random.fold_in(key, zlib.crc32(path.encode()) & 0x7FFFFFFF)
            n = jax.random.normal(k, shape, jnp.float32)
            if kind in ("kernel", "kernel_out"):
                # "kernel_out": a head whose output feeds the recurrence
                # (the flow update); a tenth of the gain keeps the random
                # network's updates at the size a trained one makes
                fan_in = max(1, int(np.prod(shape[:-1])))
                gain = 0.1 if kind == "kernel_out" else 1.0
                out[path] = n * (gain * math.sqrt(2.0 / fan_in))
            elif kind == "kernel_t":  # transposed conv: fan-in is the last axis
                fan_in = max(1, int(np.prod(shape[:2])) * shape[-1] // 4)
                out[path] = n * math.sqrt(2.0 / fan_in)
            elif kind == "identity":  # 1x1 projection initialised near identity
                eye = jnp.eye(shape[-2], shape[-1], dtype=jnp.float32)
                out[path] = eye.reshape(shape) + 0.01 * n
            elif kind == "bias":
                out[path] = 0.01 * n
            elif kind == "bn_scale":
                out[path] = 1.0 + 0.1 * n
            elif kind in ("bn_bias", "bn_mean"):
                out[path] = 0.1 * n
            elif kind == "bn_var":
                out[path] = 1.0 + 0.2 * jnp.tanh(n)
            else:
                raise ValueError(f"{path}: unknown parameter kind {kind}")
        return out

    return jax.jit(make)(jax.random.PRNGKey(int(seed) % (2 ** 31)))


def nest(flat):
    """``{'params/A/B/kernel': x}`` -> ``{'params': {'A': {'B': {...}}}}``."""
    out = {}
    for path, leaf in flat.items():
        node = out
        parts = path.split("/")
        for part in parts[:-1]:
            node = node.setdefault(part, {})
        node[parts[-1]] = leaf
    return out


def flatten(tree, prefix=""):
    """Inverse of :func:`nest` for nested dicts of arrays."""
    out = {}
    for name, sub in tree.items():
        path = f"{prefix}/{name}" if prefix else str(name)
        if isinstance(sub, dict) or hasattr(sub, "items"):
            out.update(flatten(sub, path))
        else:
            out[path] = sub
    return out


# -- layers -------------------------------------------------------------------


def conv(P, path, x, features, ksize, stride=1, bias=True, pad=None,
         kind="kernel"):
    kh, kw = ksize
    kernel = P.get(f"params/{path}/kernel", (kh, kw, x.shape[-1], features),
                   kind)
    if pad is None:
        pad = ((kh - 1) // 2, (kw - 1) // 2)
    y = lax.conv_general_dilated(
        P.q(x), P.q(kernel), (stride, stride),
        ((pad[0], pad[0]), (pad[1], pad[1])),
        dimension_numbers=("NHWC", "HWIO", "NHWC"), precision=HIGHEST)
    if bias:
        y = y + P.get(f"params/{path}/bias", (features,), "bias")
    return y


def instance_norm(x):
    mean = x.mean(axis=(1, 2), keepdims=True)
    var = ((x - mean) ** 2).mean(axis=(1, 2), keepdims=True)
    return (x - mean) / jnp.sqrt(var + EPS_NORM)


def batch_norm_frozen(P, path, x):
    """Batch norm on its running statistics (the stage freezes it; serving
    evaluates): an affine map per channel."""
    c = x.shape[-1]
    scale = P.get(f"params/{path}/BatchNorm_0/scale", (c,), "bn_scale")
    bias = P.get(f"params/{path}/BatchNorm_0/bias", (c,), "bn_bias")
    mean = P.get(f"batch_stats/{path}/BatchNorm_0/mean", (c,), "bn_mean")
    var = P.get(f"batch_stats/{path}/BatchNorm_0/var", (c,), "bn_var")
    return (x - mean) / jnp.sqrt(var + EPS_NORM) * scale + bias


def norm(P, path, x, kind):
    if kind == "instance":
        return instance_norm(x)
    if kind == "batch":
        return batch_norm_frozen(P, path, x)
    raise ValueError(kind)


def residual_block(P, path, x, planes, kind, stride):
    y = conv(P, f"{path}/Conv_0", x, planes, (3, 3), stride)
    y = jax.nn.relu(norm(P, f"{path}/Norm2d_0", y, kind))
    y = conv(P, f"{path}/Conv_1", y, planes, (3, 3))
    y = jax.nn.relu(norm(P, f"{path}/Norm2d_1", y, kind))
    if stride > 1:
        x = conv(P, f"{path}/Conv_2", x, planes, (1, 1), stride)
        x = norm(P, f"{path}/Norm2d_2", x, kind)
    return jax.nn.relu(x + y)


def stem(P, path, x, kind):
    """RAFT encoder trunk to 1/8 resolution, 128 channels."""
    x = conv(P, f"{path}/Conv_0", x, 64, (7, 7), 2)
    x = jax.nn.relu(norm(P, f"{path}/Norm2d_0", x, kind))
    plan = ((64, 1), (64, 1), (96, 2), (96, 1), (128, 2), (128, 1))
    for i, (planes, stride) in enumerate(plan):
        x = residual_block(P, f"{path}/ResidualBlock_{i}", x, planes, kind,
                           stride)
    return x


def motion_encoder(P, path, flow, corr):
    cor = jax.nn.relu(conv(P, f"{path}/Conv_0", corr, 256, (1, 1)))
    cor = jax.nn.relu(conv(P, f"{path}/Conv_1", cor, 192, (3, 3)))
    flo = jax.nn.relu(conv(P, f"{path}/Conv_2", flow, 128, (7, 7)))
    flo = jax.nn.relu(conv(P, f"{path}/Conv_3", flo, 64, (3, 3)))
    out = jnp.concatenate((cor, flo), axis=-1)
    out = jax.nn.relu(conv(P, f"{path}/Conv_4", out, 126, (3, 3)))
    return jnp.concatenate((out, flow), axis=-1)


def sep_conv_gru(P, path, h, x, hidden=128):
    for i, ksize in enumerate(((1, 5), (5, 1))):
        hx = jnp.concatenate((h, x), axis=-1)
        z = jax.nn.sigmoid(conv(P, f"{path}/Conv_{3 * i}", hx, hidden, ksize))
        r = jax.nn.sigmoid(conv(P, f"{path}/Conv_{3 * i + 1}", hx, hidden,
                                ksize))
        rhx = jnp.concatenate((r * h, x), axis=-1)
        q = jnp.tanh(conv(P, f"{path}/Conv_{3 * i + 2}", rhx, hidden, ksize))
        h = (1.0 - z) * h + z * q
    return h


def update_block(P, path, h, x, corr, flow):
    m = motion_encoder(P, f"{path}/BasicMotionEncoder_0", flow, corr)
    h = sep_conv_gru(P, f"{path}/SepConvGru_0",
                     h, jnp.concatenate((x, m), axis=-1))
    d = jax.nn.relu(conv(P, f"{path}/FlowHead_0/Conv_0", h, 256, (3, 3)))
    d = conv(P, f"{path}/FlowHead_0/Conv_1", d, 2, (3, 3), kind="kernel_out")
    return h, d


def convex_upsample_8x(P, path, hidden, flow, temperature=4.0):
    """RAFT's learned upsampling: each fine pixel is a convex combination
    of the 3x3 coarse neighbours of its cell (weights: softmax over the 9
    neighbours), of the flow scaled by 8."""
    mask = jax.nn.relu(conv(P, f"{path}/Conv_0", hidden, 256, (3, 3)))
    mask = conv(P, f"{path}/Conv_1", mask, 9 * 64, (1, 1))
    b, h, w, _ = flow.shape
    mask = jax.nn.softmax(mask.reshape(b, h, w, 9, 8, 8) / temperature, axis=3)
    fp = jnp.pad(8.0 * flow, ((0, 0), (1, 1), (1, 1), (0, 0)))
    nbrs = jnp.stack([fp[:, dy:dy + h, dx:dx + w, :]
                      for dy in range(3) for dx in range(3)], axis=3)
    up = jnp.einsum("bhwkrs,bhwkc->bhrwsc", mask, nbrs, precision=HIGHEST)
    return up.reshape(b, 8 * h, 8 * w, 2)


def hat(positions, size):
    """Bilinear weights of ``positions`` over an axis of ``size`` samples,
    zero outside: ``w[..., i] = max(0, 1 - |p - i|)``. Contracting with it
    is bilinear sampling with zero padding (``grid_sample`` with
    ``align_corners=True``), written densely because gathers run at a few
    tens of GB/s on the chip; ``tests/test_reference.py`` holds it against
    the four-tap gather."""
    idx = jnp.arange(size, dtype=jnp.float32)
    return jnp.maximum(0.0, 1.0 - jnp.abs(positions[..., None] - idx))


def grid(b, h, w):
    ys, xs = jnp.meshgrid(jnp.arange(h, dtype=jnp.float32),
                          jnp.arange(w, dtype=jnp.float32), indexing="ij")
    return jnp.broadcast_to(jnp.stack((xs, ys), axis=-1), (b, h, w, 2))


def normalize_images(img, clip=(0.0, 1.0), rng=(-1.0, 1.0)):
    x = jnp.clip(img.astype(jnp.float32), clip[0], clip[1])
    return (rng[1] - rng[0]) * x + rng[0]


# -- optimizer ----------------------------------------------------------------


def one_cycle_lr(step, max_lr, total_steps, pct_start, div_factor=25.0,
                 final_div_factor=1e4):
    """torch ``OneCycleLR`` with linear annealing, two phases."""
    initial = max_lr / div_factor
    up = pct_start * total_steps - 1.0
    down = total_steps - up - 1.0
    step = min(step, total_steps - 1)
    if step <= up:
        return initial + (max_lr - initial) * step / max(up, 1.0)
    low = initial / final_div_factor
    return max_lr + (low - max_lr) * (step - up) / max(down, 1.0)


def clip_by_global_norm(grads, max_norm):
    total = jnp.sqrt(sum(jnp.sum(g * g) for g in grads.values()))
    scale = jnp.where(total < max_norm, 1.0, max_norm / total)
    return {k: g * scale for k, g in grads.items()}, total


def adamw_step(params, grads, mu, nu, count, lr, wd, b1=0.9, b2=0.999,
               eps=1e-8):
    """One AdamW update (decoupled decay, bias-corrected moments)."""
    count = count + 1
    new_p, new_mu, new_nu = {}, {}, {}
    for k, p in params.items():
        g = grads[k]
        m = b1 * mu[k] + (1 - b1) * g
        v = b2 * nu[k] + (1 - b2) * g * g
        mh = m / (1 - b1 ** count)
        vh = v / (1 - b2 ** count)
        new_p[k] = p - lr * (mh / (jnp.sqrt(vh) + eps) + wd * p)
        new_mu[k], new_nu[k] = m, v
    return new_p, new_mu, new_nu, count
