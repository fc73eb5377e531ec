"""Pure jnp metric math — usable eagerly on host arrays and under jit.

The metric *classes* (flowmetrics/trainmetrics) wrap these functions behind
the reference's config-constructible registry (src/metrics/common.py:5-41).
Keeping the math here as pure functions lets the jitted validation/eval
steps compute metrics on-device (scalars only cross the host boundary, the
TPU-first design) while the eval command reuses the exact same definitions
eagerly.

Layout note: all flow tensors are NHWC — ``estimate``/``target`` are
(..., H, W, 2) with channels last, ``valid`` is (..., H, W). The reference
computes the same quantities on NCHW with ``dim=-3``
(src/metrics/epe.py:39, fl_all.py:34-35).
"""

import jax
import jax.numpy as jnp
import numpy as np


def masked_mean(x, valid):
    """Mean of ``x`` over pixels where ``valid``; 0 if no pixel is valid."""
    v = valid.astype(x.dtype)
    return jnp.sum(x * v) / jnp.maximum(jnp.sum(v), 1.0)


def end_point_error(estimate, target, valid, distances=(1, 3, 5)):
    """EPE mean + accuracy-at-distance fractions over valid pixels.

    Matches src/metrics/epe.py:36-52: the ``{d}px`` entries are the fraction
    of valid pixels with EPE ≤ d (inverted bad-pixel rate).
    """
    epe = jnp.linalg.norm(estimate - target, ord=2, axis=-1)

    out = {"mean": masked_mean(epe, valid)}
    for d in distances:
        out[f"{d}px"] = masked_mean((epe <= d).astype(jnp.float32), valid)
    return out


def fl_all(estimate, target, valid):
    """KITTI Fl-all outlier fraction: EPE > 3px and EPE > 5% of target
    magnitude, over valid pixels (src/metrics/fl_all.py:31-44)."""
    epe = jnp.linalg.norm(estimate - target, ord=2, axis=-1)
    mag = jnp.linalg.norm(target, ord=2, axis=-1)

    bad = jnp.logical_and(epe > 3.0, epe > 0.05 * mag)
    return masked_mean(bad.astype(jnp.float32), valid)


def average_angular_error(estimate, target, valid=None):
    """Mean angular error (degrees) between spatio-temporal vectors (u,v,1).

    Published definition (Barron et al.): the denominator is
    ``sqrt(|est|²+1)·sqrt(|tgt|²+1)``. The reference's AAE deviates twice
    (src/metrics/aae.py:32-41: NCHW channel indexing addresses the width
    axis, and the denominator drops the per-vector +1 terms under the
    roots); this implementation follows the published formula.

    ``valid`` restricts the mean to valid pixels — required under
    shape-bucketed evaluation, where padded pixels must never contribute
    (the reference applies no mask; pass ``valid=None`` for its exact
    semantics).
    """
    u_est, v_est = estimate[..., 0], estimate[..., 1]
    u_tgt, v_tgt = target[..., 0], target[..., 1]

    n_est = jnp.sqrt(jnp.square(u_est) + jnp.square(v_est) + 1.0)
    n_tgt = jnp.sqrt(jnp.square(u_tgt) + jnp.square(v_tgt) + 1.0)

    cos = (u_est * u_tgt + v_est * v_tgt + 1.0) / (n_est * n_tgt)
    cos = jnp.clip(cos, -1.0, 1.0)

    angles = jnp.arccos(cos)
    if valid is None:
        return jnp.rad2deg(jnp.mean(angles))
    return jnp.rad2deg(masked_mean(angles, valid))


def flow_magnitude(estimate, ord=2, valid=None):
    """Mean per-pixel flow-vector norm (src/metrics/flow.py:34-36);
    ``valid`` restricts the mean to valid pixels (padded-batch safe)."""
    mag = jnp.linalg.norm(estimate, ord=ord, axis=-1)
    if valid is None:
        return jnp.mean(mag)
    return masked_mean(mag, valid)


# -- pytree (gradient / parameter) statistics --------------------------------
#
# The reference walks module.named_parameters() (src/metrics/grad.py:11-47);
# the pytree analog flattens the params/grads tree with path-joined names.

def tree_named_leaves(tree):
    """Flatten a pytree into [(dotted-path-name, leaf)] pairs."""
    flat, _ = jax.tree_util.tree_flatten_with_path(tree)

    def name(path):
        parts = []
        for p in path:
            if hasattr(p, "key"):
                parts.append(str(p.key))
            elif hasattr(p, "idx"):
                parts.append(str(p.idx))
            else:
                parts.append(str(p))
        return ".".join(parts)

    return [(name(path), leaf) for path, leaf in flat]


def fetch_scalars(scalars):
    """One device→host transfer for a whole pytree of scalars, on-device
    or already floats — per-leaf ``float()`` fetches would serialize the
    device pipeline. A plain dict comes back with its keys sorted, an
    OrderedDict as it was."""
    host = jax.device_get(scalars)  # graftlint: disable=host-sync -- the sanctioned batched fetch point for metric scalars
    return jax.tree.map(float, host)


def tree_norm(tree, ord=2):
    """Per-leaf norms + 'total' (norm of the vector of norms)."""
    named = tree_named_leaves(tree)
    norms = {
        name: jnp.linalg.norm(jnp.ravel(leaf), ord=ord) for name, leaf in named
    }
    norms = fetch_scalars(norms)
    # total on host: the per-leaf norms were just fetched, so a jnp
    # round-trip here would pay a second device sync for a tiny vector
    norms["total"] = float(np.linalg.norm(list(norms.values()), ord=ord))
    return norms


def tree_mean(tree):
    """Per-leaf (size, mean) + size-weighted 'total'."""
    named = tree_named_leaves(tree)
    means = fetch_scalars({name: jnp.mean(leaf) for name, leaf in named})
    mean = {name: (int(leaf.size), means[name]) for name, leaf in named}
    total_size = sum(n for n, _ in mean.values()) or 1
    mean["total"] = (
        total_size,
        sum((n / total_size) * m for n, m in mean.values()),
    )
    return mean


def tree_minmax(tree):
    """Per-leaf (min, max) + overall 'total'."""
    named = tree_named_leaves(tree)
    lo = fetch_scalars({name: jnp.min(leaf) for name, leaf in named})
    hi = fetch_scalars({name: jnp.max(leaf) for name, leaf in named})
    mm = {name: (lo[name], hi[name]) for name, _ in named}
    mm["total"] = (
        min(l for l, _ in mm.values()),
        max(h for _, h in mm.values()),
    )
    return mm
