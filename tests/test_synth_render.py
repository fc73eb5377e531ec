"""The synthetic renderer against its plain form (PR 40).

``data/synth.py:_frame`` decides each pixel's owner first and evaluates the
texture once a pixel with the owner's parameters. The form it replaced, kept
here as the reference, textured the background and every layer over the
whole frame and kept the topmost layer's pixel. Both do the same arithmetic
on the same operands for the pixel that is kept, so ``render_sequence`` has
to agree with the reference bit for bit; one pinned digest holds the scenes
themselves in place.
"""

import functools
import hashlib
from unittest import mock

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from raft_meets_dicl_tpu.data import synth

pytestmark = pytest.mark.aug

# sha256 over img1, img2, flow, valid of ``Synth(seed=1, shape=(96, 128))[0]``,
# taken from the tree before PR 40 changed the renderer
SCENE_DIGEST = "9f58426d25583050fdcf29fdf0f115d5b1f8efd4aa4fcf25fee5ab6ab6665f4b"


def _ref_texture(p, p0y, p0x):
    args = (2.0 * jnp.pi * (p["freq"][:, 0, None, None] * p0y[None]
                            + p["freq"][:, 1, None, None] * p0x[None])
            + p["phase"][:, None, None])
    tex = p["color"][:, None, None] + p["amp"][:, None, None] * jnp.sin(args)
    return jnp.clip(jnp.moveaxis(tex, 0, -1), 0.0, 1.0)


def _ref_frame(bg, lay, t, h, w, layers):
    """``_frame`` as it was: one texture a layer over the whole frame."""
    py, px = jnp.meshgrid(jnp.arange(h, dtype=jnp.float32),
                          jnp.arange(w, dtype=jnp.float32), indexing="ij")

    bg0y = py - t * bg["vel"][0]
    bg0x = px - t * bg["vel"][1]
    img = _ref_texture(bg, bg0y, bg0x)
    own = jnp.zeros((h, w), jnp.int32)

    for i in range(layers):
        p = jax.tree.map(lambda x: x[i], lay)
        c_t, lin = synth._pose(p, float(t))
        det = lin[0, 0] * lin[1, 1] - lin[0, 1] * lin[1, 0]
        i00, i01 = lin[1, 1] / det, -lin[0, 1] / det
        i10, i11 = -lin[1, 0] / det, lin[0, 0] / det
        dy, dx = py - c_t[0], px - c_t[1]
        p0y = p["c0"][0] + i00 * dy + i01 * dx
        p0x = p["c0"][1] + i10 * dy + i11 * dx
        mask = synth._layer_mask(p, p0y, p0x)
        img = jnp.where(mask[..., None], _ref_texture(p, p0y, p0x), img)
        own = jnp.where(mask, i + 1, own)

    return own, img


@functools.partial(jax.jit, static_argnames=("shape", "frames", "layers"))
def _ref_sequence(key, shape, frames, layers, motion):
    """The reference's sequence, and of the scene's layers (drawn again as
    ``render_sequence`` draws them): whether one lies over another in frame
    0, and whether a centre has left the frame by the last one."""
    with mock.patch.object(synth, "_frame", _ref_frame):
        out = synth.render_sequence.__wrapped__(
            key, shape, frames=frames, layers=layers, motion=motion)

    h, w = shape
    py, px = jnp.meshgrid(jnp.arange(h, dtype=jnp.float32),
                          jnp.arange(w, dtype=jnp.float32), indexing="ij")
    lay = synth._draw_layers(jax.random.split(key)[1], h, w, layers, motion,
                             0.05, 0.05)
    masks = jax.vmap(lambda p: synth._layer_mask(p, py, px))(lay)
    covered = jnp.any(jnp.sum(masks, axis=0) > 1)
    end = lay["c0"] + (frames - 1) * lay["vel"]
    left = jnp.any((end < 0) | (end > jnp.array([h - 1.0, w - 1.0])))
    return out, covered, left


# (shape, layers, frames): a compile each, and its reference's
CONFIGS = [
    ((48, 64), 1, 2),
    ((48, 64), 4, 4),
    ((96, 128), 4, 2),
    ((96, 128), 1, 4),
    ((104, 200), 6, 2),
    ((104, 200), 4, 2),
]
# (key, motion): the default motion, and one that carries layers out of
# the frame within a sequence
DRAWS = [(1, 8.0), (2, 8.0), (3, 40.0)]


def _key(seed):
    return jax.random.fold_in(jax.random.PRNGKey(seed), 3)


@pytest.mark.parametrize("seed,motion", DRAWS)
@pytest.mark.parametrize("shape,layers,frames", CONFIGS)
def test_render_equals_the_plain_form_to_the_bit(shape, layers, frames, seed,
                                                 motion):
    new = synth.render_sequence(_key(seed), shape, frames=frames,
                                layers=layers, motion=motion)
    ref, _, _ = _ref_sequence(_key(seed), shape, frames, layers, motion)

    assert new[0].shape == (frames,) + shape + (3,)
    for name, a, b in zip(("images", "flows", "valids"), new, ref):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b),
                                      err_msg=name)


def test_cases_hold_covered_layers_and_layers_that_leave():
    """The cases above are worth their name only if, among them, a layer
    lies over another and a layer's centre leaves the frame."""
    flags = np.array([[bool(f) for f in _ref_sequence(
        _key(seed), shape, frames, layers, motion)[1:]]
        for shape, layers, frames in CONFIGS for seed, motion in DRAWS])
    covered, left = flags.sum(axis=0)
    assert covered >= 3 and left >= 3, (covered, left)


def test_scene_digest_is_the_pinned_one():
    sample = synth.Synth(seed=1, shape=(96, 128))[0]
    digest = hashlib.sha256()
    for a in sample[:4]:
        digest.update(np.ascontiguousarray(a).tobytes())
    assert digest.hexdigest() == SCENE_DIGEST
