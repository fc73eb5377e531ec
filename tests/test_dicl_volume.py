"""``displaced_pair_volume`` against the form it replaced.

Until PR 47 the function stacked the 49 shifts of the padded frame-two
features first and took the validity mask on the stack: a reduction, a
comparison and two multiplications over arrays of the stack's size (1.64
GB at level 2 of a served 512x1024 batch), which cost the chip more than
the MatchingNet they fed. The mask of hypothesis (i, j) at (y, x) is a
function of the padded map at (y + j, x + i) alone, so it is taken there,
on one channel, and its 49 slices select. That old body is kept here as
the reference and nowhere else.

What is held: the volume's values equal the reference's exactly (``==``
on every element, no tolerance: a kept element is the same float, a
masked one is zero in both; the reference wrote a masked element as
``x * 0``, which carries x's sign, the selection writes ``+0``: the two
compare equal and no sum can tell them apart); the gradient to ``feat2``
exactly; the gradient to ``feat1`` to the rounding of a sum of du * dv
terms (the reference reduces its broadcast in another order; with
bfloat16 inputs the gradients are bfloat16 and so is every partial sum).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from raft_meets_dicl_tpu.models.impls.dicl import displaced_pair_volume


def _reference(feat1, feat2, disp_range):
    """The body of ``displaced_pair_volume`` up to PR 46, unchanged."""
    b, h, w, c = feat1.shape
    ru, rv = disp_range
    du, dv = 2 * ru + 1, 2 * rv + 1

    f2p = jnp.pad(feat2, ((0, 0), (rv, rv), (ru, ru), (0, 0)))

    rows = []
    for i in range(du):  # x-displacement di = i - ru
        cols = []
        for j in range(dv):  # y-displacement dj = j - rv
            cols.append(f2p[:, j : j + h, i : i + w, :])
        rows.append(jnp.stack(cols, axis=1))
    shifted = jnp.stack(rows, axis=1)  # (B, du, dv, H, W, C)

    # zero out occluded / out-of-bounds hypotheses
    valid = jax.lax.stop_gradient(shifted).sum(axis=-1, keepdims=True) != 0

    f1 = jnp.broadcast_to(feat1[:, None, None], shifted.shape)
    return jnp.concatenate((f1 * valid, shifted * valid), axis=-1)


_SHAPE = (2, 9, 11, 4)


def _frame_two(kind, rs):
    f2 = rs.randn(*_SHAPE).astype(np.float32)
    if kind == "no-zeros":
        return np.abs(f2) + 0.5
    if kind == "zero-rows-and-columns":  # what a warp leaves at the border
        f2[:, :2] = 0
        f2[:, :, -3:] = 0
        f2[1, 5] = 0
        return f2
    if kind == "channels-cancel":  # non-zero channels that sum to zero
        f2[0, 4, 6] = [1, -1, 0, 0]
        f2[1, 2, 3] = [0.5, 0.25, -0.75, 0]
        return f2
    assert kind == "all-zeros"
    return np.zeros(_SHAPE, np.float32)


@pytest.mark.parametrize("kind", ["no-zeros", "zero-rows-and-columns",
                                  "channels-cancel", "all-zeros"])
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("disp_range", [(3, 3), (1, 2), (0, 0)])
def test_volume_and_gradients_equal_the_stack_first_form(disp_range, dtype,
                                                         kind):
    rs = np.random.RandomState(47)
    feat1 = jnp.asarray(rs.randn(*_SHAPE), dtype)
    feat2 = jnp.asarray(_frame_two(kind, rs), dtype)

    du, dv = 2 * disp_range[0] + 1, 2 * disp_range[1] + 1
    ct = jnp.asarray(rs.randn(_SHAPE[0], du, dv, *_SHAPE[1:3],
                              2 * _SHAPE[3]), jnp.float32)

    def through(fn):
        def loss(f1, f2):
            vol = fn(f1, f2, disp_range)
            return jnp.sum(vol.astype(jnp.float32) * ct), vol

        (_, vol), grads = jax.jit(jax.value_and_grad(
            loss, argnums=(0, 1), has_aux=True))(feat1, feat2)
        return [np.asarray(x.astype(jnp.float32)) for x in (vol, *grads)]

    vol, g1, g2 = through(displaced_pair_volume)
    ref, r1, r2 = through(_reference)

    assert vol.shape == ref.shape == ct.shape
    np.testing.assert_array_equal(vol, ref)
    if kind == "channels-cancel":  # zeroed by both, in both halves
        assert not vol[0, du // 2, dv // 2, 4, 6].any()
    if kind == "all-zeros":
        assert not vol.any()

    np.testing.assert_array_equal(g2, r2)
    # du * dv terms summed in another order: float32 rounding; bfloat16
    # inputs have bfloat16 gradients, every partial sum rounded to 8 bits
    # (a random walk of du * dv steps of 2^-8), so the two orders are held
    # together by their root mean square
    if dtype == "float32":
        np.testing.assert_allclose(g1, r1, rtol=1e-5, atol=1e-5)
    else:
        rms = np.sqrt(np.mean((g1 - r1) ** 2))
        assert rms <= np.sqrt(du * dv) * 2.0 ** -8 * np.sqrt(np.mean(r1 ** 2))
