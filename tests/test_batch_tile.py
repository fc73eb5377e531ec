"""The encoders fill the batch's tile on the TPU (``_fill_batch_tile`` in
``models/common/encoders/raft.py``).

The TPU compiler rewrites every convolution of a batch under 8 into its
space-to-batch form; an encoder of six images is 2.3 times faster with two
images of zeros behind them (PERF.md section 6, PR 38). What these cases
hold: the images of zeros change nothing of the results or the gradients
of the real ones, they appear only where they are free (the TPU, a batch of
4 to 7) and never where a live batch norm would count them, and off the TPU
the program is what it was.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from raft_meets_dicl_tpu.models.common.encoders import raft as encoders
from raft_meets_dicl_tpu.models.common.encoders.raft import (
    FeatureEncoderPyramid, FeatureEncoderS3, _fill_batch_tile)


@pytest.fixture
def on_tpu(monkeypatch):
    """The trace-time question the encoders ask, answered as on the chip
    (the arithmetic still runs on the CPU)."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")


def _image(n, seed=0):
    return jax.random.uniform(jax.random.PRNGKey(seed), (n, 64, 64, 3))


@pytest.mark.parametrize("n, filled", [(1, 1), (2, 2), (3, 3), (4, 8), (6, 8),
                                       (7, 8), (8, 8), (12, 12)])
def test_a_batch_of_4_to_7_is_filled_to_8(on_tpu, n, filled):
    x = _fill_batch_tile(jnp.ones((n, 4, 4, 3)), "instance", True, True)
    assert x.shape == (filled, 4, 4, 3)
    np.testing.assert_array_equal(x[:n], 1.0)
    np.testing.assert_array_equal(x[n:], 0.0)


@pytest.mark.parametrize("norm, train, frozen, filled", [
    ("batch", True, False, 6),      # live statistics: the zeros would count
    ("batch", True, True, 8), ("batch", False, False, 8),
    ("instance", True, False, 8), ("group", True, False, 8),
    ("none", True, False, 8)])
def test_a_live_batch_norm_is_never_fed_zeros(on_tpu, norm, train, frozen,
                                              filled):
    x = _fill_batch_tile(jnp.ones((6, 4, 4, 3)), norm, train, frozen)
    assert x.shape[0] == filled


def test_off_the_tpu_nothing_is_added():
    assert jax.default_backend() == "cpu"
    x = jnp.ones((6, 4, 4, 3))
    assert _fill_batch_tile(x, "instance", True, True) is x


def _loss(encoder, variables, image, train, frozen):
    def fn(v, a):
        out = encoder.apply(v, a, train, frozen)
        return sum(jnp.sum(jnp.sin(o)) for o in jax.tree_util.tree_leaves(out))
    return jax.value_and_grad(fn, (0, 1))(variables, image)


@pytest.mark.parametrize("encoder, norm", [
    (FeatureEncoderS3, "instance"), (FeatureEncoderS3, "batch"),
    (FeatureEncoderPyramid, "instance"), (FeatureEncoderPyramid, "batch")],
    ids=["s3-instance", "s3-frozen-batch", "pyramid-instance",
         "pyramid-frozen-batch"])
def test_results_and_gradients_are_those_of_the_bare_batch(
        monkeypatch, encoder, norm):
    # two levels: an instance norm over the 2x2 map of a third would blow
    # float32 rounding up to the size of the tolerances below
    kwargs = {"levels": 2} if encoder is FeatureEncoderPyramid else {}
    net = encoder(output_dim=16, norm_type=norm, **kwargs)
    image = _image(5)
    variables = net.init(jax.random.PRNGKey(1), image)
    bare = _loss(net, variables, image, True, True)
    out_bare = net.apply(variables, image, True, True)

    seen = []
    real = encoders._fill_batch_tile
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(encoders, "_fill_batch_tile",
                        lambda x, *a: seen.append(real(x, *a)) or seen[-1])
    filled = _loss(net, variables, image, True, True)
    out_filled = net.apply(variables, image, True, True)
    assert seen and all(x.shape[0] == 8 for x in seen)

    for a, b in zip(jax.tree_util.tree_leaves(out_filled),
                    jax.tree_util.tree_leaves(out_bare)):
        assert a.shape == b.shape and a.shape[0] == 5
        np.testing.assert_allclose(a, b, atol=1e-5, rtol=1e-5)
    np.testing.assert_allclose(filled[0], bare[0], rtol=1e-5)
    # float32 sums in another order: a few hundred-thousandths of the
    # largest gradient (a bias in front of an instance norm has none: what
    # it reads is rounding, in either program)
    grads = jax.tree_util.tree_leaves(bare[1])
    scale = max(float(np.abs(g).max()) for g in grads)
    for a, b in zip(jax.tree_util.tree_leaves(filled[1]), grads):
        assert np.all(np.isfinite(a))
        np.testing.assert_allclose(a, b, rtol=0, atol=5e-5 * scale)


def test_a_pair_is_filled_as_one_batch(on_tpu):
    # (img1, img2) of a batch of 2: the pair is a batch of 4, filled to 8,
    # and comes back as two batches of 2
    net = FeatureEncoderS3(output_dim=16, norm_type="instance")
    pair = (_image(2, 0), _image(2, 1))
    variables = net.init(jax.random.PRNGKey(1), pair)
    f1, f2 = net.apply(variables, pair, True, True)
    assert f1.shape == f2.shape == (2, 8, 8, 16)
    alone = net.apply(variables, pair[1], True, True)   # a batch of 2: bare
    np.testing.assert_allclose(f2, alone, atol=1e-5, rtol=1e-5)
