"""The encoders' batch fill under a mesh (``_fill_batch_tile``,
``_drop_fill`` in ``models/common/encoders/raft.py``).

A jitted step over a ``data=4`` mesh traces with the global batch, and the
TPU compiler converts the convolutions of the slice each chip is handed:
six images a chip are the shape that wants the fill, whatever the global
24 says. What these cases hold, on four of the virtual CPU devices with the
backend's name patched: every chip's slice is filled where it lies, the
real images' results and gradients are the bare encoder's, no
image crosses chips on the way, and with no mesh, or a chip's batch outside
4 to 7, the trace is what it was.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from raft_meets_dicl_tpu.models.common.encoders import raft as encoders
from raft_meets_dicl_tpu.models.common.encoders.raft import (
    FeatureEncoderPyramid, FeatureEncoderS3, _drop_fill, _fill_batch_tile)
from raft_meets_dicl_tpu.parallel.mesh import traced_under


@pytest.fixture
def mesh():
    return Mesh(np.array(jax.devices()[:4]), ("data",))


@pytest.fixture
def on_tpu(monkeypatch):
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")


def _sharded(mesh, fn, *args):
    """``fn`` as a step builder would run it: jitted, batch-sharded
    arguments, the mesh published to the trace."""
    data = NamedSharding(mesh, P("data"))
    jitted = traced_under(mesh, jax.jit(fn, in_shardings=(data,) * len(args)))
    return jitted, jitted(*args)


@pytest.mark.parametrize("per_chip, filled", [(1, 1), (3, 3), (4, 8), (6, 8),
                                              (7, 8), (8, 8), (12, 12)])
def test_each_chips_slice_is_filled_not_the_global_batch(on_tpu, mesh,
                                                         per_chip, filled):
    n = 4 * per_chip
    x = jnp.arange(1.0, n + 1)[:, None, None, None] * jnp.ones((n, 2, 2, 3))
    seen = {}

    def fn(x):
        y = _fill_batch_tile(x, "instance", True, True)
        seen["filled"] = y.shape
        return y, _drop_fill(y, x.shape[0])

    _, (y, back) = _sharded(mesh, fn, x)
    assert seen["filled"] == (4 * filled, 2, 2, 3)
    np.testing.assert_array_equal(back, x)
    y = np.asarray(y).reshape(4, filled, 2, 2, 3)
    # chip c still holds its own images, in order, zeros behind them
    np.testing.assert_array_equal(
        y[:, :per_chip], np.asarray(x).reshape(4, per_chip, 2, 2, 3))
    np.testing.assert_array_equal(y[:, per_chip:], 0.0)


def test_a_batch_the_mesh_does_not_divide_is_left_alone(on_tpu, mesh):
    # 6 over four chips: no chip's batch to speak of, so the global rule
    x = jnp.ones((6, 2, 2, 3))
    fn = traced_under(mesh, jax.jit(
        lambda x: _fill_batch_tile(x, "instance", True, True)))
    assert fn(x).shape == (8, 2, 2, 3)


def test_a_live_batch_norm_is_never_fed_zeros_under_a_mesh(on_tpu, mesh):
    x = jnp.ones((24, 2, 2, 3))
    fn = traced_under(mesh, jax.jit(
        lambda x: _fill_batch_tile(x, "batch", True, False)))
    assert fn(x).shape == (24, 2, 2, 3)


def test_off_the_tpu_the_mesh_step_adds_nothing(mesh):
    assert jax.default_backend() == "cpu"
    x = jnp.ones((24, 2, 2, 3))

    def fn(x):
        y = _fill_batch_tile(x, "instance", True, True)
        assert y is x
        return _drop_fill(y, 24)

    jitted, _ = _sharded(mesh, fn, x)
    assert "shard_map" not in str(jax.make_jaxpr(
        traced_under(mesh, fn))(x))


def _loss(net, variables, image, *rest):
    def fn(v, a):
        out = net.apply(v, a, True, True)
        return sum(jnp.sum(jnp.sin(o)) for o in jax.tree_util.tree_leaves(out))
    (loss, out), grads = jax.value_and_grad(
        lambda v, a: (fn(v, a), net.apply(v, a, True, True)),
        (0, 1), has_aux=True)(variables, image)
    return loss, out, grads


@pytest.mark.parametrize("encoder, norm", [
    (FeatureEncoderS3, "batch"), (FeatureEncoderS3, "instance"),
    (FeatureEncoderPyramid, "batch")],
    ids=["s3-frozen-batch", "s3-instance", "pyramid-frozen-batch"])
def test_the_filled_encoder_is_the_bare_one_on_the_real_images(
        monkeypatch, mesh, encoder, norm):
    kwargs = {"levels": 2} if encoder is FeatureEncoderPyramid else {}
    net = encoder(output_dim=16, norm_type=norm, **kwargs)
    image = jax.random.uniform(jax.random.PRNGKey(0), (24, 32, 32, 3))
    variables = net.init(jax.random.PRNGKey(1), image[:1])

    def step():
        # a function of its own a call: jit caches traces by function
        return lambda image: _loss(net, variables, image)

    _, bare = _sharded(mesh, step(), image)         # the CPU's own trace

    seen = []
    real = encoders._fill_batch_tile
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(encoders, "_fill_batch_tile",
                        lambda x, *a: seen.append(real(x, *a)) or seen[-1])
    jitted, filled = _sharded(mesh, step(), image)
    assert seen and all(x.shape[0] == 32 for x in seen)

    # the first n results: a convolution and a frozen or per-sample norm do
    # not mix samples. Equal as far as XLA:CPU computes one sample's
    # convolution alike in a batch of 6 and of 8 (it does not quite: other
    # blocking, float32 sums in another order; the same tolerances as the
    # one-device cases of test_batch_tile.py)
    for a, b in zip(jax.tree_util.tree_leaves(filled[1]),
                    jax.tree_util.tree_leaves(bare[1])):
        assert a.shape == b.shape and a.shape[0] == 24
        np.testing.assert_allclose(a, b, atol=2e-5, rtol=1e-5)
    np.testing.assert_allclose(filled[0], bare[0], rtol=1e-5)
    grads = jax.tree_util.tree_leaves(bare[2])
    scale = max(float(np.abs(g).max()) for g in grads)
    for a, b in zip(jax.tree_util.tree_leaves(filled[2]), grads):
        assert np.all(np.isfinite(a))
        np.testing.assert_allclose(a, b, rtol=0, atol=5e-5 * scale)

    # no image crosses chips for the fill: the lowered text gathers nothing
    # and the compiled one no array with an image's dimensions
    data = NamedSharding(mesh, P("data"))
    lowered = jitted.lower(jax.ShapeDtypeStruct(image.shape, image.dtype,
                                                sharding=data))
    assert "all_gather" not in lowered.as_text()
    compiled = lowered.compile().as_text()
    for line in compiled.splitlines():
        if "all-gather(" in line or "all-gather-start(" in line:
            assert "32,32,3]" not in line.split(" all-gather")[0], line


def test_one_device_steps_trace_what_they_traced(on_tpu):
    # no mesh published: the fill is the plain pad of PR 38 and the drop
    # the plain slice, whatever devices the process has
    x = jnp.ones((6, 2, 2, 3))

    def fn(x):
        return _drop_fill(_fill_batch_tile(x, "instance", True, True), 6)

    text = str(jax.make_jaxpr(fn)(x))
    assert "shard_map" not in text and "pad" in text
