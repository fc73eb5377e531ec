"""The instance norm with its own backward (``models/common/norm.py``)
against the flax form it replaced.

``Norm2d("instance")`` was ``nn.GroupNorm(num_groups=None, group_size=1,
use_scale=False, use_bias=False)``; it is now ``instance_norm``, a
``jax.custom_vjp`` that computes the same numbers in the array the
convolution wrote and hands the backward pass its input, the mean and
``1/sigma`` and nothing else. These cases hold the two forms together,
forward and gradient, in both precisions and under the transformations the
models put round the norm (``vmap``, ``nn.remat``), read what autodiff
keeps, and pin the encoders' parameter trees.
"""

import re

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.ad_checkpoint import print_saved_residuals

from raft_meets_dicl_tpu.models.common.encoders.raft import (
    FeatureEncoderPyramid, FeatureEncoderS3)
from raft_meets_dicl_tpu.models.common.norm import (
    INSTANCE_STATS, Norm2d, instance_norm)

SHAPES = [(2, 6, 11, 64), (1, 48, 88, 96), (12, 25, 45, 128), (3, 7, 13, 32)]
IDS = ["coarsest", "stem", "batch12", "odd"]


def _flax(x, dtype=None):
    return nn.GroupNorm(num_groups=None, group_size=1, epsilon=1e-5,
                        use_scale=False, use_bias=False,
                        dtype=dtype).apply({}, x)


def _inputs(shape, dtype, seed=0):
    kx, kw = jax.random.split(jax.random.PRNGKey(seed))
    # off-centre and of uneven spread, as a convolution's output is
    x = jax.random.normal(kx, shape) * 1.7 + 0.4
    w = jax.random.normal(kw, shape)
    return x.astype(dtype), w.astype(dtype)


def _f32(a):
    return np.asarray(a.astype(jnp.float32))


def _grad(fn, x, w):
    return jax.grad(
        lambda v: jnp.sum((fn(v) * w).astype(jnp.float32)))(x)


@pytest.mark.parametrize("shape", SHAPES, ids=IDS)
def test_float32_forward_and_gradient_are_flax_group_norms(shape):
    x, w = _inputs(shape, jnp.float32)
    np.testing.assert_allclose(instance_norm(x), _flax(x), atol=1e-5, rtol=0)
    np.testing.assert_allclose(_grad(instance_norm, x, w),
                               _grad(_flax, x, w), atol=1e-5, rtol=0)


@pytest.mark.parametrize("shape", SHAPES, ids=IDS)
def test_bfloat16_forward_is_within_an_ulp_and_the_gradient_within_2_to_minus_6(
        shape):
    bf16 = jnp.bfloat16
    x, w = _inputs(shape, bf16)
    ours, theirs = instance_norm(x, 1e-5, bf16), _flax(x, bf16)
    assert ours.dtype == theirs.dtype == bf16
    # one ulp of a bf16 value v is at most 2**-7 * |v|; where x - mean
    # cancels to nothing (|v| under 2**-10) the float32 rounding of the
    # mean, a millionth, is what is left of either form
    ulp = np.maximum(np.abs(_f32(theirs)), 2.0 ** -10) * 2.0 ** -7
    assert np.all(np.abs(_f32(ours) - _f32(theirs)) <= ulp)

    g_ours = _grad(lambda v: instance_norm(v, 1e-5, bf16), x, w)
    g_theirs = _grad(lambda v: _flax(v, bf16), x, w)
    assert g_ours.dtype == g_theirs.dtype == bf16
    scale = np.abs(_f32(g_theirs)).max()
    assert np.abs(_f32(g_ours) - _f32(g_theirs)).max() <= scale * 2.0 ** -6


def test_statistics_are_float32_whatever_the_input():
    # a bf16 map whose mean dwarfs its spread: sums of a thousand values
    # taken in bf16 would lose the spread altogether
    x = (100.0 + 3.0 * jax.random.normal(jax.random.PRNGKey(3),
                                         (1, 32, 32, 8))).astype(jnp.bfloat16)
    ours = _f32(instance_norm(x, 1e-5, jnp.float32))
    np.testing.assert_allclose(ours, _f32(_flax(x, jnp.float32)), atol=1e-4)
    # and both are the float64 answer to what float32 sums of squares of
    # about 1e4 leave of a variance of 9
    exact = np.asarray(x.astype(jnp.float32), np.float64)
    exact = ((exact - exact.mean((1, 2), keepdims=True))
             / np.sqrt(exact.var((1, 2), keepdims=True) + 1e-5))
    np.testing.assert_allclose(ours, exact, atol=3e-2)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
def test_under_vmap(dtype):
    x, w = _inputs((4, 2, 6, 11, 16), dtype)
    tol = 1e-5 if dtype == jnp.float32 else 2.0 ** -5
    ours = jax.vmap(lambda v: instance_norm(v, 1e-5, dtype))
    theirs = jax.vmap(lambda v: _flax(v, dtype))
    np.testing.assert_allclose(_f32(ours(x)), _f32(theirs(x)), atol=tol)
    np.testing.assert_allclose(_f32(_grad(ours, x, w)),
                               _f32(_grad(theirs, x, w)), atol=tol)


@pytest.mark.parametrize("policy", [
    None, jax.checkpoint_policies.nothing_saveable,
    jax.checkpoint_policies.save_only_these_names(INSTANCE_STATS)],
    ids=["default", "nothing", "stats"])
def test_under_nn_remat(policy):
    x, w = _inputs((2, 6, 11, 64), jnp.float32)
    plain = Norm2d("instance")
    rematted = nn.remat(Norm2d, policy=policy)("instance")
    np.testing.assert_allclose(rematted.apply({}, x), plain.apply({}, x),
                               atol=1e-6)
    np.testing.assert_allclose(
        _grad(lambda v: rematted.apply({}, v), x, w),
        _grad(_flax, x, w), atol=1e-5)


@pytest.mark.parametrize("dtype", [None, jnp.bfloat16], ids=["none", "bf16"])
def test_norm2d_returns_the_policys_dtype(dtype):
    # dtype=None is the float32 policy: the result has the input's dtype,
    # as flax's canonicalisation gave it
    for xdt in (jnp.float32, jnp.bfloat16):
        x, _ = _inputs((1, 6, 11, 8), xdt)
        y = Norm2d("instance", dtype=dtype).apply({}, x)
        assert y.dtype == _flax(x, dtype).dtype
        np.testing.assert_allclose(_f32(y), _f32(_flax(x, dtype)), atol=2e-2)


def _kept(capsys, fn, *args):
    """What autodiff keeps of ``fn`` for its backward pass, as
    ``(dtype name, shape, the printed line)``."""
    capsys.readouterr()
    print_saved_residuals(fn, *args)
    kept = []
    for line in capsys.readouterr().out.splitlines():
        m = re.match(r"(\w+)\[([\d,]*)\]", line)
        if m:
            shape = tuple(int(d) for d in m.group(2).split(",") if d)
            kept.append((m.group(1), shape, line))
    return kept


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
def test_the_backward_is_handed_the_input_the_mean_and_the_scale(
        capsys, dtype):
    shape = (2, 48, 88, 64)
    x, w = _inputs(shape, dtype)
    name = {jnp.float32: "f32", jnp.bfloat16: "bf16"}[dtype]

    def loss(norm):
        return lambda v: jnp.sum((jax.nn.relu(norm(v)) * w)
                                 .astype(jnp.float32))

    kept = _kept(capsys, loss(instance_norm), x)
    size = int(np.prod(shape))
    for dt, shp, line in kept:
        if int(np.prod(shp)) < size:
            continue
        # of the input's size: the input itself, the cotangent's weight
        # and the ReLU's mask; never a float32 array under the bf16 policy
        # and never flax's [N,H,W,C,1]
        assert shp == shape, line
        assert dt in (name, "bool"), line
    stats = [(dt, shp) for dt, shp, line in kept if INSTANCE_STATS in line]
    assert stats == 2 * [("f32", (2, 1, 1, 64))]
    # the flax form keeps what this test exists to keep out
    assert any(shp == shape + (1,) and dt == "f32"
               for dt, shp, _ in _kept(capsys, loss(_flax), x))


def _paths(tree):
    return sorted(
        ("/".join(str(getattr(k, "key", k)) for k in path), leaf.shape)
        for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0])


@pytest.mark.parametrize("encoder, convs", [
    (FeatureEncoderS3(output_dim=32), 16),
    (FeatureEncoderPyramid(output_dim=16, levels=3), 31)],
    ids=["s3", "pyramid"])
def test_the_instance_encoders_hold_convolutions_and_nothing_else(
        encoder, convs):
    # no parameter ever lived on the instance branch: the tree is the
    # convolutions' kernels and biases, under the paths checkpoints carry
    variables = jax.eval_shape(
        lambda: encoder.init(jax.random.PRNGKey(0),
                             jnp.zeros((1, 64, 64, 3))))
    assert set(variables) == {"params"}
    paths = _paths(variables["params"])
    assert len(paths) == 2 * convs
    assert all(re.search(r"(^|/)Conv_\d+/(kernel|bias)$", p) for p, _ in paths)
    assert not any("Norm2d" in p or "GroupNorm" in p for p, _ in paths)
