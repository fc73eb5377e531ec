"""DICL-hybrid fast path: Pallas window sampler, level-batched matching
nets, unstacked matching forms, and checkpoint param-path stability.

The Pallas kernel tests run in interpreter mode off-TPU, like the existing
windowed-correlation kernel tests (test_ops_parity.py)."""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from raft_meets_dicl_tpu.models.common.blocks.dicl import ConvBlock, MatchingNet
from raft_meets_dicl_tpu.models.common.corr.common import (
    sample_window,
    sample_window_fast,
    stack_pair,
)
from raft_meets_dicl_tpu.models.common.grid import coordinate_grid
from raft_meets_dicl_tpu.models.impls.raft_dicl_ml import MlCorrelationModule
from raft_meets_dicl_tpu.ops import pallas as pk

RNG = jax.random.PRNGKey(0)


def _inputs(seed=0, b=2, h2=13, w2=17, c=5, h=6, w=7, spread=12.0,
            dtype=jnp.float32):
    """f2 map + window centers including far out-of-bounds positions."""
    rs = np.random.RandomState(seed)
    f2 = jnp.asarray(rs.randn(b, h2, w2, c), dtype)
    # non-integer coords with a spread that pushes whole windows OOB
    coords = jnp.asarray(rs.randn(b, h, w, 2) * spread, jnp.float32)
    return f2, coords


# -- Pallas window sampler vs XLA sample_window ------------------------------

# What the kernel's addressing could get wrong, one geometry each: the map
# rides with x leading and y on the sublanes, a position's patch is a
# dynamic column index and a dynamic-start sublane slice.
_GEOMETRIES = ("fractions", "residuals", "outside", "coarse")


def _geometry(kind, n_j, radius, dtype, c=32):
    """(f2, coords): three rows of ``n_j`` centres over a (9, n_j) map,
    or over one of half the resolution for ``coarse``."""
    rs = np.random.RandomState(_GEOMETRIES.index(kind) * 100 + n_j + radius)
    h, w = 3, n_j
    h2, w2 = (5, n_j // 2) if kind == "coarse" else (9, n_j)
    f2 = jnp.asarray(rs.randn(1, h2, w2, c), dtype)
    yy, xx = np.meshgrid(np.arange(h), np.arange(w), indexing="ij")
    if kind == "fractions":
        # zero and almost-one fractions, all four pairings
        almost = 1.0 - 2.0 ** -10
        cx = xx + np.where(xx % 2 == 0, 0.0, almost)
        cy = 3 * yy + np.where((xx // 2) % 2 == 0, 0.0, almost)
    elif kind == "residuals":
        # patch starts on every residual of 8, along x and along y
        cx = xx - 3 + 0.37
        cy = (xx + 3 * yy) % 9 - 2 + 0.6
        for start in (np.floor(cx) - radius, np.floor(cy) - radius):
            assert set(np.unique(start.astype(int) % 8)) == set(range(8))
    elif kind == "outside":
        # a whole window beyond each border, and one column or row short
        # of it, in every pairing of x and y
        r = radius
        xs = np.array([-(r + 1) - 2.5, -(r + 1) + 0.25, 0.5 * w2,
                       w2 + r - 0.25, w2 + r + 2.5])
        ys = np.array([-(r + 1) - 2.5, -(r + 1) + 0.25, 0.5 * h2,
                       h2 + r - 0.25, h2 + r + 2.5])
        cx = xs[xx % 5]
        cy = ys[(xx // 5 + yy) % 5]
    else:
        # a map coarser than the centres (raft+dicl/ml: h >> lvl)
        cx = 0.5 * xx + rs.randn(h, w)
        cy = 0.5 * yy + rs.randn(h, w)
    coords = jnp.asarray(np.stack((cx, cy), -1)[None], jnp.float32)
    return f2, coords


def _sampler_cases(spread):
    """The kernel-parity cases: ``spread`` (random centres far past the
    borders, small C) as pytest params, then every geometry at C = 32
    with 22 and 88 centres a row, radius 1, 4 and 7, both map dtypes."""
    cases = list(spread)
    for kind in _GEOMETRIES:
        for n_j in (22, 88):
            for radius in (1, 4, 7):
                for dtype in (jnp.float32, jnp.bfloat16):
                    cases.append(pytest.param(
                        kind, n_j, radius, dtype,
                        id=f"{kind}-j{n_j}-r{radius}-"
                           f"{jnp.dtype(dtype).name}"))
    return cases


def _sampler_inputs(kind, n_j, radius, dtype):
    if kind == "spread":
        return _inputs(seed=n_j, dtype=dtype)       # n_j carries the seed
    return _geometry(kind, n_j, radius, dtype)


@pytest.mark.parametrize("kind, n_j, radius, dtype", _sampler_cases(
    pytest.param("spread", 1, radius, dtype,
                 id=f"spread-r{radius}-{jnp.dtype(dtype).name}")
    for radius in (1, 3) for dtype in (jnp.float32, jnp.bfloat16)))
def test_sampler_kernel_forward_parity(kind, n_j, radius, dtype):
    f2, coords = _sampler_inputs(kind, n_j, radius, dtype)
    # the kernel widens the map and lerps in float32: so does the reference
    ref = np.asarray(pk._sw_reference(f2.astype(jnp.float32), coords, radius))
    out = np.asarray(pk._sw_fwd_interpret(f2, coords, radius))
    assert out.dtype == np.float32
    np.testing.assert_allclose(out, ref, atol=1e-5)
    if kind == "outside":
        assert (ref == 0).any() and (ref != 0).any()


def test_sampler_kernel_zero_padding_out_of_bounds():
    # every window fully out of bounds samples exactly zero
    f2, _ = _inputs(seed=2)
    b, h, w = f2.shape[0], 3, 4
    coords = jnp.full((b, h, w, 2), 1000.0)
    out = np.asarray(pk._sw_fwd_interpret(f2, coords, 2))
    assert (out == 0).all()
    # ...and the mixed case matches the XLA masking exactly
    coords = coords.at[:, 0, 0].set(jnp.asarray([2.25, 3.75]))
    ref = np.asarray(sample_window(f2, coords, 2))
    out = np.asarray(pk._sw_fwd_interpret(f2, coords, 2))
    np.testing.assert_allclose(out, ref, atol=1e-5)


@pytest.mark.parametrize("kind, n_j, radius, dtype", _sampler_cases(
    pytest.param("spread", 3, 2, dtype,
                 id=f"spread-r2-{jnp.dtype(dtype).name}")
    for dtype in (jnp.float32, jnp.bfloat16)))
def test_sampler_kernel_backward_parity(kind, n_j, radius, dtype):
    f2, coords = _sampler_inputs(kind, n_j, radius, dtype)
    wide = f2.astype(jnp.float32)
    ref, vjp = jax.vjp(lambda m: pk._sw_reference(m, coords, radius), wide)
    dout = jnp.asarray(np.random.RandomState(4).randn(*ref.shape),
                       jnp.float32)
    (df_ref,) = vjp(dout)
    df = np.asarray(pk._sw_bwd_interpret(f2, coords, dout, radius))
    assert df.shape == f2.shape and df.dtype == np.float32
    np.testing.assert_allclose(df, np.asarray(df_ref), atol=1e-4)


# The benchmark tells the sampler's calls by their result: the forward's
# is the float32 window (b, i, j, k·k, c), the backward's a float32 padded
# map with that window among its operands (benchmark/harness/sw_kernel.py).
# A kernel that returns anything else reads as no sampler at all.
_CTF3_LEVELS = [(48, 88), (24, 44), (12, 22)]


def _pallas_calls(jaxpr):
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            yield eqn
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _pallas_calls(sub)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("h, w", _CTF3_LEVELS)
def test_sampler_forward_call_returns_the_float32_window(h, w, dtype):
    b, c, radius = 6, 32, 4
    f2 = jax.ShapeDtypeStruct((b, h, w, c), dtype)
    coords = jax.ShapeDtypeStruct((b, h, w, 2), jnp.float32)
    traced = jax.make_jaxpr(lambda a, cc: pk._sw_fwd_tpu(a, cc, radius))(
        f2, coords)
    (call,) = _pallas_calls(traced.jaxpr)
    (out,) = call.outvars
    assert out.aval.shape == (b, h, w, 81, c)
    assert out.aval.dtype == jnp.float32


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("h, w", _CTF3_LEVELS)
def test_sampler_backward_call_takes_the_float32_window(h, w, dtype):
    b, c, radius = 6, 32, 4
    f2 = jax.ShapeDtypeStruct((b, h, w, c), dtype)
    coords = jax.ShapeDtypeStruct((b, h, w, 2), jnp.float32)
    dout = jax.ShapeDtypeStruct((b, 9, 9, h, w, c), dtype)
    traced = jax.make_jaxpr(
        lambda a, cc, d: pk._sw_bwd_tpu(a, cc, d, radius))(f2, coords, dout)
    (call,) = _pallas_calls(traced.jaxpr)
    (out,) = call.outvars
    assert out.aval.ndim == 4 and out.aval.dtype == jnp.float32
    assert (out.aval.shape[0], out.aval.shape[3]) == (b, c)
    windows = [v.aval for v in call.invars if v.aval.ndim == 5]
    assert [(a.shape, a.dtype) for a in windows] == [
        ((b, h, w, 81, c), jnp.float32)]


def test_sample_window_fused_dispatch_and_grads():
    """Off-TPU the fused op takes the XLA reference path: identical values,
    identical f2 gradients, and a zero coords gradient (the fused contract:
    callers stop-gradient the lookup centers)."""
    f2, coords = _inputs(seed=5)
    out = pk.sample_window_fused(f2, coords, 3)
    ref = sample_window(f2, coords, 3)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-6)

    g = jnp.asarray(np.random.RandomState(6).randn(*ref.shape), jnp.float32)
    da = jax.grad(lambda m: (pk.sample_window_fused(m, coords, 3) * g).sum())(f2)
    db = jax.grad(lambda m: (sample_window(m, coords, 3) * g).sum())(f2)
    np.testing.assert_allclose(np.asarray(da), np.asarray(db), atol=1e-5)

    dc = jax.grad(
        lambda cc: (pk.sample_window_fused(f2, cc, 3) * g).sum())(coords)
    assert (np.asarray(dc) == 0).all()


def test_sample_window_fast_escape_hatch(monkeypatch):
    f2, coords = _inputs(seed=7)
    monkeypatch.setenv("RMD_DICL_FAST", "0")
    a = sample_window_fast(f2, coords, 2)
    monkeypatch.setenv("RMD_DICL_FAST", "1")
    b = sample_window_fast(f2, coords, 2)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-6)


# -- level-batched MatchingNet vs per-level loop -----------------------------


def _ml_inputs(levels=3, b=2, h=8, w=12, c=6, seed=0):
    rs = np.random.RandomState(seed)
    fmap1 = tuple(jnp.asarray(rs.randn(b, h, w, c), jnp.float32)
                  for _ in range(levels))
    fmap2 = tuple(
        jnp.asarray(rs.randn(b, h // 2 ** i, w // 2 ** i, c), jnp.float32)
        for i in range(levels))
    coords = coordinate_grid(b, h, w) + jnp.asarray(
        rs.randn(b, h, w, 2), jnp.float32)
    return fmap1, fmap2, coords


@pytest.mark.parametrize("share", [True, False])
@pytest.mark.parametrize("dtype", [None, jnp.bfloat16])
def test_ml_level_batched_matches_loop(share, dtype):
    fmap1, fmap2, coords = _ml_inputs()
    m = MlCorrelationModule(feature_dim=6, levels=3, radius=2, share=share,
                            dtype=dtype)
    v = m.init(RNG, fmap1, fmap2, coords)

    loop = m.apply(v, fmap1, fmap2, coords, fast=False)
    fast = m.apply(v, fmap1, fmap2, coords, fast=True)
    atol = 1e-5 if dtype is None else 5e-2
    np.testing.assert_allclose(np.asarray(fast), np.asarray(loop), atol=atol)

    # the standard training config (train with frozen batch norm)
    loop = m.apply(v, fmap1, fmap2, coords, train=True, frozen_bn=True,
                   fast=False)
    fast = m.apply(v, fmap1, fmap2, coords, train=True, frozen_bn=True,
                   fast=True)
    np.testing.assert_allclose(np.asarray(fast), np.asarray(loop), atol=atol)

    # mask_costs rides both paths identically
    loop = m.apply(v, fmap1, fmap2, coords, mask_costs=(4,), fast=False)
    fast = m.apply(v, fmap1, fmap2, coords, mask_costs=(4,), fast=True)
    np.testing.assert_allclose(np.asarray(fast), np.asarray(loop), atol=atol)
    assert (np.asarray(fast)[..., 25:50] == 0).all()


def test_ml_live_bn_falls_back_to_sequential_loop():
    """Live batch norm must keep the reference loop's sequential stat
    updates: the fast path defers, stats mutate, outputs match fast=False."""
    fmap1, fmap2, coords = _ml_inputs(seed=1)
    m = MlCorrelationModule(feature_dim=6, levels=2, radius=1, share=True)
    v = m.init(RNG, fmap1[:2], fmap2[:2], coords)

    out_a, bs_a = m.apply(v, fmap1[:2], fmap2[:2], coords, train=True,
                          frozen_bn=False, fast=True,
                          mutable=["batch_stats"])
    out_b, bs_b = m.apply(v, fmap1[:2], fmap2[:2], coords, train=True,
                          frozen_bn=False, fast=False,
                          mutable=["batch_stats"])
    np.testing.assert_allclose(np.asarray(out_a), np.asarray(out_b),
                               atol=1e-5)
    for a, b in zip(jax.tree_util.tree_leaves(bs_a),
                    jax.tree_util.tree_leaves(bs_b)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-6)


def _ml_stacked(m, v, fmap1, fmap2, coords):
    """The module's costs before DAP by the reference form: every level's
    MatchingNet on the stacked (B, du, dv, H, W, 2C) volume."""
    net = MatchingNet(norm_type=m.norm_type, dtype=m.dtype)
    out = []
    for i, (f1, f2) in enumerate(zip(fmap1, fmap2)):
        window = sample_window(f2, coords / 2 ** i, m.radius)
        if m.dtype is not None:
            f1, window = f1.astype(m.dtype), window.astype(m.dtype)
        name = "MatchingNet_0" if m.share else f"MatchingNet_{i}"
        vs = {col: tree[name] for col, tree in v.items() if name in tree}
        cost = net.apply(vs, stack_pair(f1, window), True, True)
        out.append(cost.reshape(*cost.shape[:3], -1))
    return jnp.concatenate(out, axis=-1)


def _assert_trees_close(got, want, tol):
    """Every leaf within ``tol`` of the reference leaf's largest entry (or
    of 1, for a leaf that is small throughout)."""
    got, want = jax.tree_util.tree_leaves(got), jax.tree_util.tree_leaves(want)
    assert len(got) == len(want)
    for a, b in zip(got, want):
        a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
        np.testing.assert_allclose(a, b, rtol=0,
                                   atol=tol * max(1.0, np.abs(b).max()))


@pytest.mark.parametrize("dtype", [None, jnp.bfloat16])
@pytest.mark.parametrize("share", [False, True])
def test_ml_gradients_match_loop(share, dtype):
    """Values and gradients (every parameter, both feature pyramids) of
    the level-batched pair form, the per-level pair form and the stacked
    reference form agree."""
    fmap1, fmap2, coords = _ml_inputs(seed=2)
    m = MlCorrelationModule(feature_dim=6, levels=3, radius=1, share=share,
                            dtype=dtype)
    v = m.init(RNG, fmap1, fmap2, coords)
    g = jnp.asarray(np.random.RandomState(8).randn(2, 8, 12, 27), jnp.float32)

    def loss(params, fmap1, fmap2, form):
        vs = {**v, "params": params}
        if form == "stacked":
            out = _ml_stacked(m, vs, fmap1, fmap2, coords)
        else:
            out = m.apply(vs, fmap1, fmap2, coords, dap=False, train=True,
                          frozen_bn=True, fast=form == "fast")
        return (out * g).mean(), out

    grad = jax.value_and_grad(loss, (0, 1, 2), has_aux=True)
    fast = grad(v["params"], fmap1, fmap2, "fast")
    loop = grad(v["params"], fmap1, fmap2, "loop")
    stacked = grad(v["params"], fmap1, fmap2, "stacked")

    vtol, gtol = (1e-5, 1e-4) if dtype is None else (5e-2, 5e-2)
    for got, want in ((fast, loop), (fast, stacked), (loop, stacked)):
        _assert_trees_close(got[0], want[0], vtol)
        _assert_trees_close(got[1], want[1], gtol)
    # the stacked form has no gradient the pair forms lack
    assert all(np.abs(np.asarray(a, np.float32)).max() > 0
               for a in jax.tree_util.tree_leaves(stacked[1][1:]))


# -- checkpoint param-path stability -----------------------------------------


@pytest.mark.parametrize("share", [True, False])
def test_ml_checkpoint_param_paths_stable(share):
    """The fast path must not change the checkpoint tree: per-level
    ``MatchingNet_i`` subtrees (one for share=True), unstacked shapes, and
    identical trees whichever way RMD_DICL_FAST is set at init."""
    import flax

    from raft_meets_dicl_tpu.models import config as mconfig

    cfg = {"type": "raft+dicl/ml",
           "parameters": {"corr-levels": 3, "corr-radius": 1,
                          "corr-channels": 4, "context-channels": 8,
                          "recurrent-channels": 8, "share-dicl": share}}
    img = jnp.zeros((1, 64, 64, 3))

    trees = {}
    for env in ("0", "1"):
        os.environ["RMD_DICL_FAST"] = env
        try:
            m = mconfig.load_model(cfg)
            v = jax.eval_shape(
                lambda: m.init(RNG, img, img, iterations=1))
            trees[env] = jax.tree_util.tree_map(
                lambda x: (x.shape, str(x.dtype)), v)
        finally:
            os.environ["RMD_DICL_FAST"] = "1"
    assert trees["0"] == trees["1"]

    flat = flax.traverse_util.flatten_dict(trees["1"]["params"])
    mnets = {k[1] for k in flat if k[0] == "MlCorrelationModule_0"
             and k[1].startswith("MatchingNet")}
    assert mnets == ({"MatchingNet_0"} if share else
                     {"MatchingNet_0", "MatchingNet_1", "MatchingNet_2"})
    # per-level parameters stay unstacked (no leading level axis)
    kern = flat[("MlCorrelationModule_0", "MatchingNet_0", "ConvBlock_0",
                 "Conv_0", "kernel")]
    assert len(kern[0]) == 4  # (kh, kw, cin, cout)


# -- unstacked matching forms (parity vs stack_pair reference) ---------------


def _pair_inputs(dtype, b=2, h=6, w=10, c=5, r=2, seed=9):
    rs = np.random.RandomState(seed)
    k = 2 * r + 1
    f1 = jnp.asarray(rs.randn(b, h, w, c), dtype)
    window = jnp.asarray(rs.randn(b, k, k, h, w, c), dtype)
    return f1, window


@pytest.mark.parametrize("train", [False, True], ids=["eval", "frozen"])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_matching_net_pair_matches_stacked(dtype, train):
    """``MatchingNet((f1, window))`` against ``MatchingNet(stack_pair(f1,
    window))``: the cost and its gradients with respect to f1, the window
    and every parameter."""
    f1, window = _pair_inputs(dtype)
    m = MatchingNet(dtype=None if dtype == jnp.float32 else dtype)
    v = m.init(RNG, stack_pair(f1, window))
    g = jnp.asarray(np.random.RandomState(10).randn(2, 6, 10, 5, 5),
                    jnp.float32)

    def loss(params, f1, window, pair):
        mvol = (f1, window) if pair else stack_pair(f1, window)
        cost = m.apply({**v, "params": params}, mvol, train, train)
        assert cost.dtype == jnp.float32
        return (cost * g).mean(), cost

    grad = jax.value_and_grad(loss, (0, 1, 2), has_aux=True)
    pair = grad(v["params"], f1, window, True)
    stacked = grad(v["params"], f1, window, False)
    vtol, gtol = (1e-5, 1e-4) if dtype == jnp.float32 else (5e-2, 5e-2)
    _assert_trees_close(pair[0], stacked[0], vtol)
    _assert_trees_close(pair[1], stacked[1], gtol)
    assert all(a.dtype == b.dtype for a, b in zip(
        jax.tree_util.tree_leaves(pair[1]),
        jax.tree_util.tree_leaves(stacked[1])))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_pair_form_selects_the_shared_half_bit_for_bit(dtype):
    """The shared half reaches every one of its items unrounded: with a
    per-item half of zeros the pair form is the shared half's own block,
    to the bit."""
    f1, _ = _pair_inputs(dtype)
    b, n = f1.shape[0], 25
    items = jnp.zeros((b * n, *f1.shape[1:3], 3), dtype)
    block = ConvBlock(8, dtype=None if dtype == jnp.float32 else dtype)
    v = block.init(RNG, (f1, items))

    alone = {"params": {**v["params"], "Conv_0": {
        "kernel": v["params"]["Conv_0"]["kernel"][:, :, :f1.shape[-1]]}},
        "batch_stats": v["batch_stats"]}
    want = np.asarray(block.apply(alone, f1).astype(jnp.float32))
    assert (want > 0).any()
    got = np.asarray(block.apply(v, (f1, items)).astype(jnp.float32))
    np.testing.assert_array_equal(
        got.reshape(b, n, *want.shape[1:]),
        np.broadcast_to(want[:, None], (b, n, *want.shape[1:])))


def test_matching_net_1x1_unstacked_matches_stacked():
    from raft_meets_dicl_tpu.models.common.corr.dicl_1x1 import MatchingNet1x1

    rs = np.random.RandomState(3)
    b, h, w, c, r = 2, 6, 9, 5, 2
    f1 = jnp.asarray(rs.randn(b, h, w, c), jnp.float32)
    f2 = jnp.asarray(rs.randn(b, h, w, c), jnp.float32)
    coords = coordinate_grid(b, h, w)
    window = sample_window(f2, coords, r)
    mvol = stack_pair(f1, window)

    m = MatchingNet1x1()
    v = m.init(RNG, mvol)
    stacked = m.apply(v, mvol)
    unstacked = m.apply(v, (f1, window))
    np.testing.assert_allclose(np.asarray(unstacked), np.asarray(stacked),
                               atol=1e-5)


def test_pair_embedding_unstacked_matches_stacked():
    from raft_meets_dicl_tpu.models.common.corr.dicl_emb import PairEmbedding
    from raft_meets_dicl_tpu.ops.corr import window_delta

    rs = np.random.RandomState(4)
    b, h, w, c, r = 2, 6, 9, 5, 1
    k = 2 * r + 1
    f1 = jnp.asarray(rs.randn(b, h, w, c), jnp.float32)
    window = jnp.asarray(rs.randn(b, k, k, h, w, c), jnp.float32)
    delta = jnp.broadcast_to(
        window_delta(r, jnp.float32)[None, :, :, None, None, :],
        (b, k, k, h, w, 2))
    mvol = jnp.concatenate((stack_pair(f1, window), delta), axis=-1)
    per_item = jnp.concatenate((window, delta), axis=-1)

    m = PairEmbedding(16)
    v = m.init(RNG, mvol)
    stacked = m.apply(v, mvol)
    unstacked = m.apply(v, (f1, per_item))
    np.testing.assert_allclose(np.asarray(unstacked), np.asarray(stacked),
                               atol=1e-5)
    # checkpoint tree identical to the plain nn.Conv stack
    assert set(v["params"].keys()) == {"Conv_0", "Conv_1", "Conv_2"}
    assert set(v["params"]["Conv_0"].keys()) == {"kernel", "bias"}


# -- telemetry counter -------------------------------------------------------


def test_matching_volume_bytes_counter():
    from raft_meets_dicl_tpu import compile as programs
    from raft_meets_dicl_tpu import telemetry

    sink = telemetry.create()  # memory-only
    telemetry.activate(sink)
    try:
        fmap1, fmap2, coords = _ml_inputs(levels=2)
        m = MlCorrelationModule(feature_dim=6, levels=2, radius=1,
                                share=True, dtype=jnp.bfloat16)
        v = m.init(RNG, fmap1[:2], fmap2[:2], coords)
        # counts belong to the program whose trace notes them
        step = programs.register_step("probe", jax.jit(
            lambda v: m.apply(v, fmap1[:2], fmap2[:2], coords)))
        step(v)
        sink.step_event(0)
        steps = [e for e in sink.events if e["kind"] == "step"]
        counters = steps[-1].get("counters", {})
        # bf16 matching volumes: 2 levels x (f1 + window) in 2-byte elems
        b, h, w, c = fmap1[0].shape
        k = 3
        expect = 2 * 2 * (b * h * w * c + b * k * k * h * w * c)
        assert counters.get("matching_volume_bytes") == expect
    finally:
        telemetry.deactivate()
