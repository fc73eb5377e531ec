"""DICL-hybrid fast path: Pallas window sampler, level-batched matching
nets, unstacked matching forms, and checkpoint param-path stability.

The Pallas kernel tests run in interpreter mode off-TPU, like the existing
windowed-correlation kernel tests (test_ops_parity.py)."""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

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


@pytest.mark.parametrize("radius", [1, 3])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_sampler_kernel_forward_parity(radius, dtype):
    f2, coords = _inputs(seed=1, dtype=dtype)
    ref = np.asarray(sample_window(f2, coords, radius), np.float32)
    out = np.asarray(pk._sw_fwd_interpret(f2, coords, radius))
    atol = 1e-5 if dtype == jnp.float32 else 5e-2
    np.testing.assert_allclose(out, ref, atol=atol)


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


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_sampler_kernel_backward_parity(dtype):
    radius = 2
    f2, coords = _inputs(seed=3, dtype=dtype)
    ref = sample_window(f2.astype(jnp.float32), coords, radius)
    dout = jnp.asarray(np.random.RandomState(4).randn(*ref.shape),
                       jnp.float32)

    df_ref = jax.grad(
        lambda m: (sample_window(m, coords, radius) * dout).sum()
    )(f2.astype(jnp.float32))
    df = np.asarray(pk._sw_bwd_interpret(f2, coords, dout, radius))
    np.testing.assert_allclose(df, np.asarray(df_ref),
                               atol=1e-5 if dtype == jnp.float32 else 5e-2)


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


def test_ml_gradients_match_loop():
    fmap1, fmap2, coords = _ml_inputs(seed=2)
    m = MlCorrelationModule(feature_dim=6, levels=3, radius=1, share=False)
    v = m.init(RNG, fmap1, fmap2, coords)

    def loss(params, fast):
        out = m.apply({**v, "params": params}, fmap1, fmap2, coords,
                      train=True, frozen_bn=True, fast=fast)
        return jnp.abs(out).mean()

    ga = jax.grad(loss)(v["params"], True)
    gb = jax.grad(loss)(v["params"], False)
    for a, b in zip(jax.tree_util.tree_leaves(ga),
                    jax.tree_util.tree_leaves(gb)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-4)


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
