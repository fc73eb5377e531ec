"""RAFT model tests: components, full model, loss, registry."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import raft_meets_dicl_tpu.models as models
from raft_meets_dicl_tpu.models.impls import raft as raft_impl

TINY = {
    "name": "tiny", "id": "tiny",
    "model": {
        "type": "raft/baseline",
        "parameters": {
            "corr-levels": 3, "corr-radius": 2, "corr-channels": 32,
            "context-channels": 16, "recurrent-channels": 16,
        },
        "arguments": {"iterations": 2},
    },
    "loss": {"type": "raft/sequence"},
    "input": None,
}


@pytest.fixture(scope="module")
def tiny_model():
    spec = models.load(TINY)
    rng = jax.random.PRNGKey(0)
    img = jnp.asarray(np.random.RandomState(0).randn(1, 32, 48, 3), jnp.float32)
    variables = spec.model.init(rng, img, img)
    return spec, variables, img


def test_registry_unknown_type():
    with pytest.raises(ValueError, match="unknown model type"):
        models.load_model({"type": "nope"})
    with pytest.raises(ValueError, match="unknown loss type"):
        models.load_loss({"type": "nope"})


def test_raft_forward_shapes(tiny_model):
    spec, variables, img = tiny_model
    out = spec.model.apply(variables, img, img)
    assert len(out) == 2
    assert out[0].shape == (1, 32, 48, 2)


def test_raft_zero_motion_small_flow(tiny_model):
    # identical frames: flow output must be small even untrained? Not
    # guaranteed — but must be finite and well-formed.
    spec, variables, img = tiny_model
    out = spec.model.apply(variables, img, img)
    assert np.isfinite(np.asarray(out[-1])).all()


def test_raft_corr_flow_structure(tiny_model):
    spec, variables, img = tiny_model
    out = spec.model.apply(variables, img, img, corr_flow=True)
    # 3 corr levels (coarse→fine) + final sequence
    assert len(out) == 4
    assert len(out[-1]) == 2
    assert out[0][0].shape == (1, 4, 6, 2)  # 1/8-scale corr-flow readout


def test_raft_flow_init(tiny_model):
    spec, variables, img = tiny_model
    finit = jnp.ones((1, 4, 6, 2))
    out = spec.model.apply(variables, img, img, flow_init=finit)
    assert out[0].shape == (1, 32, 48, 2)


def test_raft_adapter_result(tiny_model):
    spec, variables, img = tiny_model
    out = spec.model.apply(variables, img, img)
    result = spec.model.get_adapter().wrap_result(out, (32, 48))
    assert result.final().shape == (1, 32, 48, 2)
    sliced = result.output(0)
    assert sliced[0].shape == (1, 32, 48, 2)


def test_raft_train_mode_returns_batch_stats(tiny_model):
    spec, variables, img = tiny_model
    out, bs = spec.model.apply(variables, img, img, train=True)
    assert len(out) == 2
    assert bs  # context encoder uses batch norm


def test_raft_freeze_batchnorm(tiny_model):
    spec, variables, img = tiny_model
    spec.model.on_stage(None, freeze_batchnorm=True)
    try:
        out, bs = spec.model.apply(variables, img, img, train=True)
        # frozen: returned stats are the originals (no update)
        orig = variables["batch_stats"]
        same = jax.tree.all(
            jax.tree.map(lambda a, b: bool(jnp.all(a == b)), bs, orig)
        )
        assert same
    finally:
        spec.model.on_stage(None, freeze_batchnorm=False)


def test_sequence_loss_golden():
    loss = models.load_loss({"type": "raft/sequence"})

    flow1 = jnp.ones((1, 4, 4, 2))
    flow2 = jnp.full((1, 4, 4, 2), 2.0)
    target = jnp.zeros((1, 4, 4, 2))
    valid = jnp.ones((1, 4, 4), bool)

    # dist(L1 over channels): flow1 → 2, flow2 → 4; gamma 0.8
    val = float(loss(None, [flow1, flow2], target, valid))
    assert np.isclose(val, 0.8 * 2.0 + 1.0 * 4.0, atol=1e-5)


def test_sequence_loss_valid_masking():
    loss = models.load_loss({"type": "raft/sequence"})

    flow = jnp.ones((1, 2, 2, 2))
    target = jnp.zeros((1, 2, 2, 2))
    valid = jnp.array([[[True, False], [False, False]]])

    val = float(loss(None, [flow], target, valid))
    assert np.isclose(val, 2.0, atol=1e-5)  # only the valid pixel counts


def test_up8_constant_flow():
    # convex combination of a constant flow is the same constant (×8)
    up = raft_impl.Up8Network()
    rng = jax.random.PRNGKey(0)
    hidden = jax.random.normal(rng, (1, 4, 4, 16))
    flow = jnp.full((1, 4, 4, 2), 1.5)
    variables = up.init(rng, hidden, flow)
    out = up.apply(variables, hidden, flow)
    assert out.shape == (1, 32, 32, 2)
    # interior pixels only: border windows include zero padding
    np.testing.assert_allclose(np.asarray(out[:, 8:24, 8:24]), 12.0, atol=1e-5)


def test_softargmax_regression_peak():
    # a cost volume sharply peaked at displacement (dx=2, dy=-1) reads out
    # approximately that displacement
    radius = 3
    k = 2 * radius + 1
    corr = np.zeros((1, 4, 4, k * k), np.float32)
    dx_idx, dy_idx = 2 + radius, -1 + radius
    corr[..., dx_idx * k + dy_idx] = 50.0

    reg = raft_impl.SoftArgMaxFlowRegression(num_levels=1, radius=radius)
    variables = reg.init(jax.random.PRNGKey(0), jnp.asarray(corr))
    (flow,) = reg.apply(variables, jnp.asarray(corr))
    np.testing.assert_allclose(np.asarray(flow[0, 0, 0]), [2.0, -1.0], atol=1e-4)


def test_unfold3x3_center():
    x = jnp.arange(16, dtype=jnp.float32).reshape(1, 4, 4, 1)
    from raft_meets_dicl_tpu.models.common.util import unfold3x3
    w = unfold3x3(x)
    assert w.shape == (1, 4, 4, 9, 1)
    # center of each window is the pixel itself
    np.testing.assert_array_equal(np.asarray(w[..., 4, :]), np.asarray(x))


def test_model_config_roundtrip():
    spec = models.load(TINY)
    cfg = spec.get_config()
    spec2 = models.load(cfg)
    assert spec2.model.corr_levels == 3
    assert cfg["model"]["arguments"]["iterations"] == 2


@pytest.mark.parametrize("ord,include_invalid", [
    (1, False), (2, False), ("absmean", False),
    (1, True), ("absmean", True),
])
def test_sequence_loss_matches_torch_semantics(ord, include_invalid):
    """Torch-golden check of the documented reference semantics
    (src/models/impls/raft.py:616-644): L-ord / absmean distance, valid
    pixels either masked out of the mean or zeroed into it."""
    import torch

    rs = np.random.RandomState(5)
    n, b, h, w = 3, 2, 8, 10
    flows = [rs.randn(b, h, w, 2).astype(np.float32) for _ in range(n)]
    target = rs.randn(b, h, w, 2).astype(np.float32)
    valid = rs.rand(b, h, w) > 0.3
    gamma = 0.8

    # torch reference, NCHW like the original
    t_target = torch.from_numpy(target.transpose(0, 3, 1, 2))
    t_valid = torch.from_numpy(valid)
    expected = 0.0
    for i, f in enumerate(flows):
        t_flow = torch.from_numpy(f.transpose(0, 3, 1, 2))
        weight = gamma ** (n - i - 1)
        if ord == "absmean":
            dist = (t_flow - t_target).abs().mean(dim=-3)
        else:
            dist = torch.linalg.vector_norm(t_flow - t_target, ord=ord, dim=-3)
        if include_invalid:
            dist = dist * t_valid
            expected = expected + weight * dist.mean()
        else:
            expected = expected + weight * dist[t_valid].mean()
    expected = float(expected)

    loss = raft_impl.SequenceLoss()
    got = float(loss(None, [jnp.asarray(f) for f in flows],
                     jnp.asarray(target), jnp.asarray(valid),
                     ord=ord, gamma=gamma, include_invalid=include_invalid))

    assert got == pytest.approx(expected, rel=1e-5)


# -- final-flow protocol: ``final_only`` (see Model.apply) --------------------

# the four impls that share raft.upsample_flows: tiny parameters each
FINAL_FAMILY = {
    "raft/baseline": {"corr-levels": 2, "corr-radius": 2,
                      "corr-channels": 16, "context-channels": 8,
                      "recurrent-channels": 8},
    "raft/fs": {"corr-levels": 2, "corr-radius": 2, "corr-channels": 16,
                "context-channels": 8, "recurrent-channels": 8},
    "raft+dicl/sl": {"corr-radius": 2, "corr-channels": 8,
                     "context-channels": 8, "recurrent-channels": 8,
                     "corr-args": {"mnet_scale": 0.125}},
    "raft+dicl/ml": {"corr-levels": 2, "corr-radius": 2, "corr-channels": 8,
                     "context-channels": 8, "recurrent-channels": 8},
}

# every other registered type: what ``final_only`` must leave alone.
# (parameters, forward arguments, input size)
_DISP = {f"level-{lvl}": [1, 1] for lvl in (2, 3, 4, 5, 6)}
_SL_CTF = {"corr-radius": 2, "corr-channels": 16, "context-channels": 8,
           "recurrent-channels": 8}
_CTF = {"corr-radius": 2, "corr-channels": 8, "context-channels": 8,
        "recurrent-channels": 8, "corr-args": {"mnet_scale": 0.125}}
FINAL_OTHERS = {
    "raft/sl": ({"corr-radius": 2, "corr-channels": 16,
                 "context-channels": 8, "recurrent-channels": 8},
                {"iterations": 2}, (64, 96)),
    "raft+dicl/sl-ca": ({"corr-radius": 2, "corr-channels": 8,
                         "context-channels": 8, "recurrent-channels": 8,
                         "embedding-channels": 8},
                        {"iterations": 2}, (64, 96)),
    "raft/sl-ctf-l2": (_SL_CTF, {"iterations": (2, 1)}, (64, 96)),
    "raft/sl-ctf-l3": (_SL_CTF, {"iterations": (1, 1, 1)}, (64, 96)),
    "raft/sl-ctf-l4": (_SL_CTF, {"iterations": (1, 1, 1, 1)}, (128, 128)),
    "raft+dicl/ctf-l2": (_CTF, {"iterations": (2, 1)}, (64, 96)),
    "raft+dicl/ctf-l3": (_CTF, {"iterations": (1, 1, 1)}, (128, 128)),
    "raft+dicl/ctf-l4": (_CTF, {"iterations": (1, 1, 1, 1)}, (128, 128)),
    "dicl/baseline": ({"displacement-range": _DISP, "feature-channels": 4},
                      {}, (128, 128)),
    "dicl/64to8": ({"displacement-range": {k: v for k, v in _DISP.items()
                                           if k != "level-2"},
                    "feature-channels": 4}, {}, (128, 128)),
    "raft/cl": ({"corr-radius": 2}, {"iterations": 2}, (128, 128)),
    "wip/warp/1": ({"disp-range": [2, 2]}, {}, (128, 128)),
    "wip/warp/2": ({"feature-channels": 8, "disp-range": [[2, 2]] * 5},
                   {}, (128, 128)),
}


def test_final_only_tables_cover_the_registry():
    # a newly registered model has to take the keyword too: list it here
    assert (set(FINAL_FAMILY) | set(FINAL_OTHERS)
            == set(models.config.model_types()))


@pytest.fixture(scope="module", params=list(FINAL_FAMILY))
def family_model(request):
    m = models.config.load_model({"type": request.param,
                                  "parameters": FINAL_FAMILY[request.param]})
    rs = np.random.RandomState(3)
    img1 = jnp.asarray(rs.rand(2, 32, 48, 3), jnp.float32)
    img2 = jnp.asarray(rs.rand(2, 32, 48, 3), jnp.float32)
    v = jax.jit(lambda: m.init(jax.random.PRNGKey(0), img1, img2,
                               iterations=1))()
    hdim = FINAL_FAMILY[request.param]["recurrent-channels"]
    carry = {"flow_init": jnp.asarray(rs.randn(2, 4, 6, 2), jnp.float32),
             "hidden_init": jnp.asarray(rs.randn(2, 4, 6, hdim),
                                        jnp.float32)}
    return m, v, img1, img2, carry


@pytest.mark.parametrize("case", ["upnet", "bilinear", "reentry", "state"])
def test_final_only_returns_the_last_flow_alone(family_model, case):
    """``final_only=True`` gives the one-element ``[flow]`` that the full
    form ends with (Up8 on the last iteration's carry: the same
    arithmetic, batch b instead of iterations * b), and the same
    ``state``."""
    m, v, img1, img2, carry = family_model
    kw = {"iterations": 3}
    if case == "bilinear":
        kw["upnet"] = False
    if case in ("reentry", "state"):
        kw |= carry
    if case == "state":
        kw["return_state"] = True

    # one program for both forms: the shared encoders and loop compile once
    full, fin = jax.jit(lambda v: (
        m.apply(v, img1, img2, **kw),
        m.apply(v, img1, img2, final_only=True, **kw)))(v)

    if case == "state":
        (full, state_full), (fin, state_fin) = full, fin
        assert sorted(state_fin) == ["delta", "flow", "hidden"]
        for k in state_full:
            np.testing.assert_array_equal(np.asarray(state_fin[k]),
                                          np.asarray(state_full[k]))
    assert len(full) == 3 and len(fin) == 1
    assert fin[0].shape == (2, 32, 48, 2)
    np.testing.assert_allclose(np.asarray(fin[0]), np.asarray(full[-1]),
                               rtol=1e-5, atol=1e-5)

    adapter = m.get_adapter()
    np.testing.assert_array_equal(
        np.asarray(adapter.wrap_result(fin, (32, 48)).final()),
        np.asarray(fin[0]))


@pytest.mark.parametrize("ty", list(FINAL_OTHERS))
def test_final_only_is_accepted_by_every_other_impl(ty):
    """Builders pass the switch to whatever model they are given, so every
    registered impl takes it; one with nothing to leave out traces the
    very same program for ``final()`` (raft/sl and raft+dicl/sl-ca wrap
    family modules and honour it)."""
    from raft_meets_dicl_tpu.analysis import hlo

    params, args, (h, w) = FINAL_OTHERS[ty]
    m = models.config.load_model({"type": ty, "parameters": params})
    img = jax.ShapeDtypeStruct((1, h, w, 3), jnp.float32)
    rngs = {"permute": jax.random.PRNGKey(1)}
    v = jax.eval_shape(
        lambda a, b: m.init(jax.random.PRNGKey(0), a, b, **args), img, img)

    def final(**kw):
        def fn(v, a, b):
            out = m.apply(v, a, b, rngs=rngs, **args, **kw)
            return m.get_adapter().wrap_result(out, (h, w)).final()
        return jax.jit(fn).lower(v, img, img)

    full, fin = final(), final(final_only=True)
    assert fin.out_info.shape == full.out_info.shape == (1, h, w, 2)
    if ty in ("raft/sl", "raft+dicl/sl-ca"):
        out = jax.eval_shape(lambda v, a, b: m.apply(
            v, a, b, final_only=True, **args), v, img, img)
        assert len(out) == 1
    else:
        assert (hlo.fingerprint(fin.as_text())
                == hlo.fingerprint(full.as_text()))
