"""``benchmark/reference/dicl.py`` against ``dicl/baseline``, toy size.

The reference imports nothing of the program; this test does, to show
that both state the same mathematics: on seeded random weights (batch
statistics among them) the two agree to float32 rounding in every level's
flow and in the final flow, with and without the raw flows, the
displacement-aware projection and the context networks, and through the
padding a bucket adds. The last cases are the benchmark's comparison
seen from the CPU: the reference with fp8 operands lies far from itself,
the program does not.
"""

import ast
import json
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))

from benchmark.harness import serve_check  # noqa: E402
from benchmark.reference import common as C  # noqa: E402
from benchmark.reference import dicl  # noqa: E402

SIZE = (128, 256)        # level 6 (1/64) is 2x4
CHANNELS = 8             # the cell's 32 cost a CPU four times the time


def _config(raw=True, dap=True, ctx=True, channels=CHANNELS):
    cfg = json.loads((ROOT / "benchmark/configs/dicl-baseline.json")
                     .read_text())["model"]
    cfg["model"]["parameters"]["feature-channels"] = channels
    cfg["model"]["arguments"].update(raw=raw, dap=dap, ctx=ctx)
    return cfg


def _program(cfg):
    from raft_meets_dicl_tpu import models

    return models.load(cfg).model


def _pair(seed, n=2, size=SIZE):
    """Blocky scenes, the second frame the first moved by a few pixels: the
    warp then reads at fractional positions on every level."""
    rng = np.random.default_rng(seed)
    h, w = size
    coarse = rng.random((n, h // 8 + 2, w // 8 + 2, 3), dtype=np.float32)
    big = np.kron(coarse, np.ones((1, 8, 8, 1), np.float32))
    img1 = big[:, 8:8 + h, 8:8 + w]
    img2 = big[:, 5:5 + h, 11:11 + w]
    noise = rng.normal(0.0, 0.02, (2, n, h, w, 3)).astype(np.float32)
    return (np.clip(img1 + noise[0], 0, 1), np.clip(img2 + noise[1], 0, 1))


@pytest.fixture(scope="module")
def weights():
    return C.init(dicl.spec(_config()), 11)


def test_the_reference_imports_nothing_of_the_program():
    for name in ("dicl", "common"):
        tree = ast.parse((ROOT / f"benchmark/reference/{name}.py").read_text())
        names = [a.name for n in ast.walk(tree) if isinstance(n, ast.Import)
                 for a in n.names]
        names += [n.module or "" for n in ast.walk(tree)
                  if isinstance(n, ast.ImportFrom) and n.level == 0]
        assert not [n for n in names if "raft_meets_dicl" in n], names
    # ... and of the references only what they share
    tree = ast.parse((ROOT / "benchmark/reference/dicl.py").read_text())
    local = [a.name for n in ast.walk(tree)
             if isinstance(n, ast.ImportFrom) and n.level for a in n.names]
    assert local == ["common"]


@pytest.mark.parametrize("channels", [CHANNELS, 32])
def test_the_references_parameter_tree_is_the_programs(channels):
    # the comparison of harness/serve.py, at the toy's width and the cell's
    cfg = _config(channels=channels)
    model = _program(cfg)
    img = jnp.zeros((1, 128, 128, 3), jnp.float32)
    want = jax.eval_shape(
        lambda a, b: model.init(jax.random.PRNGKey(0), a, b), img, img)
    want = {k: tuple(v.shape) for k, v in C.flatten(dict(want)).items()}
    spec = dicl.spec(cfg)
    assert want == {k: shape for k, (shape, _) in spec.items()}
    # five levels, each with a MatchingNet, a projection and a context net
    assert {k.split("/")[1] for k in want if k.startswith("params/Flow")} \
        == {f"FlowLevel_{i}" for i in range(5)}


def test_the_seeded_weights_hold_batch_statistics_with_positive_variances(
        weights):
    spec = dicl.spec(_config())
    stats = {k: kind for k, (_, kind) in spec.items()
             if k.startswith("batch_stats/")}
    assert len(stats) == 2 * sum(k.endswith("BatchNorm_0/scale")
                                 for k in spec)
    assert set(stats.values()) == {"bn_mean", "bn_var"}
    assert all(float(weights[k].min()) > 0.5 for k, kind in stats.items()
               if kind == "bn_var")
    assert any(float(jnp.abs(weights[k]).max()) > 0.1
               for k, kind in stats.items() if kind == "bn_mean")


def _warp_gather(f2, flow):
    """The same warp as four gathered taps a position, each zero where it
    lies outside the map, times the mask of a sampled map of ones
    (``grid_sample`` with ``align_corners=True``): what the hat
    contraction is held against."""
    b, h, w, c = f2.shape
    pos = C.grid(b, h, w) + flow
    x, y = pos[..., 0], pos[..., 1]
    x0, y0 = jnp.floor(x), jnp.floor(y)
    flat = f2.reshape(b, h * w, c)
    out, ones = 0.0, 0.0
    for ox in (0, 1):
        for oy in (0, 1):
            ix, iy = x0 + ox, y0 + oy
            weight = (1.0 - jnp.abs(x - ix)) * (1.0 - jnp.abs(y - iy))
            inside = (ix >= 0) & (ix <= w - 1) & (iy >= 0) & (iy <= h - 1)
            idx = (jnp.clip(iy, 0, h - 1) * w
                   + jnp.clip(ix, 0, w - 1)).astype(jnp.int32)
            tap = jnp.take_along_axis(flat, idx.reshape(b, -1, 1), axis=1)
            out = out + tap.reshape(b, h, w, c) * (weight * inside)[..., None]
            ones = ones + weight * inside
    return out * (ones > 1.0 - dicl.EPS_MASK)[..., None]


def test_the_dense_warp_is_the_four_tap_gather():
    rng = np.random.default_rng(3)
    f2 = jnp.asarray(rng.normal(size=(2, 8, 12, 5)), jnp.float32)
    flow = jnp.asarray(rng.uniform(-4, 4, (2, 8, 12, 2)), jnp.float32)
    # whole-pixel moves too: a tap of weight zero outside masks nothing
    flow = flow.at[0, :4].set(jnp.round(flow[0, :4]))
    dense = dicl.warp(C.Params({}), f2, flow)
    taps = _warp_gather(f2, flow)
    assert float(jnp.abs(dense).max()) > 0.5
    assert 0.1 < float((dense == 0).all(axis=-1).mean()) < 0.9
    np.testing.assert_allclose(dense, taps, atol=1e-5)


def _both(cfg, flat, img1, img2):
    n1, n2 = C.normalize_images(img1), C.normalize_images(img2)
    model = _program(cfg)
    with jax.default_matmul_precision("highest"):
        ref = jax.jit(lambda f, a, b: dicl.forward(C.Params(f), cfg, a, b))(
            flat, n1, n2)
        got = jax.jit(lambda v, a, b: model.apply(v, a, b))(
            C.nest(flat), n1, n2)
    return model, ref, got


@pytest.mark.parametrize("raw, dap, ctx", [
    (True, True, True),          # the configuration's arguments
    (False, True, True),
    (True, False, True),
    (True, True, False),
], ids=["as-configured", "no-raw", "no-dap", "no-ctx"])
def test_every_levels_flow_and_the_final_flow_agree_to_float32_rounding(
        weights, raw, dap, ctx):
    cfg = _config(raw, dap, ctx)
    flat = {k: v for k, v in weights.items()
            if (dap or "DisplacementAwareProjection" not in k)
            and (ctx or "CtfContextNet" not in k)}
    assert flat.keys() == dicl.spec(cfg).keys()
    img1, img2 = _pair(5)
    model, ref, got = _both(cfg, flat, img1, img2)
    # finest first, a level's raw flow behind its refined one
    assert len(ref) == len(got) == (10 if raw else 5)
    step = 2 if raw else 1
    for i, (r, g) in enumerate(zip(ref, got, strict=True)):
        level = 2 + i // step
        assert r.shape == g.shape == (2, SIZE[0] >> level, SIZE[1] >> level, 2)
        np.testing.assert_allclose(g, r, atol=2e-5 * max(
            1.0, float(jnp.abs(r).max())), err_msg=f"output {i}")
    # the scenes move: the levels have something to say
    assert float(jnp.abs(ref[0]).mean()) > 0.05
    if raw and ctx:
        assert float(jnp.abs(ref[0] - ref[1]).mean()) > 1e-3
    final = model.get_adapter().wrap_result(got, SIZE).final()
    want = dicl.final_flow(ref)
    assert want.shape == (2, *SIZE, 2)
    np.testing.assert_allclose(final, want, atol=1e-4)


def test_final_only_is_the_same_final_flow_to_the_bit(weights):
    cfg = _config()
    model = _program(cfg)
    img1, img2 = (C.normalize_images(x) for x in _pair(7, n=1))
    variables = C.nest(weights)
    full = jax.jit(lambda v, a, b: model.apply(v, a, b))(variables, img1, img2)
    only = jax.jit(lambda v, a, b: model.apply(v, a, b, final_only=True))(
        variables, img1, img2)
    assert len(full) == 10 and len(only) == 1
    assert np.array_equal(np.asarray(only[0]), np.asarray(full[0]))
    wrap = model.get_adapter().wrap_result
    assert np.array_equal(np.asarray(wrap(only, SIZE).final()),
                          np.asarray(wrap(full, SIZE).final()))


def test_a_bucket_that_pads_and_the_control(weights):
    """What ``harness/serve_check.py`` computes for one request: the frame
    padded bottom and right to its bucket with the value that normalises
    to zero, the flow cropped back. The program under the same padding
    agrees; the reference with fp8 operands does not agree with itself."""
    cfg = _config()
    img1, img2 = _pair(9, n=1, size=(100, 200))
    a, b = jnp.asarray(img1[0]), jnp.asarray(img2[0])

    def reference(quant):
        return jax.jit(lambda f, a, b: serve_check.reference_flow(
            dicl, cfg, f, quant, a, b, SIZE))(weights, a, b)

    with jax.default_matmul_precision("highest"):
        want, low = reference(None), reference(jnp.float8_e4m3fn)
    assert want.shape == (100, 200, 2)

    def padded(x):
        x = C.normalize_images(x[None])
        return jnp.pad(x, ((0, 0), (0, SIZE[0] - 100), (0, SIZE[1] - 200),
                           (0, 0)))

    model = _program(cfg)
    with jax.default_matmul_precision("highest"):
        out = jax.jit(lambda v, a, b: model.apply(v, a, b, final_only=True))(
            C.nest(weights), padded(a), padded(b))
    got = model.get_adapter().wrap_result(out, SIZE).final()[0, :100, :200]
    gap, magnitude = serve_check.relative_epe(np.asarray(got),
                                              np.asarray(want))
    control, _ = serve_check.relative_epe(np.asarray(low), np.asarray(want))
    assert magnitude > 0.2 and gap < 1e-4
    assert control > 0.02 and control > 100 * gap
