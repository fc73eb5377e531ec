"""``benchmark/reference/ml.py`` against ``raft+dicl/ml``, toy size.

The reference imports nothing of the program; this test does, to show
that both state the same mathematics: with the program's bf16 policy off
the two agree to float32 rounding in every iterate, in the loss and in
the gradient of every leaf, whether the program evaluates the levels'
MatchingNets in one batched call (the path the chip takes) or one after
the other, with per-level weights or shared ones. The last case is the
control of the benchmark's comparison: the reference with fp8 operands
lies further from itself than the program under its bf16 policy does.
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

from benchmark.reference import common as C  # noqa: E402
from benchmark.reference import ml  # noqa: E402
from benchmark.reference import train as reftrain  # noqa: E402

SIZE = (128, 128)
ITERATIONS = 2
LEVELS = 4
RADIUS = 2        # 25 taps a level instead of the cell's 81: a third the time


def _config(mixed_precision, share=False):
    cfg = json.loads((ROOT / "benchmark/configs/raft-dicl-ml.json")
                     .read_text())["model"]
    cfg["model"]["parameters"]["mixed-precision"] = mixed_precision
    cfg["model"]["parameters"]["share-dicl"] = share
    cfg["model"]["parameters"]["corr-radius"] = RADIUS
    cfg["model"]["arguments"]["iterations"] = ITERATIONS
    return cfg


def _pair(seed, n=1):
    rng = np.random.default_rng(seed)
    img1, img2 = (rng.random((n, *SIZE, 3), dtype=np.float32) for _ in "12")
    flow = rng.normal(0.0, 4.0, (n, *SIZE, 2)).astype(np.float32)
    valid = rng.random((n, *SIZE)) > 0.1
    return img1, img2, flow, valid


@pytest.fixture(scope="module")
def weights():
    return {share: C.init(ml.spec(_config(False, share)), 11)
            for share in (False, True)}


def _program(cfg):
    from raft_meets_dicl_tpu import models

    spec = models.load(cfg)
    spec.model.frozen_batchnorm = True
    return spec


class _Program:
    """The program's model and loss behind the reference's interface, so
    that ``reference/train.py`` can drive both through the same steps."""

    def __init__(self, cfg):
        self.spec = _program(cfg)

    def forward(self, P, model_cfg, img1, img2):
        return jnp.stack(self.spec.model.apply(C.nest(P.values), img1, img2,
                                               train=True)[0])

    final_flow = staticmethod(ml.final_flow)

    def loss_sum(self, outputs, target, valid, loss_args):
        loss = self.spec.loss.compute(self.spec.model, list(outputs), target,
                                      valid, **loss_args)
        return loss * jnp.maximum(jnp.sum(valid.astype(jnp.float32)), 1.0)


class _AsOnTheChip:
    """``jax`` as the model's module sees it, with the TPU as its backend:
    off the TPU the per-level nets stay on the loop by default, and the
    batched call (stacked parameters under ``vmap``) is what the chip
    runs. Only the model's choice is steered; the sampler's dispatch asks
    the real ``jax`` and takes its XLA form."""

    def __getattr__(self, name):
        return getattr(jax, name)

    @staticmethod
    def default_backend():
        return "tpu"


@pytest.fixture
def path(request, monkeypatch):
    """The matching's path in the program: ``batched`` or ``loop``."""
    from raft_meets_dicl_tpu.models.impls import raft_dicl_ml

    if request.param == "batched":
        monkeypatch.setattr(raft_dicl_ml, "jax", _AsOnTheChip())
    else:
        monkeypatch.setenv("RMD_DICL_FAST", "0")
    return request.param


def test_the_reference_imports_nothing_of_the_program():
    for name in ("ml", "common"):
        tree = ast.parse((ROOT / f"benchmark/reference/{name}.py").read_text())
        names = [a.name for n in ast.walk(tree) if isinstance(n, ast.Import)
                 for a in n.names]
        names += [n.module or "" for n in ast.walk(tree)
                  if isinstance(n, ast.ImportFrom) and n.level == 0]
        assert not [n for n in names if "raft_meets_dicl" in n], names
    # ... and of the references only what they share
    tree = ast.parse((ROOT / "benchmark/reference/ml.py").read_text())
    local = [a.name for n in ast.walk(tree)
             if isinstance(n, ast.ImportFrom) and n.level for a in n.names]
    assert local == ["common"]


@pytest.mark.parametrize("share", [False, True])
def test_the_references_parameter_tree_is_the_programs(weights, share):
    # the comparison of harness/train.py:_same_tree
    model = _program(_config(False, share)).model
    img = jnp.zeros((1, *SIZE, 3), jnp.float32)
    want = jax.eval_shape(
        lambda a, b: model.init(jax.random.PRNGKey(0), a, b, iterations=1),
        img, img)
    want = {k: tuple(v.shape) for k, v in C.flatten(dict(want)).items()}
    assert want == {k: tuple(v.shape) for k, v in weights[share].items()}
    nets = {k.split("/")[2] for k in want if "/MatchingNet_" in k}
    assert len(nets) == (1 if share else LEVELS)


def _sample_window_gather(f2, coords, radius):
    """The same window as four gathered taps a sample, each zero where it
    lies outside the map (``grid_sample``, ``align_corners=True``): what
    the hat contraction is held against."""
    b, h2, w2, c = f2.shape
    d = jnp.arange(-radius, radius + 1, dtype=jnp.float32)
    x = coords[..., 0][:, None, None] + d[None, :, None, None, None]
    y = coords[..., 1][:, None, None] + d[None, None, :, None, None]
    x, y = jnp.broadcast_arrays(x, y)                    # (B, K, K, H, W)
    x0, y0 = jnp.floor(x), jnp.floor(y)
    flat = f2.reshape(b, h2 * w2, c)
    out = 0.0
    for ox in (0, 1):
        for oy in (0, 1):
            ix, iy = x0 + ox, y0 + oy
            weight = (1.0 - jnp.abs(x - ix)) * (1.0 - jnp.abs(y - iy))
            inside = (ix >= 0) & (ix <= w2 - 1) & (iy >= 0) & (iy <= h2 - 1)
            idx = (jnp.clip(iy, 0, h2 - 1) * w2
                   + jnp.clip(ix, 0, w2 - 1)).astype(jnp.int32)
            tap = jnp.take_along_axis(flat, idx.reshape(b, -1, 1), axis=1)
            out = out + tap.reshape(*idx.shape, c) \
                * (weight * inside)[..., None]
    return out


@pytest.mark.parametrize("level", [0, 2])
def test_window_on_a_coarser_map_is_the_four_tap_gather(level):
    # the centres' grid is 8x12, the map 2^level times coarser
    rng = np.random.default_rng(3)
    f2 = jnp.asarray(rng.normal(size=(2, 8 >> level, 12 >> level, 5)),
                     jnp.float32)
    coords = jnp.asarray(rng.uniform(-6, 16, (2, 8, 12, 2)),
                         jnp.float32) / 2 ** level
    dense = ml.sample_window(C.Params({}), f2, coords, 2)
    taps = _sample_window_gather(f2, coords, 2)
    assert dense.shape == taps.shape == (2, 5, 5, 8, 12, 5)
    assert float(jnp.abs(dense).max()) > 0.5
    np.testing.assert_allclose(dense, taps, atol=1e-5)


@pytest.mark.parametrize("share", [False, True], ids=["own", "shared"])
@pytest.mark.parametrize("path", ["batched", "loop"], indirect=True)
def test_every_iterate_loss_and_gradient_agree_to_float32_rounding(
        weights, path, share):
    cfg = _config(False, share)
    img1, img2, flow, valid = _pair(5)
    n1, n2 = C.normalize_images(img1), C.normalize_images(img2)
    loss_args = dict(cfg["loss"]["arguments"], gamma=0.8)
    flat = weights[share]
    params = {k: v for k, v in flat.items() if k.startswith("params/")}
    fixed = {k: v for k, v in flat.items() if not k.startswith("params/")}

    def numbers(module):
        def f(params):
            out = module.forward(C.Params({**params, **fixed}), cfg, n1, n2)
            return module.loss_sum(out, flow, valid, loss_args), out
        (loss, out), grads = jax.jit(jax.value_and_grad(f, has_aux=True))(
            params)
        return float(loss), out, grads

    with jax.default_matmul_precision("highest"):
        ref_loss, ref_out, ref_grads = numbers(ml)
        prog_loss, prog_out, prog_grads = numbers(_Program(cfg))

    assert ref_out.shape == prog_out.shape == (ITERATIONS, 1, *SIZE, 2)
    assert float(jnp.abs(ref_out).mean()) > 1e-3
    assert float(jnp.abs(ref_out - prog_out).max()) < 1e-3 * max(
        1.0, float(jnp.abs(ref_out).max()))
    assert abs(ref_loss - prog_loss) < 1e-4 * abs(ref_loss)
    norms = {k: float(jnp.linalg.norm(g)) for k, g in ref_grads.items()}
    median = float(np.median(list(norms.values())))
    assert median > 0
    for k, ref in norms.items():
        gap = abs(float(jnp.linalg.norm(prog_grads[k])) - ref)
        assert gap < 1e-2 * max(ref, median), (k, gap, ref)
        assert float(jnp.linalg.norm(prog_grads[k] - ref_grads[k])) \
            < 3e-2 * max(ref, median), k
    # every level's net and projection takes part in the loss
    live = [k for k in norms if "/MatchingNet_" in k and k.endswith("kernel")
            and norms[k] > 1e-3 * median]
    assert len({k.split("/")[2] for k in live}) == (1 if share else LEVELS)


def test_readouts_are_the_programs_soft_argmax(weights):
    cfg = _config(False)
    img1, img2, *_ = _pair(6)
    n1, n2 = C.normalize_images(img1), C.normalize_images(img2)
    model = _program(cfg).model
    with jax.default_matmul_precision("highest"):
        out = model.apply(C.nest(weights[False]), n1, n2, train=True,
                          corr_flow=True)[0]
        _, readouts = jax.jit(
            lambda a, b: ml.iterates(C.Params(weights[False]), cfg, a, b))(
                n1, n2)
    # with corr_flow the levels' readouts come first, coarsest level first
    assert len(out) == LEVELS + 1 and readouts.shape[0] == LEVELS
    for level, ref in zip(out[:LEVELS], readouts[::-1], strict=True):
        prog = jnp.stack(level)
        assert prog.shape == ref.shape
        assert float(jnp.abs(ref).max()) > 1e-2
        assert float(jnp.abs(prog - ref).max()) < 1e-3


def test_fp8_operands_move_a_gap_past_three_times_the_sound_one(weights):
    cfg = _config(False)
    stage = json.loads((ROOT / "benchmark/traffic/train-things.json")
                       .read_text())["stage"]
    batches = [_pair(7), _pair(8)]
    flat = weights[False]
    with jax.default_matmul_precision("highest"):
        reference = reftrain.run(ml, cfg, stage, flat, batches)
        control = reftrain.run(ml, cfg, stage, flat, batches,
                               quant=jnp.float8_e4m3fn)
    # the program as the cell runs it: its bf16 policy on
    program = reftrain.run(_Program(_config(True)), cfg, stage, flat, batches)
    sound, _ = reftrain.compare(program, reference)
    low, _ = reftrain.compare(control, reference)
    print("sound", sound, "control", low)
    assert all(np.isfinite(v) for v in (*sound.values(), *low.values()))
    # at this size the coarsest level is 2x2 samples: instance norm over
    # four of them amplifies the rounding in single leaves' gradients
    # (the worst leaf reads over 1), so the gradient's gap is held at the
    # cell's own size, on the chip, and not here
    assert max(v for k, v in sound.items() if k != "grad_norm_gap") < 0.5, \
        sound
    assert any(low[k] > 3.0 * sound[k] for k in sound), (sound, low)
