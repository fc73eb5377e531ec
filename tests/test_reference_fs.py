"""``benchmark/reference/fs.py`` against ``raft/fs``, toy size.

The reference imports nothing of the program and builds no correlation
volume on any level; this test imports both, to show that they state the
same mathematics: with the program's bf16 policy off the two agree to
float32 rounding in every iterate, in the loss and in the gradient of
every leaf, whichever way the program's per-level dispatch falls (every
level a materialised volume, the hybrids (level 0 alone on the windowed
form is the split the cell ``fs-train-1080p`` runs), every level
windowed: steered by the existing ``RMD_FS_VOLUME_GIB`` budget alone) and
whichever form computes the windowed levels (the XLA composition a CPU
takes, or the Mosaic kernels through the Pallas interpreter). The last
case is the control of the benchmark's comparison: the reference with
fp8 operands lies further from itself than the program under its bf16
policy does.
"""

import ast
import functools
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
from benchmark.reference import fs  # noqa: E402
from benchmark.reference import train as reftrain  # noqa: E402

SIZE = (64, 96)          # an 8x12 grid: the coarsest of four levels is 1x1
GRID = (1, 8, 12)
ITERATIONS = 2
LEVELS = 4


def _config(mixed_precision):
    cfg = json.loads((ROOT / "benchmark/configs/raft-fs.json")
                     .read_text())["model"]
    cfg["model"]["parameters"]["mixed-precision"] = mixed_precision
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
    return C.init(fs.spec(_config(False)), 11)


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

    final_flow = staticmethod(fs.final_flow)

    def loss_sum(self, outputs, target, valid, loss_args):
        loss = self.spec.loss.compute(self.spec.model, list(outputs), target,
                                      valid, **loss_args)
        return loss * jnp.maximum(jnp.sum(valid.astype(jnp.float32)), 1.0)


def _budget_for(n_windowed, itemsize=4):
    """A value of ``RMD_FS_VOLUME_GIB`` under which the dispatch keeps
    ``n_windowed`` levels on the windowed form at the toy grid: halfway
    between the budgets that admit one level more and one level fewer."""
    from raft_meets_dicl_tpu.models.impls.raft_fs import volume_level_split

    b, h, w = GRID
    volume = [b * h * w * (h >> l) * (w >> l) * itemsize
              for l in range(LEVELS)]
    need = [2 * sum(volume[l:]) for l in range(LEVELS)] + [0]
    gib = (need[n_windowed] + need[max(n_windowed - 1, 0)]) / 2 / 2 ** 30
    if n_windowed == 0:
        gib = 2 * need[0] / 2 ** 30
    assert volume_level_split(GRID, LEVELS, itemsize, gib) == n_windowed
    return gib


@pytest.fixture
def dispatch(request, monkeypatch):
    """``(n_windowed, form)``: the budget that gives the split, and for
    ``band`` the Mosaic kernels through the interpreter in place of the
    XLA composition a CPU takes."""
    from raft_meets_dicl_tpu.ops import pallas

    n_windowed, form = request.param
    monkeypatch.setenv("RMD_FS_VOLUME_GIB", repr(_budget_for(n_windowed)))
    if form == "band":
        monkeypatch.setattr(pallas, "_wcp_takes_kernel", lambda *a: True)
        monkeypatch.setattr(pallas, "_wcp_fwd_tpu", functools.partial(
            pallas._wcp_fwd_tpu, interpret=True))
        monkeypatch.setattr(pallas, "_wcp_bwd_tpu", functools.partial(
            pallas._wcp_bwd_tpu, interpret=True))
    return request.param


def test_the_reference_imports_nothing_of_the_program():
    for name in ("fs", "common"):
        tree = ast.parse((ROOT / f"benchmark/reference/{name}.py").read_text())
        names = [a.name for n in ast.walk(tree) if isinstance(n, ast.Import)
                 for a in n.names]
        names += [n.module or "" for n in ast.walk(tree)
                  if isinstance(n, ast.ImportFrom) and n.level == 0]
        assert not [n for n in names if "raft_meets_dicl" in n], names
    # ... and of the references only what they share
    tree = ast.parse((ROOT / "benchmark/reference/fs.py").read_text())
    local = [a.name for n in ast.walk(tree)
             if isinstance(n, ast.ImportFrom) and n.level for a in n.names]
    assert local == ["common"]


def test_the_reference_builds_no_volume(weights):
    # no value of the forward pass pairs the grid's positions with a
    # level's map positions, as axes or flattened, on any level whose map
    # holds more than one sample
    cfg = _config(False)
    img = jnp.zeros((1, *SIZE, 3), jnp.float32)
    jaxpr = jax.make_jaxpr(
        lambda a, b: fs.forward(C.Params(weights), cfg, a, b))(img, img)
    _, h, w = GRID
    volumes = [(h, w, h >> l, w >> l) for l in range(LEVELS - 1)]
    volumes += [(h * w, (h >> l) * (w >> l)) for l in range(LEVELS - 1)]

    def shapes(jaxpr):
        for eqn in jaxpr.eqns:
            yield from (tuple(v.aval.shape) for v in eqn.outvars)
            for sub in jax.core.jaxprs_in_params(eqn.params):
                yield from shapes(sub)

    seen = set(shapes(jaxpr.jaxpr))
    assert len(seen) > 50
    for shape in seen:
        for volume in volumes:
            n = len(volume)
            assert not any(shape[i:i + n] == volume
                           for i in range(len(shape) - n + 1)), shape


def test_the_references_parameter_tree_is_the_programs(weights):
    # the comparison of harness/train.py:_same_tree; the program's
    # encoders are rematerialised under the plain encoders' names
    model = _program(_config(False)).model
    img = jnp.zeros((1, *SIZE, 3), jnp.float32)
    want = jax.eval_shape(
        lambda a, b: model.init(jax.random.PRNGKey(0), a, b, iterations=1),
        img, img)
    want = {k: tuple(v.shape) for k, v in C.flatten(dict(want)).items()}
    assert want == {k: tuple(v.shape) for k, v in weights.items()}
    assert {k.split("/")[1] for k in want if k.startswith("params/")} == {
        "FeatureEncoderS3_0", "FeatureEncoderS3_1",
        "ScanCheckpoint_FsStep_0", "Up8Network_0"}


def _kept_by_the_backward(capsys, weights):
    """What autodiff keeps of a ``raft/fs`` step under the bf16 policy, as
    ``(dtype[shape], the printed line)``."""
    from jax.ad_checkpoint import print_saved_residuals

    spec = _program(_config(True))
    img = jnp.zeros((1, *SIZE, 3), jnp.bfloat16)
    params = C.nest({k: v for k, v in weights.items()})

    def loss(p):
        out = spec.model.apply({**params, "params": p}, img, img,
                               train=True)[0]
        return sum(jnp.abs(o).mean() for o in out)

    capsys.readouterr()
    print_saved_residuals(loss, params["params"])
    return [(ln.split()[0], ln)
            for ln in capsys.readouterr().out.splitlines() if ln.strip()]


@pytest.mark.parametrize("scale, channels", [(2, 64), (4, 96)],
                         ids=["half", "quarter"])
def test_the_encoders_backward_keeps_convolution_outputs_alone(
        capsys, weights, scale, channels):
    # the remat policy of raft_fs.py: of the encoders' activations the
    # backward pass holds the five convolution outputs of a stage, in the
    # compute dtype, for each frame (fnet, a frame a call at a batch of
    # one) and for frame one (cnet), and no full-size float32 intermediate
    # of a norm (without the policy there are a dozen a stage)
    h, w = SIZE[0] // scale, SIZE[1] // scale
    kept = [aval for aval, _ in _kept_by_the_backward(capsys, weights)
            if f",{h},{w},{channels}]" in aval]
    assert kept == 15 * [f"bf16[1,{h},{w},{channels}]"]


def test_the_encoders_backward_keeps_the_norms_statistics(capsys, weights):
    # ... and of every instance norm its two float32 sums a channel (the
    # policy's ``reduce_sum``; mean and 1/sigma follow from them), fifteen
    # norms a call of fnet and two calls: sixty small arrays, and nothing
    # of a feature map's size in float32 anywhere in the encoders
    kept = _kept_by_the_backward(capsys, weights)
    sums = [aval for aval, _ in kept
            if aval in ("f32[1,64]", "f32[1,96]", "f32[1,128]")]
    assert sorted(sums) == sorted(20 * ["f32[1,64]", "f32[1,96]",
                                        "f32[1,128]"])
    smallest = SIZE[0] // 8 * SIZE[1] // 8 * 128
    for aval, line in kept:
        dtype, dims = aval.rstrip("]").split("[")
        if "from the argument" in line or not dims:
            continue
        dims = [int(d) for d in dims.split(",")]
        if (dtype == "f32" and len(dims) >= 4
                and int(np.prod(dims)) >= smallest):
            assert not any(src in line for src in (
                "encoders/raft.py", "blocks/raft.py", "norm.py")), line


def _window_costs_gather(f1, f2, centres, radius):
    """The same costs from four gathered taps a sample, each zero where
    it lies outside the map (``grid_sample``, ``align_corners=True``),
    dotted with ``f1``: what the hat contraction is held against."""
    b, h2, w2, c = f2.shape
    d = jnp.arange(-radius, radius + 1, dtype=jnp.float32)
    x = centres[..., 0][..., None, None] + d[:, None]       # (B,H,W,Kx,1)
    y = centres[..., 1][..., None, None] + d[None, :]       # (B,H,W,1,Ky)
    x, y = jnp.broadcast_arrays(x, y)
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
    costs = jnp.einsum("bhwxyc,bhwc->bhwxy", out, f1)
    return costs.reshape(*costs.shape[:3], -1)


@pytest.mark.parametrize("level", [0, 2])
def test_window_costs_are_the_four_tap_gather_dotted(level):
    # the centres' grid is 8x12, the map 2^level times coarser
    rng = np.random.default_rng(3)
    f1 = jnp.asarray(rng.normal(size=(2, 8, 12, 5)), jnp.float32)
    f2 = jnp.asarray(rng.normal(size=(2, 8 >> level, 12 >> level, 5)),
                     jnp.float32)
    centres = jnp.asarray(rng.uniform(-6, 16, (2, 8, 12, 2)),
                          jnp.float32) / 2 ** level
    dense = fs.window_costs(C.Params({}), f1, f2, centres, 2)
    taps = _window_costs_gather(f1, f2, centres, 2)
    assert dense.shape == taps.shape == (2, 8, 12, 25)
    assert float(jnp.abs(dense).max()) > 0.5
    np.testing.assert_allclose(dense, taps, atol=1e-5)


@pytest.mark.parametrize("dispatch", [
    (0, "xla"), (1, "xla"), (1, "band"), (2, "xla"), (2, "band"),
    (4, "xla"), (4, "band")], indirect=True,
    ids=lambda p: f"windowed{p[0]}-{p[1]}")
def test_every_iterate_loss_and_gradient_agree_to_float32_rounding(
        weights, dispatch):
    cfg = _config(False)
    img1, img2, flow, valid = _pair(5)
    n1, n2 = C.normalize_images(img1), C.normalize_images(img2)
    loss_args = dict(cfg["loss"]["arguments"], gamma=0.8)
    params = {k: v for k, v in weights.items() if k.startswith("params/")}
    fixed = {k: v for k, v in weights.items() if not k.startswith("params/")}

    def numbers(module):
        def f(params):
            out = module.forward(C.Params({**params, **fixed}), cfg, n1, n2)
            return module.loss_sum(out, flow, valid, loss_args), out
        (loss, out), grads = jax.jit(jax.value_and_grad(f, has_aux=True))(
            params)
        return float(loss), out, grads

    with jax.default_matmul_precision("highest"):
        ref_loss, ref_out, ref_grads = numbers(fs)
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
    # the costs reach the loss through the motion encoder's first kernel
    first = f"params/{fs.STEP}/BasicMotionEncoder_0/Conv_0/kernel"
    assert norms[first] > 1e-3 * median


def test_fp8_operands_move_a_gap_past_three_times_the_sound_one(weights):
    cfg = _config(False)
    stage = json.loads((ROOT / "benchmark/traffic/train-things.json")
                       .read_text())["stage"]
    batches = [_pair(7), _pair(8)]
    with jax.default_matmul_precision("highest"):
        reference = reftrain.run(fs, cfg, stage, weights, batches)
        control = reftrain.run(fs, cfg, stage, weights, batches,
                               quant=jnp.float8_e4m3fn)
    # the program as the cell runs it: its bf16 policy on
    program = reftrain.run(_Program(_config(True)), cfg, stage, weights,
                           batches)
    sound, _ = reftrain.compare(program, reference)
    low, _ = reftrain.compare(control, reference)
    print("sound", sound, "control", low)
    assert all(np.isfinite(v) for v in (*sound.values(), *low.values()))
    assert max(v for k, v in sound.items() if k != "grad_norm_gap") < 0.5, \
        sound
    assert any(low[k] > 3.0 * sound[k] for k in sound), (sound, low)
