"""The ``data=4`` train step against the plain reference and against the
one-device step, toy size, four of the eight virtual CPU devices.

``benchmark/reference/train.py`` computes one pair at a time in float32 and
divides the batch's sum by the batch's valid pixels: it knows no layout, so
it is the statement of what a global batch of ``4 x b`` must give however
the batch is split. Compared, as the cell ``raft-train-things-dp4`` compares
them on the chip (``harness/train_check.py``): the loss of three steps,
step 1's final flow, the per-leaf norm of the first clipped gradient (read
off Adam's first moment) and the per-leaf norm of the parameters' change.
The program runs the configuration's model with the bf16 policy off, so
both sides are float32 and the tolerances are those of float32 sums in
another order. The case ``b6-filled`` runs a per-chip batch of 6 with the
encoders' trace-time question answered as on the chip, so that the per-chip
fill (``_fill_batch_tile`` under a mesh) is the path compared.
"""

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
from benchmark.reference import raft  # noqa: E402
from benchmark.reference import train as reftrain  # noqa: E402

SIZE = (64, 64)      # the least a four-level pyramid at 1/8 takes
STEPS = 3
CHIPS = 4

# Both sides are float32 (bf16 policy off, matmuls at highest precision):
# what is left is the order of float32 sums. The loss and the flow are
# sums over pixels and twelve-fold smaller than the gradient's chain; a
# leaf's gradient norm passes through the whole backward pass and the
# global clip; the parameters' change through Adam's division by the root
# of a second moment that starts at zero, which magnifies a relative gap
# in a small gradient. Measured here: 2e-7, 1e-6, 6e-4, 3e-3 (b2; at 64x64
# the coarsest level is one sample, and the worst leaf is a small one) and
# 3e-7, 1e-6, 5e-5, 2e-4 (b6-filled); limits some six to thirty times above.
# A chip's slice left out of a sum reads a quarter.
AGAINST_REFERENCE = {"loss_gap": 1e-5, "flow_gap": 5e-5,
                     "grad_norm_gap": 5e-3, "param_change_gap": 2e-2}


def _config():
    cfg = json.loads((ROOT / "benchmark/configs/raft-baseline-dp4.json")
                     .read_text())
    model = cfg["model"]
    model["model"]["parameters"]["mixed-precision"] = False
    model["model"]["arguments"]["iterations"] = 2
    return cfg, model


def _stage(batch):
    from raft_meets_dicl_tpu import strategy

    traffic = json.loads((ROOT / "benchmark/traffic/train-things.json")
                         .read_text())
    stage = json.loads(json.dumps(traffic["stage"]))
    stage["data"] = {"epochs": 1, "batch-size": batch,
                     "source": {"type": "synth", "shape": list(SIZE),
                                "size": batch, "seed": 1}}
    loaded = strategy.load(ROOT, {"mode": "continuous", "stages": [stage]})
    return stage, loaded.stages[0]


def _batches(batch, seed):
    rng = np.random.default_rng(seed)
    out = []
    for _ in range(STEPS):
        img1, img2 = (rng.random((batch, *SIZE, 3), dtype=np.float32)
                      for _ in "12")
        flow = rng.normal(0.0, 3.0, (batch, *SIZE, 2)).astype(np.float32)
        valid = rng.random((batch, *SIZE)) > 0.1
        out.append((img1, img2, flow, valid))
    return out


class _AsOnTheChip:
    """``jax`` as the encoders' module sees it, the backend's name
    answered as on the chip. Only the encoders ask through it: the Pallas
    dispatch keeps the CPU's answer and its XLA references."""

    def __getattr__(self, name):
        return getattr(jax, name)

    @staticmethod
    def default_backend():
        return "tpu"


def _run_program(model_cfg, stage_cfg, stage, flat, batches, mesh):
    """Three steps of the program's own train step over ``mesh`` (None: one
    device): what ``harness/train.py``'s probe keeps of a run."""
    from raft_meets_dicl_tpu import models, parallel

    loaded = models.load(model_cfg)
    model = loaded.model
    model.get_adapter().on_stage(stage, **stage.model_on_stage_args)
    tx, _ = stage.optimizer.build(stage.gradient)
    hp = reftrain.hyper(stage_cfg)
    loss_args = dict(stage.loss_args)
    step = parallel.make_train_step(
        model, loaded.loss, tx, mesh=mesh, loss_args=loss_args,
        model_args=stage.model_args, external_lr=True, donate=False)
    state = parallel.TrainState.create(C.nest(flat), tx)
    if mesh is not None:
        state = parallel.replicate(state, mesh)
    clip, rng = model_cfg["input"]["clip"], model_cfg["input"]["range"]
    out = {"loss": []}
    for t, (img1, img2, flow, valid) in enumerate(batches):
        batch = (C.normalize_images(jnp.asarray(img1), clip, rng),
                 C.normalize_images(jnp.asarray(img2), clip, rng),
                 jnp.asarray(flow), jnp.asarray(valid))
        if mesh is not None:
            batch = parallel.shard_batch(batch, mesh)
        lr = C.one_cycle_lr(t, hp["max_lr"], hp["total_steps"],
                            hp["pct_start"])
        state, aux = step(state, jnp.float32(lr), *batch)
        out["loss"].append(float(aux["loss"]))
        if t == 0:
            out["final"] = np.asarray(aux["final"])
            import optax

            (adam,) = [s for s in jax.tree_util.tree_leaves(
                state.opt_state,
                is_leaf=lambda x: isinstance(x, optax.ScaleByAdamState))
                if isinstance(s, optax.ScaleByAdamState)]
            mu = C.flatten(jax.tree.map(np.asarray, adam.mu), "params")
            out["grad_norms"] = {
                k: float(np.linalg.norm(v)) / (1.0 - hp["betas"][0])
                for k, v in mu.items()}
    after = C.flatten(jax.tree.map(np.asarray, state.params), "params")
    out["after"] = after
    out["delta_norms"] = {
        k: float(np.linalg.norm(after[k] - np.asarray(flat[k])))
        for k in after}
    return out, step


CASES = {"b2": (2, False), "b6-filled": (6, True)}


@functools.lru_cache(maxsize=None)
def _runs(case):
    """The reference, the mesh step and, in the case ``b2``, the
    one-device step on the same seeded weights and the same three global
    batches; the fills the encoders made."""
    from raft_meets_dicl_tpu import parallel
    from raft_meets_dicl_tpu.models.common.encoders import raft as encoders

    per_chip, on_chip = CASES[case]
    batch = CHIPS * per_chip
    cfg, model_cfg = _config()
    stage_cfg, stage = _stage(batch)
    flat = C.init(raft.spec(model_cfg), 5)
    batches = _batches(batch, 9)
    reference = reftrain.run(raft, model_cfg, stage_cfg, flat, batches)

    mesh = parallel.make_mesh(None, devices=jax.devices()[:CHIPS])
    assert dict(mesh.shape) == cfg["layout"]["mesh"] == {"data": 4}
    seen = []
    patch = pytest.MonkeyPatch()
    if on_chip:
        real = encoders._fill_batch_tile
        patch.setattr(encoders, "jax", _AsOnTheChip())
        patch.setattr(encoders, "_fill_batch_tile",
                      lambda x, *a: seen.append(
                          (x.shape[0], real(x, *a).shape[0])) or real(x, *a))
    try:
        sharded, _ = _run_program(model_cfg, stage_cfg, stage, flat, batches,
                                  mesh)
    finally:
        patch.undo()
    alone = None
    if not on_chip:
        alone, _ = _run_program(model_cfg, stage_cfg, stage, flat, batches,
                                None)
    return {"reference": reference, "mesh": sharded, "one": alone,
            "seen": seen, "per_chip": per_chip, "on_chip": on_chip}


@pytest.fixture(scope="module", params=list(CASES))
def runs(request):
    return _runs(request.param)


@pytest.fixture(scope="module")
def runs_b2():
    return _runs("b2")


@pytest.mark.parametrize("gap", list(AGAINST_REFERENCE))
def test_the_mesh_step_is_the_references_step(runs, gap):
    gaps, notes = reftrain.compare(runs["mesh"], runs["reference"])
    assert gaps[gap] <= AGAINST_REFERENCE[gap], (gaps, notes)
    # a step that returned its state unchanged would read 1.0 here
    assert notes["flow_magnitude_px"] > 0.05


@pytest.mark.parametrize("gap", list(AGAINST_REFERENCE))
def test_the_one_device_step_reads_the_same_against_the_reference(runs_b2,
                                                                  gap):
    # the control of the comparison above: the same limits hold the step
    # that knows no mesh, on the same global batch
    runs = runs_b2
    gaps, notes = reftrain.compare(runs["one"], runs["reference"])
    assert gaps[gap] <= AGAINST_REFERENCE[gap], (gaps, notes)


def test_the_mesh_step_is_the_one_device_step_on_the_same_batch(runs_b2):
    runs = runs_b2
    sharded, alone = runs["mesh"], runs["one"]
    # losses: a mean over the global batch's valid pixels either way, summed
    # a chip at a time and then over chips
    np.testing.assert_allclose(sharded["loss"], alone["loss"], rtol=2e-6)
    # step 1's flow comes before any reduction over the batch: a sample's
    # flow does not depend on which chip computed it
    np.testing.assert_allclose(sharded["final"], alone["final"], atol=2e-4)
    assert sharded["final"].shape == (CHIPS * runs["per_chip"], *SIZE, 2)
    # the parameters after three steps, leaf by leaf, against the change
    # itself (lr 1e-4: changes are 1e-4 of a weight, so this is tight)
    # (leaves with a gradient: a bias in front of an instance norm moves by
    # Adam's update of rounding noise, see reference/train.py:compare)
    grads = runs["reference"]["grad_norms"]
    floor = reftrain.DEAD_LEAF * float(np.median(list(grads.values())))
    live = [k for k, g in grads.items() if g >= floor]
    assert len(live) > 0.8 * len(grads)
    for k in live:
        a, b = sharded["after"][k], alone["after"][k]
        gap = float(np.linalg.norm(np.asarray(a) - np.asarray(b)))
        assert gap <= 0.02 * sharded["delta_norms"][k], (
            k, gap, sharded["delta_norms"][k])


def test_the_fill_is_the_path_compared_where_the_case_says_so(runs):
    if not runs["on_chip"]:
        assert runs["seen"] == []
        return
    # the context encoder's 24 images, six a chip, filled to eight a chip;
    # the feature encoder's pair of 48 is twelve a chip and left alone
    assert (24, 32) in runs["seen"] and (48, 48) in runs["seen"]
    assert all(a == b or (a, b) == (24, 32) for a, b in runs["seen"])
