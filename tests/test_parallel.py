"""SPMD layer tests on the 8-device virtual CPU mesh."""

from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

import raft_meets_dicl_tpu.models as models
from raft_meets_dicl_tpu import parallel

pytestmark = pytest.mark.slow

TINY = {
    "name": "tiny", "id": "tiny",
    "model": {
        "type": "raft/baseline",
        "parameters": {
            "corr-levels": 2, "corr-radius": 2, "corr-channels": 32,
            "context-channels": 16, "recurrent-channels": 16,
        },
        "arguments": {"iterations": 2},
    },
    "loss": {"type": "raft/sequence"},
    "input": None,
}


def _batch(b, h=16, w=24):
    rng = np.random.RandomState(0)
    return (
        jnp.asarray(rng.rand(b, h, w, 3), jnp.float32),
        jnp.asarray(rng.rand(b, h, w, 3), jnp.float32),
        jnp.asarray(rng.randn(b, h, w, 2), jnp.float32),
        jnp.ones((b, h, w), bool),
    )


def test_mesh_has_8_devices():
    mesh = parallel.data_mesh()
    assert mesh.devices.size == 8


def test_mesh_too_many_devices():
    with pytest.raises(ValueError, match="requested"):
        parallel.data_mesh(99)


def test_sharded_train_step_matches_single_device():
    spec = models.load(TINY)
    model, loss = spec.model, spec.loss

    img1, img2, flow, valid = _batch(8)
    variables = model.init(jax.random.PRNGKey(0), img1[:1], img2[:1])

    # SGD so updates are proportional to gradients (adam's first step is
    # ~sign(g)*lr, which amplifies reduction-order noise into lr-sized
    # param differences)
    tx = optax.sgd(1e-2)

    # single-device reference
    state1 = parallel.TrainState.create(variables, tx)
    step1 = parallel.make_train_step(model, loss, tx, donate=False, with_grads=True)
    state1, aux1 = step1(state1, img1, img2, flow, valid)

    # 8-device mesh
    mesh = parallel.data_mesh(8)
    state8 = parallel.TrainState.create(variables, tx)
    state8 = parallel.replicate(state8, mesh)
    step8 = parallel.make_train_step(model, loss, tx, mesh=mesh, donate=False, with_grads=True)
    batch = parallel.shard_batch((img1, img2, flow, valid), mesh)
    state8, aux8 = step8(state8, *batch)

    # same loss, same gradients (up to reduction order), same updated params
    np.testing.assert_allclose(
        float(aux1["loss"]), float(aux8["loss"]), rtol=1e-5
    )
    for a, b in zip(jax.tree.leaves(aux1["grads"]), jax.tree.leaves(aux8["grads"])):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-4)
    for a, b in zip(jax.tree.leaves(state1.params), jax.tree.leaves(state8.params)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-5)


def test_eval_step_sharded():
    spec = models.load(TINY)
    model = spec.model

    img1, img2, *_ = _batch(8)
    variables = model.init(jax.random.PRNGKey(0), img1[:1], img2[:1])

    mesh = parallel.data_mesh(8)
    step = parallel.make_eval_step(model, mesh=mesh, model_args={"iterations": 2})
    out = step(parallel.replicate(variables, mesh),
               *parallel.shard_batch((img1, img2), mesh))
    assert out.shape == (8, 16, 24, 2)
    assert np.isfinite(np.asarray(out)).all()


def test_evaluation_mesh_matches_single_device():
    """evaluation.evaluate over an 8-device data mesh yields the same
    per-sample finals/outputs as the single-device path, including a
    short (non-divisible) final batch that needs padding."""
    from raft_meets_dicl_tpu import evaluation

    spec = models.load(TINY)
    model = spec.model

    img1, img2, flow, valid = _batch(6)  # 6 % 8 != 0: exercises padding
    variables = model.init(jax.random.PRNGKey(0), img1[:1], img2[:1])

    meta = [{"sample_id": i} for i in range(6)]
    batches = [(np.asarray(img1[:4]), np.asarray(img2[:4]),
                np.asarray(flow[:4]), np.asarray(valid[:4]), meta[:4]),
               (np.asarray(img1[4:]), np.asarray(img2[4:]),
                np.asarray(flow[4:]), np.asarray(valid[4:]), meta[4:])]

    args = {"iterations": 2}
    ref = list(evaluation.evaluate(model, variables, batches,
                                   model_args=args, show_progress=False))

    mesh = parallel.data_mesh(8)
    got = list(evaluation.evaluate(model, variables, batches,
                                   model_args=args, show_progress=False,
                                   mesh=mesh))

    assert len(ref) == len(got) == 6
    for r, g in zip(ref, got):
        assert r.meta == g.meta
        np.testing.assert_allclose(r.final, g.final, atol=1e-5)
        for a, b in zip(r.output, g.output):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=1e-5)


# -- SPMD reach across the zoo ----------------------------------------------

_FULL_DIR = Path(__file__).resolve().parent.parent / "cfg" / "full" / "baseline"


def _every_model_id():
    """One frozen full config per registered model id (the reference wraps
    EVERY model in DataParallel identically — src/cmd/train.py:183-184 —
    so every id must at least trace + shard over the mesh)."""
    import json

    seen = {}
    for f in sorted(_FULL_DIR.glob("*.json")):
        cfg = json.load(open(f))["model"]
        seen.setdefault(cfg["id"], cfg)
    return [pytest.param(cfg, id=mid) for mid, cfg in sorted(seen.items())]


@pytest.mark.parametrize("mcfg", _every_model_id())
def test_spmd_train_step_lowers_for_every_model_id(mcfg):
    """Abstractly trace + lower the full SPMD training step for every
    registered model id at its published (full-channel) configuration on
    the 8-device mesh. eval_shape keeps this a pure tracing check — the
    compile+run proof per model family lives in the tests above and in
    the ``tests/test_reference_*.py`` files; this one catches per-id
    shape, adapter, loss, or sharding-annotation breaks."""
    spec = models.load(mcfg)
    model, loss = spec.model, spec.loss

    margs = dict(mcfg["model"].get("arguments", {}))
    iters = margs.get("iterations")
    if isinstance(iters, (tuple, list)):
        margs["iterations"] = (1,) * len(iters)
    elif iters is not None:
        margs["iterations"] = 1
    margs.pop("prev_flow", None)  # loss-pairing variant, not a step knob

    mesh = parallel.data_mesh(8)
    b, h, w = 8, 128, 128
    img = jnp.zeros((b, h, w, 3), jnp.float32)
    flow = jnp.zeros((b, h, w, 2), jnp.float32)
    valid = jnp.zeros((b, h, w), bool)

    tx = optax.chain(optax.clip_by_global_norm(1.0), optax.adamw(1e-4))

    def abstract_state():
        variables = model.init(jax.random.PRNGKey(0), img[:1], img[:1],
                               **margs)
        return parallel.TrainState.create(variables, tx)

    state_shape = jax.eval_shape(abstract_state)
    step = parallel.make_train_step(model, loss, tx, mesh=mesh,
                                    model_args=margs)
    lowered = step.lower(state_shape, img, img, flow, valid)
    assert lowered is not None
