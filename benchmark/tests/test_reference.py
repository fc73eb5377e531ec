"""The plain references against the program, at toy size on the CPU.

    JAX_PLATFORMS=cpu python3 -m pytest benchmark/tests/test_reference.py -q

The references import nothing of the program; these tests do, to show that
both state the same mathematics: with the program's bf16 policy off and
matmuls at highest precision the two agree to float32 rounding.
"""

import sys
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from benchmark.reference import common as C  # noqa: E402
from benchmark.reference import raft  # noqa: E402
from benchmark.reference import train as reftrain  # noqa: E402


def _config(name):
    import json

    return json.loads(
        (ROOT / f"benchmark/configs/{name}.json").read_text())["model"]


def test_hat_contraction_is_four_tap_bilinear_sampling_with_zero_padding():
    rng = np.random.default_rng(0)
    img = rng.random((7, 9)).astype(np.float32)
    xs = rng.uniform(-2, 10, 50).astype(np.float32)
    ys = rng.uniform(-2, 8, 50).astype(np.float32)
    dense = np.einsum("ph,hw,pw->p", np.asarray(C.hat(jnp.asarray(ys), 7)),
                      img, np.asarray(C.hat(jnp.asarray(xs), 9)))

    def tap(y, x):
        inside = (0 <= y) & (y < 7) & (0 <= x) & (x < 9)
        return np.where(inside, img[np.clip(y, 0, 6), np.clip(x, 0, 8)], 0.0)

    x0, y0 = np.floor(xs).astype(int), np.floor(ys).astype(int)
    fx, fy = xs - x0, ys - y0
    gathered = ((1 - fy) * (1 - fx) * tap(y0, x0) + (1 - fy) * fx * tap(y0, x0 + 1)
                + fy * (1 - fx) * tap(y0 + 1, x0) + fy * fx * tap(y0 + 1, x0 + 1))
    np.testing.assert_allclose(dense, gathered, atol=1e-5)


def _program_outputs(cfg, flat, n1, n2, **init_args):
    from raft_meets_dicl_tpu import models

    model = models.load(cfg).model
    model.frozen_batchnorm = True
    want = jax.eval_shape(
        lambda a, b: model.init(jax.random.PRNGKey(0), a, b, **init_args),
        n1[:1], n1[:1])
    have = {k: tuple(v.shape) for k, v in flat.items()}
    assert {k: tuple(v.shape) for k, v in C.flatten(dict(want)).items()} == have
    with jax.default_matmul_precision("highest"):
        return model.apply(C.nest(flat), n1, n2, train=True)[0]


def _images(h, w):
    rng = np.random.default_rng(1)
    return (C.normalize_images(rng.random((2, h, w, 3), dtype=np.float32)),
            C.normalize_images(rng.random((2, h, w, 3), dtype=np.float32)))


def test_raft_reference_is_the_programs_mathematics():
    cfg = _config("raft-baseline")
    cfg["model"]["parameters"]["mixed-precision"] = False
    cfg["model"]["arguments"]["iterations"] = 4
    flat = C.init(raft.spec(cfg), 7)
    n1, n2 = _images(64, 96)
    prog = jnp.stack(_program_outputs(cfg, flat, n1, n2, iterations=1))
    with jax.default_matmul_precision("highest"):
        ref = jax.jit(lambda a, b: raft.forward(C.Params(flat), cfg, a, b))(
            n1, n2)
    assert prog.shape == ref.shape == (4, 2, 64, 96, 2)
    assert float(jnp.abs(ref[-1]).mean()) > 0.05
    assert float(jnp.abs(prog - ref).max()) < 1e-3


def test_reference_optimizer_is_clip_then_adamw_at_the_one_cycle_rate():
    import optax

    from raft_meets_dicl_tpu.strategy.spec import OneCycleLr

    sched = OneCycleLr(1.25e-4, 1.25e-4, 100100, pct_start=0.05,
                       anneal_strategy="linear", cycle_momentum=False)
    for step in (0, 1, 2, 5003, 5004, 5005, 60000, 100099):
        sched.last_step = step
        assert abs(sched.lr() - C.one_cycle_lr(step, 1.25e-4, 100100, 0.05)) \
            < 1e-12

    rng = np.random.default_rng(2)
    params = {"a": jnp.asarray(rng.normal(size=(5, 3)), jnp.float32),
              "b": jnp.asarray(rng.normal(size=(7,)), jnp.float32)}
    tx = optax.chain(optax.clip_by_global_norm(1.0),
                     optax.scale_by_adam(b1=0.9, b2=0.999, eps=1e-8),
                     optax.add_decayed_weights(1e-4))
    state = tx.init(params)
    mine, mu, nu, count = params, *({k: jnp.zeros_like(v) for k, v in
                                     params.items()} for _ in range(2)), 0
    theirs = params
    for step in range(3):
        grads = {k: jnp.asarray(rng.normal(size=v.shape) * 3, jnp.float32)
                 for k, v in params.items()}
        lr = C.one_cycle_lr(step, 1.25e-4, 100100, 0.05)
        updates, state = tx.update(grads, state, theirs)
        theirs = optax.apply_updates(
            theirs, jax.tree.map(lambda u: -lr * u, updates))
        clipped, _ = C.clip_by_global_norm(grads, 1.0)
        mine, mu, nu, count = C.adamw_step(mine, clipped, mu, nu, count, lr,
                                           1e-4)
    for k in params:
        np.testing.assert_allclose(mine[k], theirs[k], rtol=1e-6, atol=1e-9)


def test_worst_leaf_measures_against_the_median_leaf_and_flags_no_number():
    ref = {"a": 1.0, "b": 2.0, "dead": 1e-9}
    gap, leaf = reftrain.worst_leaf({"a": 1.1, "b": 2.0, "dead": 0.05}, ref)
    assert leaf == "a" and abs(gap - 0.1) < 1e-9
    gap, leaf = reftrain.worst_leaf({"a": 1.0, "b": float("nan"),
                                     "dead": 0.0}, ref)
    assert gap == float("inf") and leaf == "b"
