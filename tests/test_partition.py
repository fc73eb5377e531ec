"""Partition-rule / 2-D mesh / grad-accumulation tests (8-device CPU).

Covers the PR-6 SPMD scale-out layer: regex rule matching, optimizer
moments cloning their parameter's spec, the (4, 2) ``(data × model)``
mesh train step (per-device param bytes ≈ ½ of replicated, loss parity
with the single-device step), bit-identity of the ``model=1`` mesh with
the historical path, in-step gradient accumulation, and per-host loader
sharding covering the epoch exactly once.
"""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from jax.sharding import PartitionSpec as P

import raft_meets_dicl_tpu.models as models
from raft_meets_dicl_tpu import parallel
from raft_meets_dicl_tpu.parallel import partition

pytestmark = pytest.mark.spmd

TINY = {
    "name": "tiny", "id": "tiny",
    "model": {
        "type": "raft/baseline",
        "parameters": {
            "corr-levels": 2, "corr-radius": 2, "corr-channels": 32,
            "context-channels": 16, "recurrent-channels": 16,
            # instance norms: no train-mode batch statistics, so the
            # grad-accumulation equivalence below is exact up to
            # reduction order
            "encoder-norm": "instance", "context-norm": "instance",
        },
        "arguments": {"iterations": 2},
    },
    "loss": {"type": "raft/sequence"},
    "input": None,
}


@pytest.fixture(scope="module")
def tiny():
    spec = models.load(TINY)
    rng = np.random.RandomState(0)
    b, h, w = 8, 16, 24
    batch = (
        jnp.asarray(rng.rand(b, h, w, 3), jnp.float32),
        jnp.asarray(rng.rand(b, h, w, 3), jnp.float32),
        jnp.asarray(rng.randn(b, h, w, 2), jnp.float32),
        jnp.ones((b, h, w), bool),
    )
    variables = spec.model.init(jax.random.PRNGKey(0),
                                batch[0][:1], batch[1][:1])
    return spec, variables, batch


def _leaf(tree, *path):
    for p in path:
        tree = tree[p]
    return tree


# -- mesh construction / spec parsing ----------------------------------------


def test_parse_mesh_spec():
    assert parallel.parse_mesh_spec(None) is None
    assert parallel.parse_mesh_spec("data") is None
    assert parallel.parse_mesh_spec("") is None
    assert parallel.parse_mesh_spec("4,2") == (4, 2)
    assert parallel.parse_mesh_spec("4x2") == (4, 2)
    assert parallel.parse_mesh_spec("8") == (8, 1)
    assert parallel.parse_mesh_spec("-1,2") == (-1, 2)
    assert parallel.parse_mesh_spec({"data": 4, "model": 2}) == (4, 2)
    assert parallel.parse_mesh_spec((2, 4)) == (2, 4)
    with pytest.raises(ValueError, match="invalid mesh spec"):
        parallel.parse_mesh_spec("banana")
    with pytest.raises(ValueError, match="two axes"):
        parallel.parse_mesh_spec("2,2,2")


def test_make_mesh_shapes():
    m = parallel.make_mesh((4, 2))
    assert m.axis_names == ("data", "model")
    assert dict(m.shape) == {"data": 4, "model": 2}

    # model=1 degenerates to the historical 1-D data mesh, same device
    # order — the compiled program is the pre-2D-mesh one bit for bit
    m1 = parallel.make_mesh((8, 1))
    ref = parallel.data_mesh(8)
    assert m1.axis_names == ref.axis_names == ("data",)
    assert list(m1.devices.flat) == list(ref.devices.flat)

    # data=-1 fills the remaining devices
    m2 = parallel.make_mesh((-1, 2))
    assert dict(m2.shape) == {"data": 4, "model": 2}

    with pytest.raises(ValueError, match="devices"):
        parallel.make_mesh((8, 2))


def test_scoped_data_axis_size_nesting():
    assert parallel.data_axis_size() == 1
    with parallel.scoped_data_axis_size(8):
        assert parallel.data_axis_size() == 8
        with parallel.scoped_data_axis_size(2):
            assert parallel.data_axis_size() == 2
        # inner scope restores the ENCLOSING value, not 1 — the leak the
        # old module-global set/reset could not prevent
        assert parallel.data_axis_size() == 8
    assert parallel.data_axis_size() == 1


def test_traced_under_publishes_the_mesh_to_calls_and_lowerings():
    """What the Pallas kernels read to map themselves over the batch
    shards: the mesh is visible while the step traces — through a call
    and through the AOT ``lower`` — and nowhere else."""
    from raft_meets_dicl_tpu.parallel.mesh import traced_mesh, traced_under

    outer, inner = parallel.make_mesh((4, 2)), parallel.data_mesh(2)
    seen = []

    def probe(x):
        seen.append((traced_mesh(), parallel.data_axis_size()))
        return x + 1

    step = traced_under(outer, jax.jit(probe))
    assert traced_mesh() is None
    step(jnp.zeros(2))
    step.lower(jnp.zeros(3))
    assert seen == [(outer, 8), (outer, 8)]

    def nested(x):
        return traced_under(inner, jax.jit(probe))(x) + traced_mesh().size

    seen.clear()
    assert float(traced_under(outer, nested)(jnp.zeros(()))) == 9.0
    assert seen == [(inner, 2)]
    assert traced_mesh() is None and parallel.data_axis_size() == 1


# -- rule matching -----------------------------------------------------------


def test_rules_shard_kernels_not_biases(tiny):
    spec, variables, _ = tiny
    part = parallel.Partitioner(parallel.make_mesh((4, 2)))

    # encoder conv kernel: output channels over 'model'
    assert part.spec("FeatureEncoderS3_0/_Stem_0/Conv_0/kernel",
                     (7, 7, 3, 64)) == P(None, None, None, "model")
    # bias / norm affine / scalars replicated
    assert part.spec("FeatureEncoderS3_0/_Stem_0/Conv_0/bias", (64,)) == P()
    assert part.spec(
        "FeatureEncoderS3_1/_Stem_0/Norm2d_0/BatchNorm_0/scale",
        (64,)) == P()
    assert part.spec("step", ()) == P()
    # non-divisible channel count falls back to replication
    assert part.spec("FlowHead_0/Conv_1/kernel", (3, 3, 256, 3)) == P()

    shardings = part.param_shardings(variables["params"])
    k = _leaf(shardings, "FeatureEncoderS3_0", "_Stem_0", "Conv_0", "kernel")
    b = _leaf(shardings, "FeatureEncoderS3_0", "_Stem_0", "Conv_0", "bias")
    assert k.spec == P(None, None, None, "model")
    assert b.spec == P()


def test_moments_clone_param_spec(tiny):
    spec, variables, _ = tiny
    part = parallel.Partitioner(parallel.make_mesh((4, 2)))
    tx = optax.chain(optax.clip_by_global_norm(1.0), optax.adamw(1e-4))
    state = parallel.TrainState.create(variables, tx)
    ss = part.state_shardings(state)

    kernel_spec = _leaf(part.param_shardings(state.params),
                        "FeatureEncoderS3_0", "_Stem_0", "Conv_0",
                        "kernel").spec
    assert kernel_spec == P(None, None, None, "model")

    # find the adam moment subtree inside the chain state and check the
    # mu/nu leaf for that kernel clones the param spec while the step
    # counter stays replicated
    def adam_states(tree, tree_sh):
        if hasattr(tree, "mu"):
            yield tree, tree_sh
        elif isinstance(tree, (tuple, list)):
            for t, s in zip(tree, tree_sh):
                yield from adam_states(t, s)

    found = list(adam_states(state.opt_state, ss.opt_state))
    assert len(found) == 1
    _, adam_sh = found[0]
    mu = _leaf(adam_sh.mu, "FeatureEncoderS3_0", "_Stem_0", "Conv_0",
               "kernel")
    nu = _leaf(adam_sh.nu, "FeatureEncoderS3_0", "_Stem_0", "Conv_0",
               "kernel")
    assert mu.spec == kernel_spec
    assert nu.spec == kernel_spec
    assert adam_sh.count.spec == P()

    # TrainState scalars replicated
    assert ss.step.spec == P()
    assert ss.nonfinite_count.spec == P()


# -- 2-D mesh train step -----------------------------------------------------


def test_2d_mesh_step_matches_single_device_and_halves_bytes(tiny):
    spec, variables, batch = tiny
    model, loss = spec.model, spec.loss
    # SGD for the parity check: adam's first step is ~sign(g)*lr, which
    # amplifies reduction-order noise into lr-sized param differences
    tx = optax.sgd(1e-2)

    state1 = parallel.TrainState.create(variables, tx)
    step1 = parallel.make_train_step(model, loss, tx, donate=False)
    state1, aux1 = step1(state1, *batch)

    mesh = parallel.make_mesh((4, 2))
    part = parallel.Partitioner(mesh)
    state2 = part.shard_state(parallel.TrainState.create(variables, tx))
    step2 = parallel.make_train_step(
        model, loss, tx, mesh=mesh, donate=False,
        state_sharding=part.state_shardings(state2))
    state2, aux2 = step2(state2, *parallel.shard_batch(batch, mesh))

    np.testing.assert_allclose(float(aux1["loss"]), float(aux2["loss"]),
                               rtol=1e-5)
    for a, b in zip(jax.tree.leaves(state1.params),
                    jax.tree.leaves(state2.params)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-5)

    # per-device param bytes ≈ ½ of replicated: the parameter mass is
    # conv kernels and they all shard over model=2
    rep = part.report(state2)
    assert rep["params_bytes_per_chip"] < 0.6 * rep["params_bytes_replicated"]
    assert rep["params_sharded_leaves"] > 0
    assert rep["mesh"] == {"data": 4, "model": 2}


def test_2d_mesh_halves_optimizer_moments(tiny):
    spec, variables, _ = tiny
    part = parallel.Partitioner(parallel.make_mesh((4, 2)))
    tx = optax.chain(optax.clip_by_global_norm(1.0), optax.adamw(1e-4))
    state = part.shard_state(parallel.TrainState.create(variables, tx))
    rep = part.report(state)
    # both adam moments shard with their params: per-chip opt bytes ≈ ½
    assert rep["opt_bytes_per_chip"] < 0.6 * rep["opt_bytes_replicated"]
    assert rep["opt_sharded_leaves"] > 0


def test_model1_mesh_bit_identical_to_current_path(tiny):
    spec, variables, batch = tiny
    model, loss = spec.model, spec.loss
    tx = optax.sgd(1e-2)

    # historical path: data_mesh + replicate
    mesh_ref = parallel.data_mesh(8)
    sA = parallel.replicate(parallel.TrainState.create(variables, tx),
                            mesh_ref)
    stepA = parallel.make_train_step(model, loss, tx, mesh=mesh_ref,
                                     donate=False)
    sA, auxA = stepA(sA, *parallel.shard_batch(batch, mesh_ref))

    # model=1 mesh through the partitioner (degenerate all-replicated)
    mesh1 = parallel.make_mesh((8, 1))
    part = parallel.Partitioner(mesh1)
    assert part.model_size == 1
    sB = part.shard_state(parallel.TrainState.create(variables, tx))
    stepB = parallel.make_train_step(
        model, loss, tx, mesh=mesh1, donate=False,
        state_sharding=part.state_shardings(sB))
    sB, auxB = stepB(sB, *parallel.shard_batch(batch, mesh1))

    assert float(auxA["loss"]) == float(auxB["loss"])
    for a, b in zip(jax.tree.leaves(sA.params), jax.tree.leaves(sB.params)):
        assert np.array_equal(np.asarray(a), np.asarray(b))


# -- gradient accumulation ---------------------------------------------------


def test_grad_accum_matches_big_batch_step(tiny):
    spec, variables, batch = tiny
    model, loss = spec.model, spec.loss
    tx = optax.sgd(1e-2)

    # one big-batch step over the full batch of 8 ...
    state1 = parallel.TrainState.create(variables, tx)
    step1 = parallel.make_train_step(model, loss, tx, donate=False)
    state1, aux1 = step1(state1, *batch)

    # ... equals one accumulate=4 step scanning 4 microbatches of 2
    # (equal-sized microbatches + all-valid masks: the mean of microbatch
    # means IS the big-batch mean, and the averaged gradients match)
    state4 = parallel.TrainState.create(variables, tx)
    step4 = parallel.make_train_step(model, loss, tx, donate=False,
                                     accumulate=4)
    state4, aux4 = step4(state4, *batch)

    np.testing.assert_allclose(float(aux1["loss"]), float(aux4["loss"]),
                               rtol=1e-5)
    for a, b in zip(jax.tree.leaves(state1.params),
                    jax.tree.leaves(state4.params)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-4)

    # aux keeps the full-batch contract for host metrics
    assert aux4["final"].shape == aux1["final"].shape


def test_grad_accum_on_2d_mesh(tiny):
    spec, variables, batch = tiny
    model, loss = spec.model, spec.loss
    tx = optax.sgd(1e-2)

    mesh = parallel.make_mesh((4, 2))
    part = parallel.Partitioner(mesh)

    ref = parallel.TrainState.create(variables, tx)
    step_ref = parallel.make_train_step(model, loss, tx, donate=False)
    ref, aux_ref = step_ref(ref, *batch)

    state = part.shard_state(parallel.TrainState.create(variables, tx))
    step = parallel.make_train_step(
        model, loss, tx, mesh=mesh, donate=False, accumulate=2,
        state_sharding=part.state_shardings(state))
    state, aux = step(state, *parallel.shard_batch(batch, mesh))

    np.testing.assert_allclose(float(aux_ref["loss"]), float(aux["loss"]),
                               rtol=1e-5)
    for a, b in zip(jax.tree.leaves(ref.params),
                    jax.tree.leaves(state.params)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-4)


# -- eval picks up sharded params --------------------------------------------


def test_eval_fn_accepts_sharded_variables(tiny):
    from raft_meets_dicl_tpu import evaluation

    spec, variables, batch = tiny
    model = spec.model
    img1, img2 = batch[0], batch[1]
    args = {"iterations": 2}

    fn = evaluation.make_eval_fn(model, args)
    _, ref = fn(variables, img1, img2)

    mesh = parallel.make_mesh((4, 2))
    part = parallel.Partitioner(mesh)
    v_sh = part.shard_variables(variables)
    fn2 = evaluation.make_eval_fn(
        model, args, mesh=mesh,
        variables_sharding=part.variables_sharding(variables))
    _, out = fn2(v_sh, *parallel.shard_batch((img1, img2), mesh))

    np.testing.assert_allclose(np.asarray(ref), np.asarray(out), atol=1e-4)


# -- per-host input sharding -------------------------------------------------


class _IndexSource:
    """Source whose sample payload encodes its own index."""

    def __init__(self, n, h=4, w=4):
        self.n, self.h, self.w = n, h, w

    def __getitem__(self, index):
        from raft_meets_dicl_tpu.data.collection import (
            Metadata, SampleArgs, SampleId,
        )

        img = np.full((1, self.h, self.w, 3), index, np.float32)
        flow = np.zeros((1, self.h, self.w, 2), np.float32)
        valid = np.ones((1, self.h, self.w), bool)
        meta = [Metadata(True, "idx",
                         SampleId(str(index), SampleArgs(), SampleArgs()),
                         ((0, self.h), (0, self.w)))]
        return img, img, flow, valid, meta

    def __len__(self):
        return self.n


def _shard_indices(loader):
    return [int(m.sample_id.format)
            for batch in loader for m in batch[4]]


def test_per_host_loader_shard_covers_epoch_once():
    from raft_meets_dicl_tpu.models.input import Loader

    n, n_proc, bs = 37, 4, 3
    seed = 1234  # every process draws the SAME epoch order (shared seed)
    shards = [
        _shard_indices(Loader(_IndexSource(n), batch_size=bs, shuffle=True,
                              num_workers=0, seed=seed, shard=(i, n_proc)))
        for i in range(n_proc)
    ]

    # equal length per shard (processes step in lockstep) ...
    lengths = {len(s) for s in shards}
    assert lengths == {n // n_proc}

    # ... pairwise disjoint and jointly covering the epoch exactly once
    # (up to the documented floor-drop of the ragged tail)
    seen = [i for s in shards for i in s]
    assert len(seen) == len(set(seen)), "shards overlap"
    assert len(seen) == (n // n_proc) * n_proc
    assert set(seen) <= set(range(n))


# -- end-to-end training loop on the 2-D mesh --------------------------------


def test_training_context_on_2d_mesh_with_accumulation(tmp_path):
    """Full TrainingContext epoch on a (4, 2) mesh with accumulate=2:
    sharded state placement, the k·B loader batch, one optimizer step
    per step call, and the per-stage ``sharding`` telemetry event."""
    from raft_meets_dicl_tpu import strategy, telemetry
    from raft_meets_dicl_tpu.data.collection import (
        Collection, Metadata, SampleArgs, SampleId,
    )
    from raft_meets_dicl_tpu.utils.logging import Logger

    class FlowSource(Collection):
        type = "fake-flow"

        def __init__(self, n=16, h=16, w=24):
            self.n, self.h, self.w = n, h, w

        def __getitem__(self, index):
            rng = np.random.RandomState(index)
            img1 = rng.rand(1, self.h, self.w, 3).astype(np.float32)
            img2 = rng.rand(1, self.h, self.w, 3).astype(np.float32)
            flow = np.zeros((1, self.h, self.w, 2), np.float32)
            valid = np.ones((1, self.h, self.w), bool)
            meta = Metadata(True, "fake",
                            SampleId("s", SampleArgs(), SampleArgs()),
                            ((0, self.h), (0, self.w)))
            return img1, img2, flow, valid, [meta]

        def __len__(self):
            return self.n

        def get_config(self):
            return {"type": self.type, "n": self.n}

        def description(self):
            return f"fake-flow ({self.n} samples)"

    stage = strategy.spec.Stage(
        name="s0", id="test/s0",
        data=strategy.spec.DataSpec(FlowSource(16), epochs=1, batch_size=8),
        validation=[],
        optimizer=strategy.spec.OptimizerSpec("adam", {"lr": 1e-3}),
        gradient=strategy.spec.GradientSpec(
            clip=strategy.spec.ClipGradientNorm(1.0)),
        scheduler=strategy.spec.MultiSchedulerSpec(),
    )
    spec = models.load(TINY)
    mgr = strategy.CheckpointManager(
        "tiny", tmp_path / "checkpoints",
        "{id_model}-s{n_stage}_e{n_epoch}_b{n_steps}.ckpt",
        compare=["{m_loss}"], keep_best=1, keep_latest=1)

    sink = telemetry.activate(telemetry.Telemetry())
    try:
        ctx = strategy.TrainingContext(
            Logger("test"), tmp_path, strategy.Strategy("continuous",
                                                        [stage]),
            "tiny", spec.model, spec.model.get_adapter(), spec.loss,
            spec.input, strategy.Inspector(), mgr,
            mesh=parallel.make_mesh((4, 2)),
            loader_args={"num_workers": 0}, accumulate=2,
        )
        ctx.run()
    finally:
        telemetry.deactivate()

    # 16 samples at batch 8 × accumulate 2 = one 16-sample step call
    assert ctx.step == 1
    assert all(np.isfinite(np.asarray(leaf)).all()
               for leaf in jax.tree.leaves(ctx.variables["params"]))

    shardings = [e for e in sink.events if e["kind"] == "sharding"]
    assert len(shardings) == 1
    assert shardings[0]["mesh"] == {"data": 4, "model": 2}
    assert (shardings[0]["params_bytes_per_chip"]
            < shardings[0]["params_bytes_replicated"])


# -- telemetry ---------------------------------------------------------------


def test_sharding_event_schema_and_report(tiny):
    from raft_meets_dicl_tpu import telemetry
    from raft_meets_dicl_tpu.telemetry import report
    from raft_meets_dicl_tpu.telemetry.core import validate_event

    spec, variables, _ = tiny
    part = parallel.Partitioner(parallel.make_mesh((4, 2)))
    tx = optax.adamw(1e-4)
    state = part.shard_state(parallel.TrainState.create(variables, tx))

    sink = telemetry.Telemetry()
    ev = sink.emit("sharding", step=0, stage=0, **part.report(state))
    validate_event(ev)

    rendered = report.render([ev])
    assert "== sharding ==" in rendered
    assert "data=4" in rendered and "model=2" in rendered

    stats = report.sharding_stats([ev])
    assert len(stats) == 1
    assert stats[0]["params_per_chip"] < stats[0]["params_replicated"]
