"""What a partitioned step says of itself (PR 39): the ``mesh`` and the
``collectives`` on its ``compile`` / ``aot`` events, the phase
``collective`` of its ``owners`` record, ``devices`` on the loop's ``step``
events; and that a one-device step says none of it.

A tiny ``raft/baseline`` train step over four of the virtual CPU devices,
compiled and saved by one boot and loaded by a second, beside the same step
on one device.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from raft_meets_dicl_tpu import compile as programs
from raft_meets_dicl_tpu import parallel, telemetry
from raft_meets_dicl_tpu.analysis import collectives
from raft_meets_dicl_tpu.compile import owners
import raft_meets_dicl_tpu.models as models
from raft_meets_dicl_tpu.models.wire import WireFormat

TINY = {
    "name": "tiny-mesh", "id": "tiny-mesh",
    "model": {
        "type": "raft/baseline",
        "parameters": {"corr-levels": 2, "corr-radius": 2,
                       "corr-channels": 32, "context-channels": 16,
                       "recurrent-channels": 16},
        "arguments": {"iterations": 2},
    },
    "loss": {"type": "raft/sequence"},
    "input": None,
}


def _boot(key, mesh):
    """One boot of the tiny train step: the program, the ``compile`` and
    ``aot`` events it emitted, the compiled text's own schedule."""
    import optax

    spec = models.load(TINY)
    model, loss = spec.model, spec.loss
    variables = model.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 32, 48, 3)),
        jnp.zeros((1, 32, 48, 3)), iterations=1)
    tx = optax.chain(optax.clip_by_global_norm(1.0), optax.adamw(1e-3))
    rng = np.random.RandomState(0)
    batch = (jnp.asarray(rng.rand(4, 32, 48, 3), jnp.bfloat16),
             jnp.asarray(rng.rand(4, 32, 48, 3), jnp.bfloat16),
             jnp.asarray(rng.randn(4, 32, 48, 2), jnp.float16),
             jnp.full((4, 32, 6), 255, jnp.uint8))      # bit-packed valid
    state = parallel.TrainState.create(variables, tx)
    if mesh is not None:
        state = parallel.replicate(state, mesh)
        batch = parallel.shard_batch(batch, mesh)
    step = parallel.make_train_step(
        model, loss, tx, mesh=mesh, model_args={"iterations": 2}, key=key,
        external_lr=True, wire=WireFormat.from_config("bf16"))
    sink = telemetry.get()
    before = len(sink.events)
    _, aux = step(state, jnp.float32(1e-3), *batch)
    assert np.isfinite(float(aux["loss"]))
    events = [e for e in sink.events[before:]
              if e["kind"] == "aot"
              or (e["kind"] == "compile" and e["label"] == "train_step")]
    return step, events


def _key(name):
    return programs.ProgramKey(
        "train_step", "tiny-mesh",
        programs.flag_items(shape=(4, 32, 48), iterations=2, layout=name))


@pytest.fixture(scope="module")
def boots(tmp_path_factory):
    """``{"mesh": (events of the saving boot, events of the loading boot,
    its program), "one": (events, program)}``."""
    store = tmp_path_factory.mktemp("mesh_events") / "programs"
    programs.reset()
    programs.enable_aot(str(store))
    telemetry.activate(telemetry.Telemetry())
    mesh = parallel.make_mesh(None, devices=jax.devices()[:4])
    try:
        step1, first = _boot(_key("data4"), mesh)
        assert step1.aot_saves == 1
        programs.reset()
        step2, second = _boot(_key("data4"), mesh)
        assert step2.aot_hits == 1 and step2.compiles == 0
        programs.reset()
        step3, alone = _boot(_key("one"), None)
        yield {"mesh": (first, second, step2), "one": (alone, step3)}
    finally:
        telemetry.deactivate()
        programs.disable_aot()
        programs.reset()


def _one(events, **match):
    (ev,) = [e for e in events
             if all(e.get(k) == v for k, v in match.items())]
    return ev


def test_the_mesh_steps_compile_event_says_its_mesh(boots):
    first, _, _ = boots["mesh"]
    ev = _one(first, kind="compile")
    assert ev["mesh"] == {"data": 4}
    telemetry.validate_event(ev)


def test_the_event_that_holds_the_executable_says_mesh_and_collectives(boots):
    first, _, _ = boots["mesh"]
    ev = _one(first, kind="aot", event="save")
    assert ev["mesh"] == {"data": 4}
    said = ev["collectives"]
    assert set(said) == {"counts", "bytes", "total_bytes"}
    # a data-parallel train step reduces its gradients, and nothing is
    # stored sharded: no parameter gather
    assert said["counts"].get("all-reduce", 0) >= 1
    assert said["bytes"]["all-reduce"] > 0
    assert said["total_bytes"] == sum(said["bytes"].values())
    assert set(said["counts"]) == set(said["bytes"])
    telemetry.validate_event(ev)


def test_the_gradients_reduce_covers_the_parameters_once(boots):
    # the sharding contract (analysis/collectives.expected_schedule) held
    # against what the event says: one reduce phase, about the gradient's
    # mass, not twice it
    first, _, step = boots["mesh"]
    said = _one(first, kind="aot", event="save")["collectives"]
    spec = models.load(TINY)
    variables = spec.model.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 32, 48, 3)),
        jnp.zeros((1, 32, 48, 3)), iterations=1)
    want = collectives.expected_schedule("train_step", 4,
                                         params=variables["params"])
    assert want.phases == ("reduce",)
    found = collectives.diff(want, dict(said, order=[]), key="tiny-mesh")
    assert not found, [f.message for f in found]
    # most of the parameters' mass, once (XLA:CPU reduces 94% of it here:
    # not every leaf's gradient crosses as a reduce of its own size)
    reduced = sum(said["bytes"].get(op, 0) for op in collectives.REDUCE_OPS)
    assert 0.5 * want.reduce_bytes < reduced < 1.8 * want.reduce_bytes


def test_the_owners_record_books_them_under_collective(boots):
    first, _, _ = boots["mesh"]
    rec = _one(first, kind="aot", event="owners")
    said = _one(first, kind="aot", event="save")["collectives"]
    booked = owners.flat(rec)
    mine = {k: v for k, v in booked.items() if v[0] == owners.COLLECTIVE}
    assert mine and rec["rules"]["collective"] == len(mine)
    # by opcode, whatever the name stack: every key is a collective's name
    for key, (_, scope, _) in mine.items():
        assert key.split(":")[0].split(".")[0].removesuffix("-start") \
            .removesuffix("-done") == scope or key.startswith("async-")
    # the schedule counts a start and its done once, the record each
    assert len(mine) >= sum(said["counts"].values())
    assert set(said["counts"]) <= {scope for _, scope, _ in mine.values()}
    # the model's phases are all still there
    assert set(owners.PHASES) - {"input"} <= set(rec["owners"])


def test_a_warm_boot_reads_them_from_the_artifact(boots):
    first, second, step2 = boots["mesh"]
    assert [e["event"] for e in second if e["kind"] == "aot"] == [
        "hit", "owners"]
    assert not [e for e in second if e["kind"] == "compile"]
    hit = _one(second, kind="aot", event="hit")
    assert hit["mesh"] == {"data": 4}
    assert hit["collectives"] == _one(first, kind="aot",
                                      event="save")["collectives"]
    rec = _one(second, kind="aot", event="owners")
    assert rec["source"] == "artifact" and rec["seconds"] == 0.0
    assert step2.mesh_axes == {"data": 4}


def test_a_one_device_step_says_neither(boots):
    alone, step = boots["one"]
    assert step.mesh_axes is None
    for ev in alone:
        assert "mesh" not in ev and "collectives" not in ev, ev
    rec = _one(alone, kind="aot", event="owners")
    assert owners.COLLECTIVE not in rec["owners"]
    assert "collective" not in rec["rules"]
    assert {e["event"] for e in alone if e["kind"] == "aot"} >= {"save"}


def test_an_artifact_from_before_the_collectives_loads_and_says_none(
        tmp_path, monkeypatch):
    # ``text_facts`` is additive: what an older boot stored has no
    # ``collectives`` key, and the hit then carries the mesh alone
    import importlib

    registry = importlib.import_module(
        "raft_meets_dicl_tpu.compile.registry")
    monkeypatch.delenv("RMD_AOT", raising=False)
    monkeypatch.delenv("RMD_AOT_DIR", raising=False)
    programs.reset()
    programs.enable_aot(str(tmp_path / "programs"))
    telemetry.activate(telemetry.Telemetry())
    real = registry.Program._text_facts

    def older(self, compiled):
        facts = real(self, compiled)
        facts.pop("collectives", None)
        return facts

    mesh = parallel.make_mesh(None, devices=jax.devices()[:2])
    try:
        monkeypatch.setattr(registry.Program, "_text_facts", older)
        _boot(_key("older"), mesh)
        monkeypatch.setattr(registry.Program, "_text_facts", real)
        programs.reset()
        step, events = _boot(_key("older"), mesh)
        assert step.aot_hits == 1
        hit = _one(events, kind="aot", event="hit")
        assert hit["mesh"] == {"data": 2} and "collectives" not in hit
    finally:
        telemetry.deactivate()
        programs.disable_aot()
        programs.reset()


def test_the_loops_step_event_counts_the_devices_it_fed(tmp_path):
    from test_strategy import TINY_MODEL, _make_stage

    from raft_meets_dicl_tpu import strategy
    from raft_meets_dicl_tpu.utils.logging import Logger

    def run(mesh, where):
        spec = models.load(TINY_MODEL)
        mgr = strategy.CheckpointManager(
            "tiny", where / "checkpoints",
            "{id_model}-s{n_stage}_e{n_epoch}_b{n_steps}.ckpt",
            compare=["{m_loss}"], keep_best=2, keep_latest=2)
        ctx = strategy.TrainingContext(
            Logger("test"), where, strategy.Strategy(
                "continuous", [_make_stage(epochs=1)]), "tiny",
            spec.model, spec.model.get_adapter(), spec.loss, spec.input,
            strategy.Inspector(), mgr, mesh=mesh,
            loader_args={"num_workers": 0})
        programs.reset()
        sink = telemetry.activate(telemetry.Telemetry())
        try:
            ctx.run()
        finally:
            telemetry.deactivate()
        return [e for e in sink.events if e["kind"] == "step"]

    (tmp_path / "a").mkdir()
    (tmp_path / "b").mkdir()
    mesh = parallel.make_mesh(None, devices=jax.devices()[:2])
    steps = run(mesh, tmp_path / "a")
    assert len(steps) == 2
    for ev in steps:
        # one put a step, whatever it feeds
        assert len(ev["put"]) == 2 and ev["devices"] == 2
        telemetry.validate_event(ev)
    for ev in run(None, tmp_path / "b"):
        assert "put" in ev and "devices" not in ev


def test_a_kernel_keeps_its_scopes_name_inside_the_map(monkeypatch):
    """A compiled Mosaic call is named after the scope round its
    ``pallas_call``; ``ops/pallas._per_shard`` states that scope again
    inside its ``shard_map``, so a mesh step's kernels are ``Up8Network_0.N``
    and not ``shard_map.N`` to a capture's readers."""
    from jax.extend import source_info_util
    from jax.sharding import NamedSharding, PartitionSpec as P

    from raft_meets_dicl_tpu.ops import pallas
    from raft_meets_dicl_tpu.parallel.mesh import traced_under

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    mesh = parallel.make_mesh(None, devices=jax.devices()[:4])
    seen = []

    def kernel(x):
        seen.append(str(source_info_util.current_name_stack()))
        return x * 2

    def step(x):
        with jax.named_scope("up8"), jax.named_scope("Up8Network_0"):
            return pallas._per_shard(kernel)(x)

    x = jnp.arange(8.0).reshape(4, 2)
    data = NamedSharding(mesh, P("data"))
    out = traced_under(mesh, jax.jit(step, in_shardings=(data,)))(x)
    np.testing.assert_array_equal(out, 2 * x)
    # the map's body is traced under a stack of its own: the scope alone
    assert seen and set(seen) == {"Up8Network_0"}, seen
    text = traced_under(mesh, jax.jit(step, in_shardings=(data,))).lower(
        x).as_text(debug_info=True)
    # the operation inside the map is the scope's, the map itself under it
    assert 'loc("Up8Network_0/mul"' in text
    assert "up8/Up8Network_0/shard_map" in text

    # no mesh, or off the TPU: the function itself, nothing wrapped
    assert pallas._per_shard(kernel) is kernel
    # outside every scope there is nothing to state again
    assert pallas._under_its_scope(kernel) is kernel
