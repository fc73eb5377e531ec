"""Compiled-program registry + AOT export + prefetch (PR 7).

Covers: ProgramKey identity/stability, registry dedupe across the
train-validation and eval-CLI paths, AOT save→reload roundtrips
(bit-identical outputs, zero second-boot compiles), corrupted and
version-mismatched artifacts falling back cleanly, per-program compile
attribution (the warm-cache overcount bugfix), the configurable
persistent-cache directory, the boot/aot telemetry schema + report
section, and the training loop's prefetched feed.
"""

import os
import pickle

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from raft_meets_dicl_tpu import compile as programs
from raft_meets_dicl_tpu import evaluation, parallel, telemetry
import raft_meets_dicl_tpu.models as models
from raft_meets_dicl_tpu.models.wire import WireFormat


@pytest.fixture
def aot_store(tmp_path, monkeypatch):
    """AOT program store enabled against a temp dir; clean registry."""
    monkeypatch.delenv("RMD_AOT", raising=False)
    monkeypatch.delenv("RMD_AOT_DIR", raising=False)
    programs.reset()
    d = tmp_path / "programs"
    programs.enable_aot(str(d))
    yield d
    programs.disable_aot()
    programs.reset()


TINY_EVAL_MODEL = {
    "name": "tiny-prog", "id": "tiny-prog",
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


# -- ProgramKey -----------------------------------------------------------


def test_program_key_identity():
    k1 = programs.ProgramKey("train_step", "m",
                             programs.flag_items(a=1, wire="u8"))
    k2 = programs.ProgramKey("train_step", "m",
                             programs.flag_items(wire="u8", a=1))
    assert k1 == k2  # flag order normalized
    assert hash(k1) == hash(k2)
    assert k1.canonical() == k2.canonical()

    assert k1 != programs.ProgramKey("eval_step", "m", k1.flags)
    assert k1 != programs.ProgramKey("train_step", "m2", k1.flags)
    assert k1 != programs.ProgramKey(
        "train_step", "m", programs.flag_items(a=2, wire="u8"))


def test_program_key_stability():
    stable = programs.ProgramKey("eval_step", "model-id",
                                 programs.flag_items(wire=None))
    assert stable.stable

    by_object = programs.ProgramKey("eval_step", programs.unstable(object()))
    assert not by_object.stable

    # an unstable flag component also pins the key to the process
    pinned = programs.ProgramKey(
        "val_loss", "model-id",
        programs.flag_items(loss=programs.unstable(object())))
    assert not pinned.stable


def test_shape_signature_over_pytrees():
    sig = programs.shape_signature(
        (({"a": jnp.zeros((2, 3)), "b": jnp.zeros((4,), jnp.int32)},),
         1.5, True))
    assert ((2, 3), "float32") in sig
    assert ((4,), "int32") in sig
    assert "float" in sig and "bool" in sig
    # identical structure, different shape -> different signature
    sig2 = programs.shape_signature(
        (({"a": jnp.zeros((2, 4)), "b": jnp.zeros((4,), jnp.int32)},),
         1.5, True))
    assert sig != sig2


# -- registry dedupe + compile attribution --------------------------------


def test_registry_dedupe_and_anonymous():
    programs.reset()
    key = programs.ProgramKey("eval_step", "dedupe-model")
    f1, f2 = jax.jit(lambda x: x + 1), jax.jit(lambda x: x + 1)
    a = programs.register_step("eval_step", f1, key=key)
    b = programs.register_step("eval_step", f2, key=key)
    assert a is b  # same key: second build returns the first program

    c = programs.register_step("eval_step", f1)
    d = programs.register_step("eval_step", f1)
    assert c is not d  # anonymous: never shared
    programs.reset()


def test_program_counts_compiles_without_telemetry_sink():
    """Per-program compile counters come from the jax.monitoring
    listener and work with the null sink — the basis of the warm-cache
    accounting fix."""
    programs.reset()
    prog = programs.register_step("eval_step", jax.jit(lambda x: x * 2))
    assert isinstance(telemetry.get(), telemetry.NullTelemetry)
    assert prog.compiles == 0
    prog(jnp.ones((3,)))
    assert prog.compiles == 1
    assert prog.compile_seconds > 0.0
    prog(jnp.ones((3,)))
    assert prog.compiles == 1  # jit cache hit: no new compile
    prog(jnp.ones((4,)))
    assert prog.compiles == 2  # new shape retraces
    programs.reset()


_ARGS = "(('final_only', 'True'), ('iterations', '2'))"


def _rung_with_quant_clip(model, monkeypatch):
    monkeypatch.setenv("RMD_QUANT_CLIP", "0.9")
    return evaluation.make_rung_fn(model, 2, model_id="tiny-prog",
                                   quant="u8")


def _eval_step_with_caller_key(model, _):
    key = programs.ProgramKey("eval_step", "tiny-prog",
                              programs.flag_items(mesh=None))
    return parallel.make_eval_step(model, key=key)


# (build, canonical form) of the inference keys no budget pin covers, as
# commit ebb2877 (PR 28) produced them: a stored executable is found by
# this string, so a builder that renames or reorders a flag orphans every
# artifact of that form
_INFERENCE_KEYS = {
    "full": (
        lambda m, _: evaluation.make_eval_fn(
            m, {"iterations": 3}, model_id="tiny-prog"),
        "('eval_step', 'tiny-prog', (('args', \"(('iterations', '3'),)\"),"
        " ('mesh', 'None'), ('wire', 'None')))"),
    "wire": (
        lambda m, _: evaluation.make_eval_fn(
            m, {"final_only": True}, wire=WireFormat.from_config("u8"),
            model_id="tiny-prog"),
        f"('eval_step', 'tiny-prog', (('args', \"{_ARGS}\"),"
        " ('mesh', 'None'), ('wire', \"('u8', 'f16', True, (0.0, 1.0),"
        " (-1.0, 1.0))\")))"),
    "mesh": (
        lambda m, _: evaluation.make_eval_fn(
            m, {"final_only": True}, mesh=parallel.data_mesh(2),
            model_id="tiny-prog"),
        f"('eval_step', 'tiny-prog', (('args', \"{_ARGS}\"),"
        " ('mesh', '(0, 1)'), ('wire', 'None')))"),
    "pyid": (
        lambda m, _: evaluation.make_eval_fn(m, {"final_only": True}),
        f"('eval_step', 'pyid:<id>', (('args', \"{_ARGS}\"),"
        " ('mesh', 'None'), ('wire', 'None')))"),
    "rung_quant_clip": (
        _rung_with_quant_clip,
        f"('rung_step', 'tiny-prog', (('args', \"{_ARGS}\"),"
        " ('cont', 'False'), ('iterations', '2'), ('mesh', 'None'),"
        " ('quant', \"'u8'\"), ('quant_clip', '0.9'), ('wire', 'None')))"),
    # a caller key that lacks ``args`` gets them appended, unsorted
    "eval_step_caller_key": (
        _eval_step_with_caller_key,
        f"('eval_step', 'tiny-prog', (('mesh', 'None'),"
        f" ('args', \"{_ARGS}\")))"),
}


@pytest.mark.parametrize("case", list(_INFERENCE_KEYS))
def test_inference_keys_are_the_stored_ones(case, monkeypatch):
    build, want = _INFERENCE_KEYS[case]
    programs.reset()
    model = models.load(TINY_EVAL_MODEL).model
    got = build(model, monkeypatch).key.canonical()
    assert got.replace(str(id(model)), "<id>") == want
    programs.reset()


def test_registry_reset_alone_forgets_an_eval_program():
    """The registry is the only cache in front of the builders: after
    ``programs.reset()`` the same (model, args) builds a new program."""
    programs.reset()
    model = models.load(TINY_EVAL_MODEL).model
    first = evaluation.make_eval_fn(model, {"iterations": 2})
    assert evaluation.make_eval_fn(model, {"iterations": 2}) is first
    programs.reset()
    assert evaluation.make_eval_fn(model, {"iterations": 2}) is not first
    programs.reset()


def test_parallel_does_not_import_evaluation():
    """``evaluation`` builds on ``parallel`` and ``compile``; nothing
    points back (the step builders share ``parallel.train.inference_step``
    and the keys of ``compile``). The package's own ``__init__`` imports
    every layer, so the child puts a bare package in its place and sees
    what ``parallel`` itself pulls in."""
    import subprocess
    import sys

    import raft_meets_dicl_tpu

    code = (
        "import sys, types\n"
        "pkg = types.ModuleType('raft_meets_dicl_tpu')\n"
        f"pkg.__path__ = {list(raft_meets_dicl_tpu.__path__)!r}\n"
        "sys.modules['raft_meets_dicl_tpu'] = pkg\n"
        "import raft_meets_dicl_tpu.parallel\n"
        "assert 'raft_meets_dicl_tpu.compile' in sys.modules\n"
        "assert 'raft_meets_dicl_tpu.evaluation' not in sys.modules\n")
    subprocess.run([sys.executable, "-c", code], check=True, timeout=120,
                   env=dict(os.environ, JAX_PLATFORMS="cpu"))


def test_eval_fn_dedupes_across_validation_and_cli_paths():
    """The same (model, bucket, wire) triple builds ONE program whether
    it is requested through the eval-CLI path or the training-validation
    path — both name the model by its stable config id."""
    programs.reset()
    m_cli = models.load(TINY_EVAL_MODEL).model
    m_val = models.load(TINY_EVAL_MODEL).model  # a distinct object
    assert m_cli is not m_val

    cli = evaluation.make_eval_fn(m_cli, {"iterations": 2},
                                  model_id="tiny-prog")
    val = evaluation.make_eval_fn(m_val, {"iterations": 2},
                                  model_id="tiny-prog")
    assert cli is val

    # and the validation step builder reuses exactly that program as its
    # forward pass
    from types import SimpleNamespace

    from raft_meets_dicl_tpu.inspect.summary import StrategyValidation

    sv = StrategyValidation(1, False, "", [], None)
    ctx = SimpleNamespace(model=m_val, loss=models.load(TINY_EVAL_MODEL).loss,
                          model_id="tiny-prog")
    stage = SimpleNamespace(model_args={"iterations": 2}, loss_args={})
    step = sv._val_step(ctx, stage)
    assert step.programs[0] is cli
    assert step.programs[1].key.kind == "val_loss"
    programs.reset()


def test_val_step_matches_fused_reference():
    """The split validation step (shared forward program + loss program)
    must produce the same numbers as the pre-PR-7 fused jit."""
    from types import SimpleNamespace

    from raft_meets_dicl_tpu.inspect.summary import StrategyValidation

    programs.reset()
    spec = models.load(TINY_EVAL_MODEL)
    model, loss_fn = spec.model, spec.loss
    variables = model.init(jax.random.PRNGKey(0),
                           jnp.zeros((1, 32, 48, 3)),
                           jnp.zeros((1, 32, 48, 3)), iterations=1)

    rng = np.random.RandomState(7)
    img1 = jnp.asarray(rng.rand(2, 32, 48, 3), jnp.float32)
    img2 = jnp.asarray(rng.rand(2, 32, 48, 3), jnp.float32)
    flow = jnp.asarray(rng.randn(2, 32, 48, 2), jnp.float32)
    valid = jnp.ones((2, 32, 48), bool)

    sv = StrategyValidation(1, False, "", [], None)
    ctx = SimpleNamespace(model=model, loss=loss_fn, model_id="tiny-prog")
    stage = SimpleNamespace(model_args={"iterations": 2}, loss_args={})
    step = sv._val_step(ctx, stage)
    assert sv._val_step(ctx, stage) is step  # memoized
    est, loss = step(variables, img1, img2, flow, valid)

    out = model.apply(variables, img1, img2, train=False, iterations=2)
    result = model.get_adapter().wrap_result(out, (32, 48))
    ref_est = result.final()
    ref_loss = loss_fn(model, result.output(), flow, valid)

    # the reference runs op by op, the step as fused programs: the f32
    # convolutions accumulate in another order (2.4e-5 absolute on
    # flows of magnitude 2 under jaxlib 0.9.0); a mis-wired step is off
    # by O(1)
    np.testing.assert_allclose(np.asarray(est), np.asarray(ref_est),
                               atol=1e-4, rtol=1e-4)
    assert float(loss) == pytest.approx(float(ref_loss), rel=1e-4)
    programs.reset()


# -- AOT roundtrip --------------------------------------------------------


def _toy_step_fn():
    def fn(state, x):
        return {"w": state["w"] + x.sum()}, {"y": x * state["w"]}

    return jax.jit(fn)


def test_aot_roundtrip_bit_identical(aot_store):
    key = programs.ProgramKey("train_step", "toy-roundtrip")
    prog = programs.register_step("train_step", _toy_step_fn(), key=key)
    state = {"w": jnp.asarray(2.0)}
    x = jnp.arange(6, dtype=jnp.float32)

    s1, aux1 = prog(state, x)
    assert prog.aot_misses == 1 and prog.aot_saves == 1
    assert len(list(aot_store.glob("*.rmdp"))) == 1

    # "second boot": fresh registry, fresh jit closure, same key
    programs.reset()
    prog2 = programs.register_step("train_step", _toy_step_fn(), key=key)
    s2, aux2 = prog2(state, x)
    assert prog2.aot_hits == 1
    assert prog2.compiles == 0  # the acceptance bar: zero compiles
    assert np.array_equal(np.asarray(aux1["y"]), np.asarray(aux2["y"]))
    assert np.array_equal(np.asarray(s1["w"]), np.asarray(s2["w"]))


def test_aot_second_boot_emits_no_compile_events(aot_store):
    """With artifacts present, a registered program records 0 compile
    events in the telemetry sink on the next boot."""
    key = programs.ProgramKey("train_step", "toy-events")
    prog = programs.register_step("train_step", _toy_step_fn(), key=key)
    prog({"w": jnp.asarray(1.0)}, jnp.ones((4,)))
    assert prog.aot_saves == 1

    programs.reset()
    sink = telemetry.activate(telemetry.Telemetry())
    try:
        prog2 = programs.register_step("train_step", _toy_step_fn(),
                                       key=key)
        prog2({"w": jnp.asarray(1.0)}, jnp.ones((4,)))
        compiles = [e for e in sink.events
                    if e["kind"] == "compile"
                    and e["label"] == "train_step"]
        assert compiles == []
        aot_events = [e for e in sink.events if e["kind"] == "aot"]
        # the hit, then what its executable's text says of its owners
        assert [e["event"] for e in aot_events] == ["hit", "owners"]
        assert aot_events[0]["program"] == "train_step"
    finally:
        telemetry.deactivate()


def test_aot_artifact_per_shape_signature(aot_store):
    key = programs.ProgramKey("eval_step", "toy-shapes")
    prog = programs.register_step("eval_step", jax.jit(lambda x: x + 1),
                                  key=key)
    prog(jnp.ones((2, 3)))
    prog(jnp.ones((4, 5)))
    assert prog.aot_saves == 2
    assert len(list(aot_store.glob("*.rmdp"))) == 2


def test_aot_corrupt_artifact_falls_back(aot_store):
    key = programs.ProgramKey("train_step", "toy-corrupt")
    prog = programs.register_step("train_step", _toy_step_fn(), key=key)
    state, x = {"w": jnp.asarray(3.0)}, jnp.ones((5,))
    _, aux_ref = prog(state, x)

    artifact = next(aot_store.glob("*.rmdp"))
    blob = bytearray(artifact.read_bytes())
    blob[len(blob) // 2] ^= 0xFF  # flip a payload byte
    artifact.write_bytes(bytes(blob))

    programs.reset()
    sink = telemetry.activate(telemetry.Telemetry())
    try:
        prog2 = programs.register_step("train_step", _toy_step_fn(),
                                       key=key)
        _, aux2 = prog2(state, x)  # must not raise
        assert np.array_equal(np.asarray(aux_ref["y"]),
                              np.asarray(aux2["y"]))
        assert prog2.aot_hits == 0
        assert prog2.aot_fallbacks >= 1
        events = [e["event"] for e in sink.events if e["kind"] == "aot"]
        assert "fallback" in events
    finally:
        telemetry.deactivate()

    # truncation is also absorbed
    artifact = next(aot_store.glob("*.rmdp"))
    artifact.write_bytes(artifact.read_bytes()[:64])
    programs.reset()
    prog3 = programs.register_step("train_step", _toy_step_fn(), key=key)
    _, aux3 = prog3(state, x)
    assert np.array_equal(np.asarray(aux_ref["y"]), np.asarray(aux3["y"]))
    assert prog3.aot_hits == 0


def test_aot_version_mismatch_falls_back(aot_store):
    key = programs.ProgramKey("train_step", "toy-version")
    prog = programs.register_step("train_step", _toy_step_fn(), key=key)
    state, x = {"w": jnp.asarray(1.0)}, jnp.ones((3,))
    _, aux_ref = prog(state, x)

    artifact = next(aot_store.glob("*.rmdp"))
    record = pickle.loads(artifact.read_bytes())
    record["fingerprint"] = "jax=0.0.0 stale"
    artifact.write_bytes(pickle.dumps(record))

    programs.reset()
    prog2 = programs.register_step("train_step", _toy_step_fn(), key=key)
    _, aux2 = prog2(state, x)
    assert np.array_equal(np.asarray(aux_ref["y"]), np.asarray(aux2["y"]))
    assert prog2.aot_hits == 0 and prog2.aot_fallbacks >= 1
    # the cold compile re-saved a loadable artifact for the next boot
    assert prog2.aot_saves == 1


def test_tombstoned_program_says_so_on_every_boot(aot_store):
    """A program that cannot reload stays on JIT, but not silently: each
    boot's ``aot`` trail carries the fallback."""
    from raft_meets_dicl_tpu.compile import aot

    key = programs.ProgramKey("train_step", "toy-tombstone")
    state, x = {"w": jnp.asarray(1.0)}, jnp.ones((3,))
    sig = programs.shape_signature((state, x))
    aot_store.mkdir(parents=True, exist_ok=True)
    aot.tombstone(aot.artifact_path(key, sig))

    for _ in range(2):
        programs.reset()
        sink = telemetry.activate(telemetry.Telemetry())
        try:
            prog = programs.register_step("train_step", _toy_step_fn(),
                                          key=key)
            prog(state, x)
            events = [e for e in sink.events if e["kind"] == "aot"]
        finally:
            telemetry.deactivate()
        assert [e["event"] for e in events] == ["fallback"]
        assert "tombstoned" in events[0]["reason"]
        assert prog.aot_fallbacks == 1 and prog.aot_saves == 0


def test_failed_compile_raises_instead_of_compiling_twice(aot_store):
    """A program the compiler refuses fails the same way through plain
    jit: the registry must not answer with a second compile and a
    fallback event."""
    class Refused(Exception):
        pass

    lowerings = []

    class Lowered:
        def compile(self):
            raise Refused("Mosaic says no")

    class Step:
        def lower(self, *args):
            lowerings.append(args)
            return Lowered()

        def __call__(self, *args):
            raise AssertionError("fell back to the jit path")

    key = programs.ProgramKey("train_step", "toy-refused")
    prog = programs.register_step("train_step", Step(), key=key)
    with pytest.raises(Refused):
        prog(jnp.ones((2,)))
    assert len(lowerings) == 1 and prog.aot_fallbacks == 0


def test_runtime_failure_of_a_compiled_call_propagates(aot_store):
    """Only the executable's own argument checks (TypeError/ValueError)
    put a signature on the jit path; a failed execution raises."""
    key = programs.ProgramKey("eval_step", "toy-runtime")
    prog = programs.register_step("eval_step", jax.jit(lambda x: x + 1),
                                  key=key)
    x = jnp.ones((2,))
    prog(x)
    sig = programs.shape_signature((x,))

    def out_of_memory(*args):
        raise RuntimeError("RESOURCE_EXHAUSTED")

    prog._compiled[sig] = out_of_memory
    with pytest.raises(RuntimeError, match="RESOURCE_EXHAUSTED"):
        prog(x)
    assert prog.aot_fallbacks == 0

    def wrong_placement(*args):
        raise ValueError("input sharding does not match")

    prog._compiled[sig] = wrong_placement
    assert np.array_equal(np.asarray(prog(x)), np.asarray(x) + 1)
    assert prog.aot_fallbacks == 1


def test_cache_served_compile_is_not_counted_as_a_compile(aot_store):
    """jax 0.9 reports backend_compile_duration around the persistent
    cache lookup too; a duration that follows a cache hit is a retrieval
    and must not reach Program.compiles or the compile events."""
    from jax import monitoring

    event = "/jax/core/compile/backend_compile_duration"
    key = programs.ProgramKey("train_step", "toy-cache-served")
    prog = programs.register_step("train_step", _toy_step_fn(), key=key)
    sink = telemetry.activate(telemetry.Telemetry())
    try:
        with telemetry.jit_label("train_step", prog):
            monitoring.record_event("/jax/compilation_cache/cache_hits")
            monitoring.record_event_duration_secs(event, 0.07)
            assert prog.compiles == 0
            monitoring.record_event("/jax/compilation_cache/cache_misses")
            monitoring.record_event_duration_secs(event, 1.5)
            assert prog.compiles == 1
        kinds = [(e["kind"], e.get("event")) for e in sink.events
                 if e["kind"] in ("cache", "compile")]
        assert kinds == [("cache", "hit"), ("cache", "miss"),
                         ("compile", None)]
    finally:
        telemetry.deactivate()


def test_aot_roundtrip_of_a_one_device_program_on_a_many_device_host(
        aot_store):
    """The artifact records its device assignment: jax 0.9 loads onto
    every device of the backend unless told otherwise, and a one-device
    executable then rejects its arguments (8 virtual devices here)."""
    key = programs.ProgramKey("eval_step", "toy-devices")
    dev = jax.devices()[-1]
    x = jax.device_put(jnp.arange(4.0), dev)
    prog = programs.register_step("eval_step", jax.jit(lambda x: x * 2),
                                  key=key)
    prog(x)
    assert prog.aot_saves == 1

    programs.reset()
    prog2 = programs.register_step("eval_step", jax.jit(lambda x: x * 2),
                                   key=key)
    out = prog2(x)
    assert prog2.aot_hits == 1 and prog2.aot_fallbacks == 0
    assert prog2.compiles == 0
    assert out.devices() == {dev}
    assert np.array_equal(np.asarray(out), 2 * np.arange(4.0))


def test_aot_train_step_roundtrip_through_builder(aot_store):
    """End-to-end through parallel.make_train_step: a keyed tiny train
    step saves its executable; a fresh build reloads it with zero
    compiles and bit-identical parameter updates."""
    import optax

    spec = models.load(TINY_EVAL_MODEL)
    model, loss = spec.model, spec.loss
    variables = jax.tree.map(np.asarray, model.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 32, 48, 3)),
        jnp.zeros((1, 32, 48, 3)), iterations=1))
    tx = optax.adam(1e-3)

    rng = np.random.RandomState(0)
    batch = tuple(jnp.asarray(v) for v in (
        rng.rand(2, 32, 48, 3).astype(np.float32),
        rng.rand(2, 32, 48, 3).astype(np.float32),
        rng.randn(2, 32, 48, 2).astype(np.float32),
        np.ones((2, 32, 48), bool)))
    key = programs.ProgramKey(
        "train_step", "tiny-prog",
        programs.flag_items(shape=(2, 32, 48), iterations=2))

    def build_and_step():
        state = parallel.TrainState.create(
            jax.tree.map(jnp.asarray, variables), tx)
        step = parallel.make_train_step(model, loss, tx,
                                        model_args={"iterations": 2},
                                        key=key)
        new_state, aux = step(state, *batch)
        return step, new_state, float(aux["loss"])

    step1, state1, loss1 = build_and_step()
    assert step1.aot_saves == 1

    programs.reset()
    step2, state2, loss2 = build_and_step()
    assert step2.aot_hits == 1 and step2.compiles == 0
    assert loss1 == loss2
    for a, b in zip(jax.tree.leaves(state1.params),
                    jax.tree.leaves(state2.params)):
        assert np.array_equal(np.asarray(a), np.asarray(b))


# -- warm-cache compile accounting (overcount bugfix) ---------------------


def test_warmup_compiles_not_overcounted_when_warm(aot_store):
    """Second warmup over the same shapes reports 0 compiles — with the
    telemetry sink disabled, where the pre-PR-7 fallback guessed 1 per
    shape."""
    model = models.load(TINY_EVAL_MODEL).model
    variables = model.init(jax.random.PRNGKey(0),
                           jnp.zeros((1, 32, 48, 3)),
                           jnp.zeros((1, 32, 48, 3)), iterations=1)
    fn = evaluation.make_eval_fn(model, {"iterations": 2},
                                 model_id="tiny-prog-warm")
    assert isinstance(telemetry.get(), telemetry.NullTelemetry)

    cold = evaluation.EvalRunStats(name="cold")
    evaluation.warmup_eval_fn(fn, variables, [(32, 48), (24, 40)], 2,
                              stats=cold)
    assert cold.compiles == 2

    warm = evaluation.EvalRunStats(name="warm")
    evaluation.warmup_eval_fn(fn, variables, [(32, 48), (24, 40)], 2,
                              stats=warm)
    assert warm.compiles == 0
    assert warm.phases.get("warmup", 0.0) > 0.0
    programs.reset()


# -- compcache satellite --------------------------------------------------


@pytest.fixture
def cache_config(monkeypatch):
    """Restore jax's cache options and compcache's state after a test;
    start with none of the placement variables set (the driver may
    export JAX_COMPILATION_CACHE_DIR)."""
    from raft_meets_dicl_tpu.utils import compcache

    names = ("jax_compilation_cache_dir",
             "jax_persistent_cache_min_entry_size_bytes",
             "jax_persistent_cache_min_compile_time_secs")
    saved = {n: getattr(jax.config, n) for n in names}
    for var in (compcache.EXTERNAL_VAR, "RMD_COMPILE_CACHE",
                "RMD_NO_COMPILE_CACHE"):
        monkeypatch.delenv(var, raising=False)
    monkeypatch.setattr(compcache, "_configured", None)
    yield compcache
    for n, v in saved.items():
        jax.config.update(n, v)


def test_compile_cache_precedence_without_external_dir(tmp_path, monkeypatch,
                                                       cache_config):
    compcache = cache_config
    monkeypatch.setenv("RMD_COMPILE_CACHE", str(tmp_path / "env-cache"))
    got = compcache.enable_persistent_cache()
    assert got == str(tmp_path / "env-cache")
    assert compcache.effective_dir() == got
    assert jax.config.jax_compilation_cache_dir == got
    assert os.path.isdir(got)

    # an explicit path (the --compile-cache flag) wins over the env
    got = compcache.enable_persistent_cache(str(tmp_path / "cli-cache"))
    assert got == str(tmp_path / "cli-cache")
    assert compcache.effective_dir() == got

    # neither: the repo-local default
    monkeypatch.delenv("RMD_COMPILE_CACHE")
    monkeypatch.setattr(compcache, "DEFAULT_DIR", str(tmp_path / "default"))
    assert compcache.enable_persistent_cache() == str(tmp_path / "default")

    # kill switch
    monkeypatch.setenv("RMD_NO_COMPILE_CACHE", "1")
    assert compcache.enable_persistent_cache() is None
    assert compcache.effective_dir() is None


def test_external_cache_dir_wins_and_is_not_set_in_code(tmp_path,
                                                        monkeypatch,
                                                        cache_config):
    compcache = cache_config
    external = str(tmp_path / "placed-from-outside")
    monkeypatch.setenv(compcache.EXTERNAL_VAR, external)
    monkeypatch.setenv("RMD_COMPILE_CACHE", str(tmp_path / "env-cache"))
    monkeypatch.setattr(compcache, "DEFAULT_DIR", str(tmp_path / "default"))
    before = jax.config.jax_compilation_cache_dir

    # flag, knob and kill switch all yield; the option is left to jax
    assert compcache.enable_persistent_cache(
        str(tmp_path / "cli-cache")) == external
    monkeypatch.setenv("RMD_NO_COMPILE_CACHE", "1")
    assert compcache.enable_persistent_cache() == external
    assert compcache.effective_dir() == external
    assert jax.config.jax_compilation_cache_dir == before
    for name in ("cli-cache", "env-cache", "default"):
        assert not (tmp_path / name).exists()

    # the AOT store follows it
    monkeypatch.delenv("RMD_AOT", raising=False)
    monkeypatch.delenv("RMD_AOT_DIR", raising=False)
    try:
        assert programs.enable_aot() == os.path.join(external, "programs")
    finally:
        programs.disable_aot()


def test_unusable_cache_dir_raises(tmp_path, cache_config):
    blocker = tmp_path / "file"
    blocker.write_text("not a directory")
    with pytest.raises(OSError):
        cache_config.enable_persistent_cache(str(blocker / "cache"))


def test_aot_dir_defaults_next_to_compile_cache(tmp_path, monkeypatch,
                                                cache_config):
    monkeypatch.delenv("RMD_AOT", raising=False)
    monkeypatch.delenv("RMD_AOT_DIR", raising=False)
    monkeypatch.setattr(cache_config, "_configured", str(tmp_path / "cc"))
    try:
        got = programs.enable_aot()
        assert got == os.path.join(str(tmp_path / "cc"), "programs")
        assert programs.aot_enabled()
        # RMD_AOT=0 wins
        monkeypatch.setenv("RMD_AOT", "0")
        assert programs.enable_aot() is None
        assert not programs.aot_enabled()
    finally:
        programs.disable_aot()


# -- telemetry schema + report --------------------------------------------


def test_boot_and_aot_event_schema():
    def ev(kind, **f):
        return {"v": telemetry.SCHEMA_VERSION, "t": 0.0, "kind": kind, **f}

    telemetry.validate_event(ev("boot", compile_cache=None, aot_dir=None,
                                aot=False, prefetch=True))
    telemetry.validate_event(ev("aot", event="hit", program="train_step",
                                model="m", bytes=10, seconds=0.1))
    with pytest.raises(ValueError):
        telemetry.validate_event(ev("aot"))  # event field required
    with pytest.raises(ValueError):
        telemetry.validate_event(ev("boot"))


def test_report_compiled_programs_section_and_anomaly():
    from raft_meets_dicl_tpu.telemetry import report

    def ev(kind, **f):
        return {"v": telemetry.SCHEMA_VERSION, "t": 0.0, "kind": kind, **f}

    events = [
        ev("boot", compile_cache="/tmp/cc", aot_dir="/tmp/cc/programs",
           aot=True),
        ev("aot", event="save", program="train_step", model="m",
           bytes=2 ** 20, seconds=0.2),
        ev("aot", event="hit", program="eval_step", model="m",
           bytes=2 ** 19, seconds=0.05),
        ev("aot", event="fallback", program="eval_step", model="m",
           reason="corrupt: crc mismatch"),
    ]
    stats = report.aot_stats(events)
    assert stats["boot"]["compile_cache"] == "/tmp/cc"
    assert stats["programs"][("train_step", "m")]["save"] == 1
    assert stats["programs"][("eval_step", "m")]["hit"] == 1
    assert stats["programs"][("eval_step", "m")]["fallback"] == 1

    text = report.render(events)
    assert "compiled programs" in text
    assert "/tmp/cc/programs" in text
    assert "1 AOT hits" in text

    flags = report.find_anomalies(events)
    assert any("AOT fallback to cold JIT" in f for f in flags)
    # a clean boot (no fallback) raises no AOT flag
    clean = [e for e in events if e.get("event") != "fallback"]
    assert not any("AOT" in f for f in report.find_anomalies(clean))


# -- prefetch -------------------------------------------------------------


def _batches(n):
    return [(np.full((1,), i), np.full((1,), i), None, None, [i])
            for i in range(n)]


def test_prefetched_stream_is_the_iterator_with_put_applied():
    """What the training loop runs (depth 2): item for item and in order
    the plain iterator with ``put`` applied once to each batch, the host
    arrays handed through as they came, however far the worker runs ahead
    of a slow consumer."""
    import time

    from raft_meets_dicl_tpu.strategy.training import _device_prefetch

    items = _batches(7)
    calls = []

    def put(host):
        calls.append(int(host[0][0]))
        return tuple(None if a is None else a * 10 + 1 for a in host)

    stream = _device_prefetch(iter(items), put, depth=2)
    got = []
    for out in stream:
        got.append(out)
        time.sleep(0.005)     # the worker fills its queue meanwhile
        # the bounded queue: two staged, one in the worker's hand
        assert len(calls) <= len(got) + 3
    assert calls == list(range(7))
    assert len(got) == len(items)
    for (host, dev, meta, _put, _pull), item in zip(got, items):
        assert all(a is b for a, b in zip(host, item[:4]))
        assert meta is item[4]
        want = put(item[:4])
        assert all(np.array_equal(a, b) if a is not None else b is None
                   for a, b in zip(dev, want))


def test_a_put_that_raises_on_the_worker_surfaces_at_its_own_batch():
    """``put`` runs on the worker's thread: its exception comes out of the
    consumer's ``next()`` for the batch it failed on, after every batch
    before it, and ends the stream."""
    from raft_meets_dicl_tpu.strategy.training import _device_prefetch

    pulled = []

    def source():
        for item in _batches(5):
            pulled.append(item[4][0])
            yield item

    def put(host):
        if int(host[0][0]) == 2:
            raise ValueError("no room on the device")
        return host

    stream = _device_prefetch(source(), put, depth=2)
    assert [next(stream)[2] for _ in range(2)] == [[0], [1]]
    with pytest.raises(ValueError, match="no room on the device"):
        next(stream)
    # the worker stopped at the failure: nothing was pulled behind it
    assert pulled == [0, 1, 2]
    with pytest.raises(StopIteration):
        next(stream)


def test_prefetch_depth_knob(monkeypatch):
    """The prefetch generator respects depth and re-raises loader
    errors at the consumption point."""
    from raft_meets_dicl_tpu.strategy.training import _device_prefetch

    items = _batches(4)
    got = list(_device_prefetch(iter(items), lambda b: ("dev",) + b,
                                depth=1))
    assert [m for _, _, m, _put, _pull in got] == [[0], [1], [2], [3]]
    assert all(dev[0] == "dev" for _, dev, _, _put, _pull in got)
    # each batch carries the interval of its own put, in order
    puts = [put for *_, put, _pull in got]
    assert all(t0 <= t1 for t0, t1 in puts)
    assert all(a[1] <= b[0] for a, b in zip(puts, puts[1:]))

    def boom():
        yield items[0]
        raise RuntimeError("loader died")

    it = _device_prefetch(boom(), lambda b: b)
    next(it)
    with pytest.raises(RuntimeError, match="loader died"):
        next(it)
