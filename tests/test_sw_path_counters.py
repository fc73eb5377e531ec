"""Trace-time counts of the DICL matching path: which form each window
sampler call took, and the bytes the matching feeds its cost net.

The counts belong to the program whose trace noted them
(``telemetry.note_trace``): they ride in its ``compile`` event, in the
``aot`` events that hold its executable and in the next ``step`` event's
counters, and a boot that loads the executable from the store (and never
traces) reads them from the artifact.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from raft_meets_dicl_tpu import compile as programs
from raft_meets_dicl_tpu import telemetry
from raft_meets_dicl_tpu.models.common.corr.dicl import CorrelationModule
from raft_meets_dicl_tpu.models.common.grid import coordinate_grid
from raft_meets_dicl_tpu.ops import pallas as pk

B, H, W, C, R = 2, 6, 8, 8, 2
ITERATIONS = 3


@pytest.fixture
def aot_store(tmp_path, monkeypatch):
    monkeypatch.delenv("RMD_AOT", raising=False)
    monkeypatch.delenv("RMD_AOT_DIR", raising=False)
    programs.reset()
    programs.enable_aot(str(tmp_path / "programs"))
    yield tmp_path / "programs"
    programs.disable_aot()
    programs.reset()


@pytest.fixture
def sink():
    sink = telemetry.activate(telemetry.Telemetry())
    yield sink
    telemetry.deactivate()


def _matching_step():
    """A scan of ``ITERATIONS`` matching calls inside one trace site, the
    way the coarse-to-fine model runs a level."""
    rs = np.random.RandomState(0)
    f1 = jnp.asarray(rs.randn(B, H, W, C), jnp.float32)
    f2 = jnp.asarray(rs.randn(B, H, W, C), jnp.float32)
    coords = coordinate_grid(B, H, W)
    cmod = CorrelationModule(feature_dim=C, radius=R, dtype=jnp.bfloat16)
    variables = cmod.init(jax.random.PRNGKey(0), f1, f2, coords)

    def step(variables, shift):
        def body(carry, _):
            cost = cmod.apply(variables, f1, f2, coords + carry)
            return carry + 0.0 * cost.mean(), cost.sum()

        with telemetry.trace_site("level", ITERATIONS):
            # visited twice, as flax's lifted scan visits its body
            jax.eval_shape(body, shift, None)
            _, sums = jax.lax.scan(body, shift, None, length=ITERATIONS)
        return sums

    window_bytes = 2 * B * H * W * C * (1 + (2 * R + 1) ** 2)
    return jax.jit(step), variables, ITERATIONS * window_bytes


def _counts(event):
    return {k: event.get(k) for k in ("sw_fused_calls", "sw_fallback_calls",
                                      "matching_volume_bytes")}


def test_counts_ride_compile_and_aot_events_and_survive_a_warm_load(
        aot_store, sink):
    fn, variables, volume = _matching_step()
    key = programs.ProgramKey("train_step", "toy-matching")
    want = {"sw_fused_calls": None, "sw_fallback_calls": ITERATIONS,
            "matching_volume_bytes": volume}

    prog = programs.register_step("train_step", fn, key=key)
    cold = prog(variables, jnp.float32(0.0))
    sink.step_event(0)
    compiles = [e for e in sink.events if e["kind"] == "compile"
                and e["label"] == "train_step"]
    assert len(compiles) == 1 and _counts(compiles[0]) == want
    saves = [e for e in sink.events if e["kind"] == "aot"
             and e["event"] == "save"]
    assert len(saves) == 1 and _counts(saves[0]) == want
    step = [e for e in sink.events if e["kind"] == "step"][-1]
    assert step["counters"]["matching_volume_bytes"] == volume
    assert step["counters"]["sw_fallback_calls"] == ITERATIONS

    # "second boot": the executable comes from the store, nothing traces
    programs.reset()
    del sink.events[:]
    fn2, _, _ = _matching_step()
    prog2 = programs.register_step("train_step", fn2, key=key)
    warm = prog2(variables, jnp.float32(0.0))
    sink.step_event(1)
    assert prog2.aot_hits == 1 and prog2.compiles == 0
    assert not [e for e in sink.events if e["kind"] == "compile"
                and e["label"] == "train_step"]
    hits = [e for e in sink.events if e["kind"] == "aot"
            and e["event"] == "hit"]
    assert len(hits) == 1 and _counts(hits[0]) == want
    step = [e for e in sink.events if e["kind"] == "step"][-1]
    assert step["counters"]["matching_volume_bytes"] == volume
    np.testing.assert_array_equal(np.asarray(cold), np.asarray(warm))


def test_counts_outside_a_site_add_up_and_outside_a_program_are_dropped(sink):
    f2 = jnp.ones((1, 5, 7, 4))
    coords = coordinate_grid(1, 5, 7)

    pk.sample_window_fused(f2, coords, 1)            # eager: no program
    sink.step_event(0)
    assert "counters" not in sink.events[-1]

    prog = programs.register_step("probe", jax.jit(
        lambda a, c: pk.sample_window_fused(a, c, 1)
        + pk.sample_window_fused(a, c + 1.0, 1)))
    prog(f2, coords)
    sink.step_event(1)
    assert sink.events[-1]["counters"] == {"sw_fallback_calls": 2}
    prog(f2, coords)                                 # no trace, no counts
    sink.step_event(2)
    assert "counters" not in sink.events[-1]


@pytest.mark.parametrize("shape, radius, fused", [
    ((6, 48, 88, 32), 4, True),        # the cell's finest level
    ((6, 48, 88, 32), 8, False),       # the bodies unroll for radius <= 7
    ((1, 248, 440, 32), 4, True),      # the largest 9:16 map Mosaic takes
    ((1, 255, 455, 32), 4, False),     # ... and the first it refuses
    ((1, 1600, 2400, 32), 4, False),   # the padded map exceeds VMEM
])
def test_a_call_that_fails_the_vmem_check_is_a_fallback(monkeypatch, shape,
                                                        radius, fused):
    f2 = jax.ShapeDtypeStruct(shape, jnp.bfloat16)
    coords = jax.ShapeDtypeStruct((*shape[:3], 2), jnp.float32)
    assert pk._sw_fits_vmem(f2, coords, radius) is fused
    # off the TPU every call takes the XLA reference
    assert not pk._sw_takes_kernel(f2, coords, radius)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert pk._sw_takes_kernel(f2, coords, radius) is fused


# -- raft+dicl/ml: four levels in every iteration of one scan ----------------

ML_ITERATIONS, ML_LEVELS, ML_RADIUS, ML_CHANNELS = 3, 4, 2, 8
ML_SHAPE = (1, 64, 128)


def _ml_train_step(share, key=None):
    """The multi-level model's train step at toy widths, through the
    builder the training loop uses, with its state and one batch."""
    import optax

    from raft_meets_dicl_tpu import models, parallel

    spec = models.load({
        "name": "toy ml", "id": "toy/ml",
        "model": {"type": "raft+dicl/ml",
                  "parameters": {"corr-radius": ML_RADIUS,
                                 "corr-channels": ML_CHANNELS,
                                 "share-dicl": share},
                  "arguments": {"iterations": ML_ITERATIONS}},
        "loss": {"type": "raft/sequence"},
        "input": {"clip": [0, 1], "range": [-1, 1]}})
    model = spec.model
    model.frozen_batchnorm = True
    b, h, w = ML_SHAPE
    variables = model.init(jax.random.PRNGKey(0), jnp.zeros((1, h, w, 3)),
                           jnp.zeros((1, h, w, 3)), iterations=1)
    tx = optax.adam(1e-3)
    state = parallel.TrainState.create(variables, tx)
    step = parallel.make_train_step(model, spec.loss, tx, donate=False,
                                    key=key)
    rs = np.random.RandomState(0)
    batch = (jnp.asarray(rs.rand(b, h, w, 3), jnp.float32),
             jnp.asarray(rs.rand(b, h, w, 3), jnp.float32),
             jnp.asarray(rs.randn(b, h, w, 2), jnp.float32),
             jnp.ones((b, h, w), bool))
    # an iteration feeds the nets frame one's stack and one window a level
    # (float32 here: the toy model states no bf16 policy)
    b, h8, w8 = b, h // 8, w // 8
    volume = 4 * ML_LEVELS * b * h8 * w8 * ML_CHANNELS * (
        1 + (2 * ML_RADIUS + 1) ** 2)
    return step, state, batch, ML_ITERATIONS * volume


ML_NOTES = ("sw_fused_calls", "sw_fallback_calls", "matching_volume_bytes",
            "matching_levels_batched")


@pytest.mark.parametrize("share, want", [
    # off the TPU the shared net takes the fast path (its sampler calls the
    # XLA reference), the per-level nets the loop and the plain sampler
    (True, {"sw_fallback_calls": ML_LEVELS * ML_ITERATIONS,
            "matching_levels_batched": ML_LEVELS}),
    (False, {"matching_levels_batched": 1}),
])
def test_ml_train_step_notes_stand_for_every_iteration_and_survive_a_load(
        aot_store, sink, share, want):
    key = programs.ProgramKey("train_step", f"toy-ml-share{share}")
    step, state, batch, volume = _ml_train_step(share, key)
    want = {n: want.get(n) for n in ML_NOTES} | {
        "matching_volume_bytes": volume}
    _, cold = step(state, *batch)
    sink.step_event(0)
    compiles = [e for e in sink.events if e["kind"] == "compile"
                and e["label"] == "train_step"]
    assert len(compiles) == 1
    assert {n: compiles[0].get(n) for n in ML_NOTES} == want
    counters = [e for e in sink.events if e["kind"] == "step"][-1]["counters"]
    assert counters["matching_volume_bytes"] == volume
    assert counters["matching_levels_batched"] == want[
        "matching_levels_batched"]

    # "second boot": the executable and its notes come from the store
    programs.reset()
    del sink.events[:]
    step2, state, batch, _ = _ml_train_step(share, key)
    _, warm = step2(state, *batch)
    sink.step_event(1)
    assert step2.aot_hits == 1 and step2.compiles == 0
    hits = [e for e in sink.events if e["kind"] == "aot"
            and e["event"] == "hit"]
    assert len(hits) == 1 and {n: hits[0].get(n) for n in ML_NOTES} == want
    counters = [e for e in sink.events if e["kind"] == "step"][-1]["counters"]
    assert counters["matching_levels_batched"] == want[
        "matching_levels_batched"]
    assert float(cold["loss"]) == float(warm["loss"])


def test_ml_train_step_traced_for_the_tpu_takes_the_kernel_on_every_level(
        monkeypatch):
    """What the chip's program notes, from its trace alone: the dispatch
    asks ``jax.default_backend`` while it traces, and nothing compiles."""
    step, state, batch, volume = _ml_train_step(share=False)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    with telemetry.jit_label(step.label, step):
        jax.eval_shape(step.__wrapped__, state, *batch)
    assert step.trace_counts() == {
        "sw_fused_calls": ML_LEVELS * ML_ITERATIONS,
        "matching_levels_batched": ML_LEVELS,
        "matching_volume_bytes": volume}


def test_a_model_that_counts_its_notes_revisions_has_them_in_its_keys():
    from raft_meets_dicl_tpu import models
    from raft_meets_dicl_tpu.models.impls.raft_dicl_ml import RaftPlusDiclMl

    assert RaftPlusDiclMl.notes_revision == 2
    ml = RaftPlusDiclMl()
    key = programs.inference_key("eval_step", ml, {}, model_id="raft+dicl/ml")
    assert ("notes", "2") in key.flags
    # every other model's keys are byte for byte what they were
    raft = models.load({"name": "r", "id": "r", "model": {
        "type": "raft/baseline", "parameters": {}}, "loss": {
        "type": "raft/sequence"}, "input": {}}).model
    assert programs.notes_flag(raft) == {}
    key = programs.inference_key("eval_step", raft, {}, model_id="r")
    assert not [f for f in key.flags if f[0] == "notes"]
