"""Trace-time notes of ``raft/fs``: which form each windowed-correlation
call took, how many levels are computed on the fly, and the bytes of the
volumes that are materialised.

As the window sampler's counts (``tests/test_sw_path_counters.py``) they
belong to the program whose trace noted them: they ride in its
``compile`` event, in the ``aot`` events that hold its executable and in
the next ``step`` event's counters, and a boot that loads the executable
from the store reads them from the artifact.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from raft_meets_dicl_tpu import compile as programs
from raft_meets_dicl_tpu import telemetry
from raft_meets_dicl_tpu.models.common.grid import coordinate_grid
from raft_meets_dicl_tpu.models.impls.raft_fs import (RaftFs,
                                                      volume_level_split)
from raft_meets_dicl_tpu.ops import pallas as pk

ITERATIONS, LEVELS = 3, 4
SHAPE = (1, 64, 96)
GRID = (1, 8, 12)
NOTES = ("wcp_fused_calls", "wcp_fallback_calls", "wcp_levels_windowed",
         "corr_volume_bytes")


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


def _volume_bytes(n_windowed):
    """Float32 volumes of the levels past the windowed prefix."""
    b, h, w = GRID
    return sum(4 * b * h * w * (h >> l) * (w >> l)
               for l in range(n_windowed, LEVELS))


def _budget(n_windowed):
    """An ``RMD_FS_VOLUME_GIB`` that leaves ``n_windowed`` levels on the
    windowed form at the toy grid (the budget charges a volume twice)."""
    gib = (2 * _volume_bytes(n_windowed) + 8) / 2 ** 30
    assert volume_level_split(GRID, LEVELS, 4, gib) == n_windowed
    return gib


def _fs_train_step(key=None):
    """The model's train step at toy widths, through the builder the
    training loop uses, with its state and one batch."""
    import optax

    from raft_meets_dicl_tpu import models, parallel

    spec = models.load({
        "name": "toy fs", "id": "toy/fs",
        "model": {"type": "raft/fs",
                  "parameters": {"corr-channels": 32, "context-channels": 32,
                                 "recurrent-channels": 32},
                  "arguments": {"iterations": ITERATIONS}},
        "loss": {"type": "raft/sequence"},
        "input": {"clip": [0, 1], "range": [-1, 1]}})
    model = spec.model
    model.frozen_batchnorm = True
    b, h, w = SHAPE
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
    return step, state, batch


def _want(n_windowed, path="wcp_fallback_calls"):
    """One call an iteration where a level is windowed, none where every
    level is a volume; the levels unscaled by the iterations."""
    want = dict.fromkeys(NOTES)
    want["wcp_levels_windowed"] = n_windowed
    want["corr_volume_bytes"] = _volume_bytes(n_windowed)
    if n_windowed:
        want[path] = ITERATIONS
    return want


@pytest.mark.parametrize("n_windowed", [0, 2, 4])
def test_fs_train_step_notes_stand_for_every_iteration_and_survive_a_load(
        aot_store, sink, monkeypatch, n_windowed):
    monkeypatch.setenv("RMD_FS_VOLUME_GIB", repr(_budget(n_windowed)))
    key = programs.ProgramKey("train_step", f"toy-fs-windowed{n_windowed}")
    step, state, batch = _fs_train_step(key)
    want = _want(n_windowed)       # off the TPU a call is a fallback
    _, cold = step(state, *batch)
    sink.step_event(0)
    compiles = [e for e in sink.events if e["kind"] == "compile"
                and e["label"] == "train_step"]
    assert len(compiles) == 1
    assert {n: compiles[0].get(n) for n in NOTES} == want
    counters = [e for e in sink.events if e["kind"] == "step"][-1]["counters"]
    assert counters["wcp_levels_windowed"] == n_windowed
    assert counters.get("wcp_fallback_calls") == want["wcp_fallback_calls"]

    # "second boot": the executable and its notes come from the store
    programs.reset()
    del sink.events[:]
    step2, state, batch = _fs_train_step(key)
    _, warm = step2(state, *batch)
    sink.step_event(1)
    assert step2.aot_hits == 1 and step2.compiles == 0
    hits = [e for e in sink.events if e["kind"] == "aot"
            and e["event"] == "hit"]
    assert len(hits) == 1 and {n: hits[0].get(n) for n in NOTES} == want
    counters = [e for e in sink.events if e["kind"] == "step"][-1]["counters"]
    assert counters["wcp_levels_windowed"] == n_windowed
    assert float(cold["loss"]) == float(warm["loss"])


@pytest.mark.parametrize("n_windowed", [1, 4])
def test_fs_train_step_traced_for_the_tpu_takes_the_kernel(monkeypatch,
                                                            n_windowed):
    """What the chip's program notes, from its trace alone: the dispatch
    asks ``jax.default_backend`` while it traces, and nothing compiles."""
    monkeypatch.setenv("RMD_FS_VOLUME_GIB", repr(_budget(n_windowed)))
    step, state, batch = _fs_train_step()
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    with telemetry.jit_label(step.label, step):
        jax.eval_shape(step.__wrapped__, state, *batch)
    want = _want(n_windowed, "wcp_fused_calls")
    assert step.trace_counts() == {k: v for k, v in want.items()
                                   if v is not None}


@pytest.mark.parametrize("shape, levels, radius, fused", [
    ((1, 136, 240, 256), 1, 4, True),     # the cell: level 0 of 1088x1920
    ((1, 134, 320, 256), 4, 4, True),     # the recipe's frame, every level
    ((1, 136, 240, 256), 1, 8, False),    # the slab covers radius <= 7
    ((1, 272, 480, 256), 1, 4, False),    # the padded map exceeds VMEM
])
def test_a_call_that_fails_the_vmem_check_is_one_fallback(
        sink, monkeypatch, shape, levels, radius, fused):
    b, h, w, c = shape
    f1 = jax.ShapeDtypeStruct(shape, jnp.bfloat16)
    f2 = tuple(jax.ShapeDtypeStruct((b, h >> l, w >> l, c), jnp.bfloat16)
               for l in range(levels))
    coords = jax.ShapeDtypeStruct((b, h, w, 2), jnp.float32)
    assert pk._wcp_fits_vmem(f1, f2, radius) is fused
    # off the TPU every call takes the XLA composition
    assert not pk._wcp_takes_kernel(f1, f2, radius)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert pk._wcp_takes_kernel(f1, f2, radius) is fused

    prog = programs.register_step("probe", jax.jit(
        lambda a, bb, cc: pk.windowed_corr_pyramid(a, bb, cc, radius)))
    with telemetry.jit_label(prog.label, prog):
        jax.eval_shape(prog.__wrapped__, f1, f2, coords)
    assert prog.trace_counts() == {
        "wcp_fused_calls" if fused else "wcp_fallback_calls": 1}


def test_counts_outside_a_program_are_dropped(sink):
    f = jnp.ones((1, 5, 7, 4))
    pk.windowed_corr_pyramid(f, (f,), coordinate_grid(1, 5, 7), 1)
    sink.step_event(0)
    assert "counters" not in sink.events[-1]


def test_the_models_notes_revision_is_in_its_keys():
    assert RaftFs.notes_revision == 3
    key = programs.inference_key("eval_step", RaftFs(), {},
                                 model_id="raft/fs")
    assert ("notes", "3") in key.flags
