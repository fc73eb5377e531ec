"""Iteration-ladder tests: rung programs, escalation policy, classes.

The device half pins the load-bearing invariant — chained rungs are
bit-exact against the monolithic budget in f32, because the models carry
``(hidden, flow)`` across iterations and a program boundary is a no-op
in that carry — plus the delta-norm semantics and the zero-compile
class-serving contract. The policy half (LadderSpec validation, the
balanced escalation loop, scheduler class plumbing and per-class
telemetry) runs against host-only fakes.
"""

import numpy as np
import pytest

import raft_meets_dicl_tpu.models as models
from raft_meets_dicl_tpu import evaluation, serve, telemetry
from raft_meets_dicl_tpu import compile as programs
from raft_meets_dicl_tpu.models.input import ShapeBuckets
from raft_meets_dicl_tpu.serve import LadderSpec, Scheduler, ServeError
from raft_meets_dicl_tpu.serve.session import ServeSession
from raft_meets_dicl_tpu.telemetry import report as treport

pytestmark = pytest.mark.ladder

TINY_LADDER_MODEL = {
    "name": "ladder tiny", "id": "ladder-tiny",
    "model": {"type": "raft/baseline",
              "parameters": {"corr-levels": 2, "corr-radius": 2,
                             "corr-channels": 32, "context-channels": 16,
                             "recurrent-channels": 16}},
    "loss": {"type": "raft/sequence"},
    "input": {"padding": {"type": "modulo", "mode": "zeros",
                          "size": [8, 8]}},
}


# -- LadderSpec: parsing + validation -----------------------------------------


def test_ladder_spec_defaults_and_parsing(monkeypatch):
    assert LadderSpec().rungs == (4, 8, 12)
    assert LadderSpec.from_config("2, 4,6").rungs == (2, 4, 6)
    assert LadderSpec.from_config([2, 5]).rungs == (2, 5)
    assert LadderSpec.from_config("2,4", threshold=0.25).threshold == 0.25
    monkeypatch.setenv("RMD_LADDER", "3,9")
    monkeypatch.setenv("RMD_LADDER_THRESHOLD", "0.5")
    spec = LadderSpec.from_config(True)
    assert spec.rungs == (3, 9) and spec.threshold == 0.5


@pytest.mark.parametrize("kwargs", [
    {"rungs": (12,)},              # a ladder needs at least two rungs
    {"rungs": (0, 4)},             # budgets must be positive
    {"rungs": (4, 4, 8)},          # strictly ascending
    {"rungs": (8, 4)},
    {"rungs": (4, 8), "threshold": 0.0},
])
def test_ladder_spec_rejects_degenerate(kwargs):
    with pytest.raises(ValueError):
        LadderSpec(**kwargs)


def test_ladder_programs_one_per_distinct_increment():
    # uniform increments collapse to a single continuation program
    assert LadderSpec(rungs=(4, 8, 12)).programs() == [
        (4, False), (12, False), (4, True)]
    # mixed increments: one continuation per distinct step size
    assert LadderSpec(rungs=(2, 4, 8)).programs() == [
        (2, False), (8, False), (2, True), (4, True)]
    assert LadderSpec(rungs=(2, 4, 8)).increments() == (2, 4)


# -- escalation policy: host-only against fake rung programs ------------------


class _Stub:
    """Bare object carrying just what ServeSession.run_ladder reads."""


def _policy_session(deltas, rungs=(2, 4, 8), threshold=0.5):
    """A stub whose fake rung programs pop scripted post-rung deltas and
    record every (iterations, cont) execution."""
    stub = _Stub()
    stub.ladder = LadderSpec(rungs=rungs, threshold=threshold)
    stub.variables = None
    stub.calls = []
    queue = list(deltas)

    def rung(its, cont):
        def fn(variables, img1, img2, *carry):
            stub.calls.append((its, cont, len(carry)))
            state = {"flow": np.full((1, 4, 6, 2), len(stub.calls), np.float32),
                     "hidden": np.zeros((1, 4, 6, 3), np.float32),
                     "delta": np.asarray([queue.pop(0)], np.float32)}
            return np.zeros((1, 32, 48, 2), np.float32), state
        return fn

    stub._rung_fns = {(its, cont): rung(its, cont)
                      for its, cont in stub.ladder.programs()}
    img = np.zeros((1, 32, 48, 3), np.float32)
    return stub, img


def test_fast_and_quality_are_single_programs():
    stub, img = _policy_session(deltas=[9.0])
    flow, info = ServeSession.run_ladder(stub, img, img, "fast")
    assert info == {"rungs": 1, "iterations": 2}
    assert stub.calls == [(2, False, 0)]

    stub, img = _policy_session(deltas=[9.0])
    flow, info = ServeSession.run_ladder(stub, img, img, "quality")
    assert info == {"rungs": 1, "iterations": 8}
    assert stub.calls == [(8, False, 0)]


def test_balanced_stops_when_delta_converges():
    # base delta already under threshold: no escalation
    stub, img = _policy_session(deltas=[0.4])
    _, info = ServeSession.run_ladder(stub, img, img, "balanced")
    assert info == {"rungs": 1, "iterations": 2}
    assert stub.calls == [(2, False, 0)]

    # converges after one continuation: the +4 rung never runs
    stub, img = _policy_session(deltas=[0.9, 0.4, 0.9])
    _, info = ServeSession.run_ladder(stub, img, img, "balanced")
    assert info == {"rungs": 2, "iterations": 4}
    assert stub.calls == [(2, False, 0), (2, True, 2)]


def test_balanced_escalates_to_the_full_budget():
    stub, img = _policy_session(deltas=[0.9, 0.8, 0.7])
    _, info = ServeSession.run_ladder(stub, img, img, "balanced")
    assert info == {"rungs": 3, "iterations": 8}
    # 2 -> +2 -> +4, continuation rungs fed the (flow, hidden) carry
    assert stub.calls == [(2, False, 0), (2, True, 2), (4, True, 2)]


# -- scheduler: class plumbing + per-class telemetry --------------------------


class FakeLadderSession:
    """Host-only ladder session: deterministic flow, scripted per-class
    iteration accounting."""

    ITS = {"fast": 2, "balanced": 4, "quality": 8}

    def __init__(self, buckets, ladder=None, batch_size=2):
        self.buckets = buckets
        self.ladder = ladder
        self.batch_size = batch_size

    def encode_image(self, img):
        return np.asarray(img, np.float32)

    def compiles(self):
        return 0

    def run(self, img1, img2):
        return (img1 + img2)[..., :2]

    def run_ladder(self, img1, img2, klass):
        its = self.ITS[klass]
        rungs = {"fast": 1, "balanced": 2, "quality": 1}[klass]
        return (img1 + img2)[..., :2], {"rungs": rungs, "iterations": its}

    def fetch(self, flow):
        return np.asarray(flow)


def _ladder_scheduler(ladder):
    session = FakeLadderSession(ShapeBuckets([(16, 24)]), ladder=ladder)
    return Scheduler(session, batch_size=2, max_wait_ms=2.0)


def _pair(shape, seed=0):
    rng = np.random.default_rng(seed)
    h, w = shape
    return (rng.random((h, w, 3), dtype=np.float32),
            rng.random((h, w, 3), dtype=np.float32))


def test_scheduler_classes_route_and_default_to_balanced():
    sink = telemetry.activate(telemetry.Telemetry())
    try:
        sched = _ladder_scheduler(LadderSpec()).start()
        try:
            img1, img2 = _pair((16, 24))
            results = {k: sched.submit(img1, img2, klass=k).result(timeout=10.0)
                       for k in serve.CLASSES}
            default = sched.submit(img1, img2).result(timeout=10.0)
        finally:
            sched.stop(drain=True)
        for k in serve.CLASSES:
            assert results[k].klass == k
            assert results[k].iterations == FakeLadderSession.ITS[k]
        assert default.klass == "balanced"

        ev = [e for e in sink.events
              if e["kind"] == "serve" and e["event"] == "request"]
        assert sorted(e["klass"] for e in ev) == sorted(
            list(serve.CLASSES) + ["balanced"])
        stats = treport.serve_stats(sink.events)
        assert set(stats["classes"]) == set(serve.CLASSES)
        assert stats["classes"]["balanced"]["requests"] == 2
        assert stats["classes"]["quality"]["iterations"] == {8: 1}
        text = treport.render(sink.events)
        assert "class fast" in text and "class quality" in text
    finally:
        telemetry.deactivate()


def test_scheduler_rejects_bad_classes_typed():
    # a class on a ladder-less session is a typed admission error
    sched = Scheduler(FakeLadderSession(ShapeBuckets([(16, 24)])),
                      batch_size=2)
    img1, img2 = _pair((16, 24))
    with pytest.raises(ServeError) as exc:
        sched.submit(img1, img2, klass="fast")
    assert exc.value.kind == "unknown_class"
    # no ladder, no class: the legacy single-program path, no klass tag
    assert sched._validate_klass(None) == ""

    sched = _ladder_scheduler(LadderSpec())
    with pytest.raises(ServeError) as exc:
        sched.submit(img1, img2, klass="turbo")
    assert exc.value.kind == "unknown_class"


# -- ProgramKey regression: iterations must key the program -------------------


def test_eval_program_keys_encode_iterations():
    # PR-11 bugfix pin: a non-default iteration count must produce its
    # own registry key (and thus its own AOT artifact) — explicit-args
    # keys used to collide with the default program's
    spec = models.load(TINY_LADDER_MODEL)
    default = evaluation.make_eval_fn(spec.model, model_id=spec.id)
    three = evaluation.make_eval_fn(spec.model, {"iterations": 3},
                                    model_id=spec.id)
    assert default is not three
    assert default.key != three.key
    assert "'iterations', '3'" in dict(three.key.flags)["args"]

    # rung programs: distinct keys per (iterations, cont) variant
    base = evaluation.make_rung_fn(spec.model, 2, model_id=spec.id)
    cont = evaluation.make_rung_fn(spec.model, 2, cont=True,
                                   model_id=spec.id)
    assert base.key != cont.key
    assert base is evaluation.make_rung_fn(spec.model, 2, model_id=spec.id)


# -- device half: real tiny model ---------------------------------------------


@pytest.fixture(scope="module")
def tiny_rungs():
    spec = models.load(TINY_LADDER_MODEL)
    model = spec.model
    rng = np.random.default_rng(3)
    img1 = rng.random((2, 32, 48, 3), dtype=np.float32)
    img2 = rng.random((2, 32, 48, 3), dtype=np.float32)
    import jax
    import jax.numpy as jnp

    variables = model.init(jax.random.PRNGKey(0), jnp.asarray(img1),
                           jnp.asarray(img2), iterations=1)
    return spec, variables, jnp.asarray(img1), jnp.asarray(img2)


def test_chained_rungs_bit_exact_vs_monolithic(tiny_rungs):
    spec, variables, img1, img2 = tiny_rungs
    base = evaluation.make_rung_fn(spec.model, 2, model_id=spec.id)
    cont = evaluation.make_rung_fn(spec.model, 2, cont=True,
                                   model_id=spec.id)
    full = evaluation.make_rung_fn(spec.model, 6, model_id=spec.id)

    flow, state = base(variables, img1, img2)
    for _ in range(2):
        flow, state = cont(variables, img1, img2,
                           state["flow"], state["hidden"])
    flow_full, state_full = full(variables, img1, img2)

    # f32 end to end: 2+2+2 chained through the (flow, hidden) carry is
    # the SAME arithmetic as the monolithic 6 — exact equality, no tol
    np.testing.assert_array_equal(np.asarray(flow), np.asarray(flow_full))
    np.testing.assert_array_equal(np.asarray(state["flow"]),
                                  np.asarray(state_full["flow"]))
    np.testing.assert_array_equal(np.asarray(state["hidden"]),
                                  np.asarray(state_full["hidden"]))


def test_delta_is_the_last_step_flow_norm(tiny_rungs):
    spec, variables, img1, img2 = tiny_rungs
    base = evaluation.make_rung_fn(spec.model, 2, model_id=spec.id)
    cont1 = evaluation.make_rung_fn(spec.model, 1, cont=True,
                                    model_id=spec.id)

    _, s2 = base(variables, img1, img2)
    # one continuation iteration: its delta is the norm of the flow
    # update relative to the carry it was fed
    _, s3 = cont1(variables, img1, img2, s2["flow"], s2["hidden"])
    diff = np.asarray(s3["flow"]) - np.asarray(s2["flow"])
    want = np.sqrt(np.mean(np.sum(diff * diff, axis=-1), axis=(1, 2)))
    np.testing.assert_allclose(np.asarray(s3["delta"]), want,
                               rtol=1e-5, atol=1e-6)
    assert s3["delta"].shape == (2,)  # per-sample, host-readable


def test_ladder_session_serves_all_classes_without_compiling():
    spec = models.load(TINY_LADDER_MODEL)
    session = ServeSession(spec, ShapeBuckets([(32, 48)]), batch_size=1,
                           ladder=LadderSpec(rungs=(2, 4, 6)))
    outcomes = session.warm_pool()
    rungs = sorted(o["rung"] for o in outcomes if "rung" in o)
    assert rungs == ["base:2", "cont:+2", "full:6"]

    c0 = session.compiles()
    sched = Scheduler(session, batch_size=1, max_wait_ms=2.0).start()
    try:
        img1, img2 = _pair((30, 44), seed=5)
        results = {k: sched.submit(img1, img2, klass=k).result(timeout=60.0)
                   for k in serve.CLASSES}
    finally:
        sched.stop(drain=True)
    assert results["fast"].iterations == 2
    assert results["quality"].iterations == 6
    assert 2 <= results["balanced"].iterations <= 6
    for res in results.values():
        assert res.flow.shape == (30, 44, 2)
    # every class — including balanced escalation — rode warm programs
    assert session.compiles() == c0


# -- final-flow programs: what serving builds upsamples one flow --------------

_FINAL_ITS, _FINAL_B = 5, 2     # 5 * 2 = 10: no other dimension of the toy


@pytest.fixture(scope="module")
def final_programs():
    """Every builder whose program returns the final flow alone, at
    ``_FINAL_ITS`` iterations: ``{name: (program, takes)}``."""
    from raft_meets_dicl_tpu import parallel

    cfg = dict(TINY_LADDER_MODEL, id="final-tiny")
    cfg["model"] = dict(cfg["model"], arguments={"iterations": _FINAL_ITS})
    spec = models.load(cfg)
    session = ServeSession(spec, ShapeBuckets([(32, 48)]),
                           batch_size=_FINAL_B, video=True,
                           ladder=LadderSpec(rungs=(_FINAL_ITS, 8)))
    step_key = programs.ProgramKey(kind="eval_step", model=spec.id)
    return spec, session, {
        "session": (session.eval_fn, "pair"),
        "rung": (session._rung_fns[(_FINAL_ITS, False)], "pair"),
        "cont": (evaluation.make_rung_fn(spec.model, _FINAL_ITS, cont=True,
                                         model_id=spec.id), "carry"),
        "warm": (session._warm_fn, "flow"),
        "eval_step": (parallel.make_eval_step(spec.model, key=step_key),
                      "pair"),
    }


def _lower(final_programs, name):
    import jax
    import jax.numpy as jnp

    spec, session, progs = final_programs
    prog, takes = progs[name]
    f32 = jnp.float32
    img = jax.ShapeDtypeStruct((_FINAL_B, 32, 48, 3), f32)
    flow = jax.ShapeDtypeStruct((_FINAL_B, 4, 6, 2), f32)
    hidden = jax.ShapeDtypeStruct((_FINAL_B, 4, 6, 16), f32)
    extra = {"pair": (), "flow": (flow,), "carry": (flow, hidden)}[takes]
    return prog, prog.lower(session.variables, img, img, *extra)


def _stacked_over_iterations(text):
    """StableHLO tensor types whose leading dimension is ``iterations *
    b`` at or above the coarse grid: Up8's batch when it runs on every
    iteration."""
    import re

    hits = set()
    for dims in re.findall(r"tensor<((?:\d+x)+)[a-z]", text):
        d = [int(x) for x in dims.split("x") if x]
        if (len(d) >= 3 and d[0] == _FINAL_ITS * _FINAL_B
                and d[1] >= 4 and d[2] >= 6):
            hits.add(tuple(d))
    return hits


@pytest.mark.parametrize("name", ["session", "rung", "cont", "warm",
                                  "eval_step"])
def test_final_flow_programs_upsample_one_flow(final_programs, name):
    import jax

    prog, lowered = _lower(final_programs, name)
    # no operand of leading dimension iterations * b from the coarse
    # grid up: Up8 (mask head, combine, pixel shuffle) runs on batch b
    assert _stacked_over_iterations(lowered.as_text()) == set()

    # the outputs hold the final flow and no other full-resolution one
    # (the session's eval program returns it as ``out == [final]`` too)
    full_res = [leaf.shape for leaf in jax.tree.leaves(lowered.out_info)
                if tuple(leaf.shape[1:3]) == (32, 48)]
    assert full_res == [(_FINAL_B, 32, 48, 2)] * (2 if name == "session"
                                                  else 1)

    # the switch is part of the program's identity: the AOT store and the
    # registry can never hand this program a twelve-flow executable
    assert "('final_only', 'True')" in dict(prog.key.flags)["args"]


def test_final_flow_detector_sees_the_full_form(final_programs):
    # positive control, and the key contract: evaluate()'s program (no
    # switch) still upsamples every iteration and is another program
    spec, session, _ = final_programs
    full = evaluation.make_eval_fn(spec.model, None, model_id=spec.id)
    import jax
    import jax.numpy as jnp

    img = jax.ShapeDtypeStruct((_FINAL_B, 32, 48, 3), jnp.float32)
    lowered = full.lower(session.variables, img, img)
    assert _stacked_over_iterations(lowered.as_text())
    out, final = lowered.out_info
    assert len(out) == _FINAL_ITS

    assert full is not session.eval_fn and full.key != session.eval_fn.key
    assert "final_only" not in dict(full.key.flags)["args"]
    # what every trace/batch event carries as ``program``
    assert "('final_only', 'True')" in session.program_fingerprint()
    assert "final_only" in session.program_fingerprint("quality")


def test_final_flow_program_drops_the_hidden_stack(final_programs):
    # compiled, the loop no longer carries the per-iteration hiddens
    # (nobody reads them once Up8 takes the final carry), nor anything
    # else stacked over the iterations at the coarse grid
    import re

    _, lowered = _lower(final_programs, "session")
    text = lowered.compile().as_text()
    assert re.search(r"\[%d,%d,4,6,\d+\]" % (_FINAL_ITS, _FINAL_B),
                     text) is None
    assert re.search(r"\[%d,4,6,\d+\]" % (_FINAL_ITS * _FINAL_B),
                     text) is None
