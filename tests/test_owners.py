"""Whose instruction is it (PR 37): ``compile/owners.py`` and its record.

The parser on hand-written HLO text, one case a rule; the table of scopes;
a tiny ``raft/baseline`` train step compiled on the CPU (every phase has
instructions, few have no owner); the same record from an executable
loaded back from the AOT store, once an executable a boot; its size; and
that with the sink off no executable's text is taken at all. The text the
v5e compiler writes is rehearsed in ``tests/test_pallas_compile.py``.
"""

import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from raft_meets_dicl_tpu import compile as programs
from raft_meets_dicl_tpu import parallel, telemetry
from raft_meets_dicl_tpu.compile import owners
import raft_meets_dicl_tpu.models as models
from raft_meets_dicl_tpu.models.wire import WireFormat

STACK = "jit(step)/jvp(RaftModule)"
BACK = "jit(step)/transpose(jvp(RaftModule))"


def _hlo(entry, *computations):
    """A module's text from the entry's instruction lines and whole
    computations before it."""
    body = "\n".join(f"  {line}" for line in entry)
    return ("HloModule jit_step, is_scheduled=true\n\n"
            + "\n\n".join(computations)
            + "\n\nENTRY %main.9 (p0.1: f32[4,8]) -> f32[4,8] {\n"
            + body + "\n}\n")


def _meta(op_name):
    return f'metadata={{op_name="{op_name}" source_file="x.py" source_line=3}}'


def _owner(record, key):
    return owners.flat(record).get(key)


P0 = "%p0.1 = f32[4,8]{1,0} parameter(0)"


# -- the table ---------------------------------------------------------------


@pytest.mark.parametrize("op_name,want", [
    (f"{STACK}/encoders/FeatureEncoderS3_0/Conv_0/conv_general_dilated",
     ("encoders", "encoders", "fwd")),
    (f"{STACK}/corr/dot_general", ("corr", "corr", "fwd")),
    # existing scope names are mapped, not renamed
    (f"{STACK}/pyramid/reduce_window_sum", ("corr", "pyramid", "fwd")),
    (f"{STACK}/while/body/closed_call/lookup/level0/matching/sampler/"
     "pallas_call", ("lookup", "sampler", "fwd")),
    (f"{BACK}/while/body/closed_call/lookup/level1/matching/mnet/"
     "MatchingNet_0/ConvBlock_0/conv_general_dilated",
     ("lookup", "mnet", "bwd")),
    (f"{STACK}/matching/dap/DisplacementAwareProjection_0/dot_general",
     ("lookup", "dap", "fwd")),
    (f"{STACK}/while/body/closed_call/lookup/wcp/pallas_call",
     ("lookup", "wcp", "fwd")),
    # a scope under a transform's parentheses
    (f"jit(step)/jvp(matching/mnet)/add", ("lookup", "mnet", "fwd")),
    (f"{STACK}/while/body/closed_call/update/BasicUpdateBlock_0/"
     "SepConvGru_0/tanh", ("update", "update", "fwd")),
    (f"{BACK}/up8/jvp(RaftModule)/up8/checkpoint/Up8Network_0/pallas_call",
     ("up8", "up8", "bwd")),
    ("jit(step)/jvp(loss)/reduce_sum", ("loss", "loss", "fwd")),
    # the ladder's own phases (DICL): the warp before a level, the
    # context network after it; its shift stack and soft-argmin are
    # ``matching``, its last resize ``up8``
    ("jit(step)/DiclModule/FlowLevel_3/warp/gather", ("warp", "warp", "fwd")),
    ("jit(step)/DiclModule/FlowLevel_3/context/CtfContextNet_0/ConvBlock_2/"
     "Conv_0/conv_general_dilated", ("context", "context", "fwd")),
    ("jit(step)/DiclModule/FlowLevel_4/matching/concatenate",
     ("lookup", "matching", "fwd")),
    ("jit(step)/up8/dot_general", ("up8", "up8", "fwd")),
    ("jit(step)/optimizer/mul", ("optimizer", "optimizer", "fwd")),
    ("jit(step)/input/convert_element_type", ("input", "input", "fwd")),
    # no scope of the table: the outermost own component names it
    (f"{STACK}/while/body/dynamic_update_slice",
     ("other", "RaftModule", "fwd")),
    ("jit(step)/mul", ("other", "mul", "fwd")),
])
def test_scope_table(op_name, want):
    assert owners.owner_of(op_name) == want


def test_every_scope_maps_to_a_phase_of_the_vocabulary():
    assert set(owners.SCOPES.values()) == set(owners.PHASES
                                              + owners.LADDER_PHASES)
    # a level's scope is transparent: it is in no table
    assert "level0" not in owners.SCOPES


# -- the rules, one case each -------------------------------------------------


def test_rule_own_op_name():
    rec = owners.parse(_hlo([
        P0,
        f"ROOT %add.2 = f32[4,8]{{1,0}} add(%p0.1, %p0.1), "
        f"{_meta(STACK + '/update/add')}",
    ]))
    assert _owner(rec, "add.2:f32[4,8]") == ("update", "update", "fwd")
    assert rec["rules"] == {"own": 1}
    assert (rec["instructions"], rec["inferred"], rec["unowned"]) == (1, 0, 0)
    assert rec["module"] == "jit_step"


def test_rule_fusion_takes_its_computations_commonest_owner():
    fused = (
        "%fused_computation.3 (param_0.1: f32[4,8]) -> f32[4,8] {\n"
        "  %param_0.1 = f32[4,8]{1,0} parameter(0)\n"
        f"  %mul.1 = f32[4,8]{{1,0}} multiply(%param_0.1, %param_0.1), "
        f"{_meta(STACK + '/encoders/mul')}\n"
        f"  %neg.1 = f32[4,8]{{1,0}} negate(%mul.1), "
        f"{_meta(STACK + '/encoders/neg')}\n"
        f"  ROOT %add.1 = f32[4,8]{{1,0}} add(%neg.1, %mul.1), "
        f"{_meta(STACK + '/corr/add')}\n"
        "}")
    rec = owners.parse(_hlo([
        P0,
        "ROOT %fusion.7 = f32[4,8]{1,0:T(8,128)} fusion(%p0.1), kind=kLoop, "
        "calls=%fused_computation.3",
    ], fused))
    assert _owner(rec, "fusion.7:f32[4,8]") == ("encoders", "encoders", "fwd")
    assert rec["rules"] == {"fusion": 1}
    # the fused computation's own instructions are no operations
    assert rec["instructions"] == 1


REDUCER = ("%region_0.4 (a.1: f32[], b.1: f32[]) -> f32[] {\n"
           "  %a.1 = f32[] parameter(0)\n  %b.1 = f32[] parameter(1)\n"
           "  ROOT %add.9 = f32[] add(%a.1, %b.1)\n}")


@pytest.mark.parametrize("line,key,scope,direction", [
    # a gradient's all-reduce inherits the backward convolution's name stack
    (f"ROOT %all-reduce.3 = f32[4,8]{{1,0}} all-reduce(%p0.1), channel_id=1, "
     f"replica_groups={{{{0,1,2,3}}}}, use_global_device_ids=true, "
     f"to_apply=%region_0.4, "
     f"{_meta(BACK + '/encoders/Conv_0/conv_general_dilated')}",
     "all-reduce.3:f32[4,8]", "all-reduce", "bwd"),
    # the pair concatenation's reshard, named by the encoder
    (f"ROOT %all-to-all.2 = f32[4,8]{{1,0}} all-to-all(%p0.1), channel_id=2, "
     f"replica_groups={{{{0,1,2,3}}}}, dimensions={{0}}, "
     f"{_meta(STACK + '/encoders/FeatureEncoderS3_0/concatenate')}",
     "all-to-all.2:f32[4,8]", "all-to-all", "fwd"),
    # the asynchronous halves, as the TPU's scheduler writes them
    ("ROOT %all-reduce-start.1 = f32[4,8]{1,0} all-reduce-start(%p0.1), "
     "channel_id=3, replica_groups={{0,1,2,3}}, to_apply=%region_0.4",
     "all-reduce-start.1:f32[4,8]", "all-reduce", "fwd"),
    ("ROOT %collective-permute-done.5 = f32[4,8]{1,0} "
     f"collective-permute-done(%p0.1), {_meta('jit(step)/optimizer/mul')}",
     "collective-permute-done.5:f32[4,8]", "collective-permute", "fwd"),
    ("ROOT %all-gather.7 = f32[4,8]{1,0} all-gather(%p0.1), channel_id=4, "
     "dimensions={0}", "all-gather.7:f32[4,8]", "all-gather", "fwd"),
    ("ROOT %reduce-scatter.8 = f32[4,8]{1,0} reduce-scatter(%p0.1), "
     "channel_id=5, dimensions={0}, to_apply=%region_0.4",
     "reduce-scatter.8:f32[4,8]", "reduce-scatter", "fwd"),
], ids=["all-reduce", "all-to-all", "all-reduce-start",
        "collective-permute-done", "all-gather", "reduce-scatter"])
def test_rule_collective_whatever_the_name_stack(line, key, scope, direction):
    rec = owners.parse(_hlo([P0, line], REDUCER))
    assert _owner(rec, key) == ("collective", scope, direction)
    assert rec["rules"] == {"collective": 1}
    assert (rec["instructions"], rec["inferred"], rec["unowned"]) == (1, 0, 0)
    # the reducer's add is no operation of the program
    assert "other" not in rec["owners"]


def test_rule_collective_under_an_asynchronous_wrapper():
    wrapped = ("%async_computation.2 (param_0.9: f32[4,8]) -> f32[4,8] {\n"
               "  %param_0.9 = f32[4,8]{1,0} parameter(0)\n"
               "  ROOT %all-to-all.4 = f32[4,8]{1,0} all-to-all(%param_0.9), "
               "channel_id=6, replica_groups={{0,1,2,3}}, dimensions={0}\n}")
    rec = owners.parse(_hlo([
        P0,
        "%async-start.1 = ((f32[4,8]), f32[4,8], u32[]) async-start(%p0.1), "
        "calls=%async_computation.2",
        "ROOT %async-done.1 = f32[4,8]{1,0} async-done(%async-start.1), "
        "calls=%async_computation.2",
    ], wrapped))
    flat = owners.flat(rec)
    assert flat["async-start.1:f32[4,8]"] == ("collective", "all-to-all",
                                              "fwd")
    assert flat["async-done.1:f32[4,8]"] == ("collective", "all-to-all",
                                             "fwd")
    assert rec["rules"] == {"collective": 2}


def test_a_copy_next_to_a_collective_is_not_the_collectives():
    # the rule is the opcode's: a neighbour still infers from its other side
    rec = owners.parse(_hlo([
        P0,
        f"%mul.2 = f32[4,8]{{1,0}} multiply(%p0.1, %p0.1), "
        f"{_meta('jit(step)/optimizer/mul')}",
        "%all-reduce.3 = f32[4,8]{1,0} all-reduce(%mul.2), channel_id=1, "
        "to_apply=%region_0.4",
        "ROOT %copy.4 = f32[4,8]{0,1} copy(%all-reduce.3)",
    ], REDUCER))
    assert _owner(rec, "all-reduce.3:f32[4,8]")[0] == "collective"
    assert _owner(rec, "mul.2:f32[4,8]") == ("optimizer", "optimizer", "fwd")
    # ... and looks through the collective to what produced its operand
    assert _owner(rec, "copy.4:f32[4,8]") == ("optimizer", "optimizer", "fwd")
    assert rec["rules"] == {"own": 1, "collective": 1, "producer": 1}


def test_rule_copy_belongs_to_its_user():
    rec = owners.parse(_hlo([
        P0,
        "%copy.5 = f32[4,8]{0,1} copy(%p0.1)",
        f"ROOT %dot.6 = f32[4,8]{{1,0}} convolution(%copy.5, %p0.1), "
        f"{_meta(STACK + '/lookup/dot_general')}",
    ]))
    assert _owner(rec, "copy.5:f32[4,8]") == ("lookup", "lookup", "fwd")
    assert rec["rules"] == {"own": 1, "user": 1}
    assert rec["inferred"] == 1
    assert rec["inferred_keys"] == ["copy.5:f32[4,8]"]


def test_rule_copy_with_no_user_belongs_to_its_producer():
    rec = owners.parse(_hlo([
        P0,
        f"%exp.3 = f32[4,8]{{1,0}} exponential(%p0.1), "
        f"{_meta(BACK + '/up8/exp')}",
        "ROOT %copy.4 = f32[4,8]{0,1} copy(%exp.3)",
    ]))
    assert _owner(rec, "copy.4:f32[4,8]") == ("up8", "up8", "bwd")
    assert rec["rules"] == {"own": 1, "producer": 1}


def test_rule_a_chain_of_copies_resolves_from_its_far_end():
    # the prefetch pair the v5e compiler writes: start -> done -> user
    rec = owners.parse(_hlo([
        P0,
        "%copy-start.1 = (f32[4,8]{1,0:S(1)}, f32[4,8]{1,0}, u32[]{:S(2)}) "
        "copy-start(%p0.1)",
        "%copy-done.1 = f32[4,8]{1,0:S(1)} copy-done(%copy-start.1)",
        "%bitcast.2 = f32[32]{0} bitcast(%copy-done.1)",
        f"ROOT %tanh.3 = f32[32]{{0}} tanh(%bitcast.2), "
        f"{_meta(STACK + '/encoders/tanh')}",
    ]))
    flat = owners.flat(rec)
    assert flat["copy-start.1:f32[4,8]"] == ("encoders", "encoders", "fwd")
    assert flat["copy-done.1:f32[4,8]"] == ("encoders", "encoders", "fwd")
    # a bitcast runs nothing: it is looked through and not recorded
    assert "bitcast.2:f32[32]" not in flat
    assert rec["instructions"] == 3


def test_rule_unowned():
    rec = owners.parse(_hlo([
        P0,
        "ROOT %copy.2 = f32[4,8]{0,1} copy(%p0.1)",
    ]))
    assert _owner(rec, "copy.2:f32[4,8]") == ("unowned", "", "fwd")
    assert (rec["unowned"], rec["rules"]) == (1, {"unowned": 1})


def test_a_parameters_op_name_is_its_arguments_name_not_an_owner():
    rec = owners.parse(_hlo([
        f"%p0.1 = f32[4,8]{{1,0}} parameter(0), "
        f"{_meta('state.params[encoders]')}",
        "ROOT %copy.2 = f32[4,8]{0,1} copy(%p0.1)",
    ]))
    assert _owner(rec, "copy.2:f32[4,8]") == ("unowned", "", "fwd")


def test_forward_against_transpose():
    rec = owners.parse(_hlo([
        P0,
        f"%a.2 = f32[4,8]{{1,0}} add(%p0.1, %p0.1), "
        f"{_meta(STACK + '/update/add')}",
        f"ROOT %b.3 = f32[4,8]{{1,0}} add(%a.2, %a.2), "
        f"{_meta(BACK + '/update/add_any')}",
    ]))
    assert rec["owners"] == {"update": {"update": {
        "fwd": ["a.2:f32[4,8]"], "bwd": ["b.3:f32[4,8]"]}}}


def test_scope_inside_a_while_body_under_checkpoint():
    body = (
        "%wide.region_0.5 (arg.1: (s32[], f32[4,8])) -> (s32[], f32[4,8]) {\n"
        "  %arg.1 = (s32[]{:T(128)}, f32[4,8]{1,0}) parameter(0)\n"
        "  %i.2 = s32[]{:T(128)} get-tuple-element(%arg.1), index=0\n"
        "  %x.3 = f32[4,8]{1,0} get-tuple-element(%arg.1), index=1\n"
        f"  %dot.4 = f32[4,8]{{1,0}} convolution(%x.3, %x.3), "
        f"{_meta(BACK + '/while/body/closed_call/checkpoint/rematted_computation/lookup/dot_general')}\n"
        "  %copy.9 = f32[4,8]{0,1} copy(%dot.4)\n"
        "  ROOT %tuple.6 = (s32[]{:T(128)}, f32[4,8]{1,0}) tuple(%i.2, "
        "%copy.9)\n"
        "}")
    cond = (
        "%cond.7 (arg.2: (s32[], f32[4,8])) -> pred[] {\n"
        "  %arg.2 = (s32[]{:T(128)}, f32[4,8]{1,0}) parameter(0)\n"
        "  %i.8 = s32[]{:T(128)} get-tuple-element(%arg.2), index=0\n"
        f"  ROOT %lt.9 = pred[]{{:T(512)}} compare(%i.8, %i.8), "
        f"direction=LT, {_meta(BACK + '/while/cond/lt')}\n"
        "}")
    rec = owners.parse(_hlo([
        P0,
        "%zero.2 = s32[]{:T(128)} constant(0)",
        "%tuple.3 = (s32[]{:T(128)}, f32[4,8]{1,0}) tuple(%zero.2, %p0.1)",
        "%while.4 = (s32[]{:T(128)}, f32[4,8]{1,0}) while(%tuple.3), "
        "condition=%cond.7, body=%wide.region_0.5",
        "ROOT %out.5 = f32[4,8]{1,0} get-tuple-element(%while.4), index=1",
    ], body, cond))
    flat = owners.flat(rec)
    # the rematerialised forward that runs in the backward pass is ``bwd``
    assert flat["dot.4:f32[4,8]"] == ("lookup", "lookup", "bwd")
    # the body's copy feeds the loop's carry: it is its producer's
    assert flat["copy.9:f32[4,8]"] == ("lookup", "lookup", "bwd")
    assert flat["lt.9:pred[]"] == ("other", "RaftModule", "bwd")
    # the while itself, its tuples and elements are no operations
    assert rec["instructions"] == 3


def test_the_key_is_name_and_first_array_of_the_result():
    assert owners.key_of("fusion.3196",
                         "bf16[6,50,90,50,90]{4,3,2,1,0:T(8,128)(2,1)} "
                         "fusion(%a), kind=kOutput") \
        == "fusion.3196:bf16[6,50,90,50,90]"
    assert owners.key_of("copy-start.4",
                         "(f32[7,7,3,64]{3,1,2,0:T(8,128)S(1)}, f32[7,7,3,64]"
                         "{3,1,2,0}, u32[]{:S(2)}) copy-start(%w)") \
        == "copy-start.4:f32[7,7,3,64]"
    assert owners.key_of("after-all.1", "token[] after-all()") \
        == "after-all.1:token[]"


def test_the_benchmarks_reader_cuts_the_same_key():
    # the benchmark may not import the program's module (its readers must
    # run over a tree that has none): its copy of the cut is held to this
    from benchmark.layers import _owners as reader

    for name, rest in [
        ("fusion.3196", "bf16[6,50,90,50,90]{4,3,2,1,0:T(8,128)(2,1)} "
                        "fusion(), kind=kOutput"),
        ("copy-start.4", "(f32[7,7,3,64]{3,1,2,0:T(8,128)S(1)}, "
                         "f32[7,7,3,64]{3,1,2,0}, u32[]{:S(2)}) copy-start()"),
        ("wcp.27", "f32[1,136,240,81]{3,2,1,0:T(8,128)} custom-call()"),
        ("slice-start.2", "((bf16[6,400,720,3]{3,2,1,0}), bf16[2,400,720,3]"
                          "{3,2,1,0}, s32[]) async-start()"),
    ]:
        assert reader.key_of(f"%{name} = {rest}") == owners.key_of(name, rest)
    assert reader.PHASES == owners.PHASES
    assert (reader.OTHER, reader.UNOWNED) == (owners.OTHER, owners.UNOWNED)


# -- a real program -----------------------------------------------------------


TINY = {
    "name": "tiny-owners", "id": "tiny-owners",
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


@pytest.fixture
def aot_store(tmp_path, monkeypatch):
    monkeypatch.delenv("RMD_AOT", raising=False)
    monkeypatch.delenv("RMD_AOT_DIR", raising=False)
    programs.reset()
    d = tmp_path / "programs"
    programs.enable_aot(str(d))
    yield d
    programs.disable_aot()
    programs.reset()


def _boot(key):
    """One boot of the tiny train step through the builder, with the wire
    decode the cells have: the program and the ``aot`` events it emitted."""
    import optax

    spec = models.load(TINY)
    model, loss = spec.model, spec.loss
    variables = model.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 32, 48, 3)),
        jnp.zeros((1, 32, 48, 3)), iterations=1)
    tx = optax.chain(optax.clip_by_global_norm(1.0), optax.adamw(1e-3))
    rng = np.random.RandomState(0)
    batch = (jnp.asarray(rng.rand(2, 32, 48, 3), jnp.bfloat16),
             jnp.asarray(rng.rand(2, 32, 48, 3), jnp.bfloat16),
             jnp.asarray(rng.randn(2, 32, 48, 2), jnp.float16),
             jnp.full((2, 32, 6), 255, jnp.uint8))      # bit-packed valid
    state = parallel.TrainState.create(variables, tx)
    step = parallel.make_train_step(
        model, loss, tx, model_args={"iterations": 2}, key=key,
        external_lr=True, wire=WireFormat.from_config("bf16"))
    sink = telemetry.get()
    before = len(getattr(sink, "events", ()))
    _, aux = step(state, jnp.float32(1e-3), *batch)
    assert np.isfinite(float(aux["loss"]))
    events = [e for e in getattr(sink, "events", ())[before:]
              if e["kind"] == "aot"]
    return step, events


@pytest.fixture(scope="module")
def two_boots(tmp_path_factory):
    """The tiny step compiled and saved, then loaded back by a second
    boot, with the sink on: ``(events of boot 1, events of boot 2,
    program of boot 2)``."""
    store = tmp_path_factory.mktemp("owners") / "programs"
    programs.reset()
    programs.enable_aot(str(store))
    sink = telemetry.activate(telemetry.Telemetry())
    key = programs.ProgramKey(
        "train_step", "tiny-owners",
        programs.flag_items(shape=(2, 32, 48), iterations=2))
    try:
        step1, first = _boot(key)
        assert step1.aot_saves == 1
        programs.reset()
        step2, second = _boot(key)
        assert step2.aot_hits == 1 and step2.compiles == 0
        yield first, second, step2
    finally:
        telemetry.deactivate()
        programs.disable_aot()
        programs.reset()
    del sink


def _record(events):
    (rec,) = [e for e in events if e["event"] == "owners"]
    return rec


def test_tiny_train_step_every_phase_has_instructions(two_boots):
    first, _, _ = two_boots
    rec = _record(first)
    assert rec["program"] == "train_step" and rec["model"] == "tiny-owners"
    assert rec["module"] == "jit_step"
    directions = {phase: set().union(*(d.keys() for d in scopes.values()))
                  for phase, scopes in rec["owners"].items()}
    for phase in ("encoders", "corr", "lookup", "update", "up8"):
        assert directions[phase] == {"fwd", "bwd"}, (phase, directions)
    for phase in ("input", "loss", "optimizer"):
        assert "fwd" in directions[phase], (phase, directions)
    assert set(directions["input"]) == set(directions["optimizer"]) == {"fwd"}
    # few instructions have no owner, and the counts add up
    assert rec["unowned"] < 0.10 * rec["instructions"], rec["rules"]
    assert sum(rec["rules"].values()) == rec["instructions"]
    assert rec["instructions"] == len(owners.flat(rec))
    assert rec["seconds"] < 5.0


def test_record_emitted_once_an_executable_on_save_and_on_hit(two_boots):
    first, second, _ = two_boots
    assert [e["event"] for e in first] == ["miss", "save", "owners"]
    assert [e["event"] for e in second] == ["hit", "owners"]


SAME = ("module", "owners", "inferred_keys", "instructions", "inferred",
        "unowned", "rules")


def test_hit_hands_on_the_record_stored_with_the_artifact(two_boots):
    first, second, step2 = two_boots
    saved, loaded = _record(first), _record(second)
    for field in SAME:
        assert saved[field] == loaded[field], field
    # the saving boot read the text; the loading boot took none
    assert (saved["source"], loaded["source"]) == ("text", "artifact")
    assert saved["seconds"] > 0.0 and loaded["seconds"] == 0.0
    held = [e for e in first + second if e["event"] in ("save", "hit")]
    assert [e["mosaic_calls"] for e in held] == [0, 0]
    # and the program keeps it for readers in the process
    (kept,) = step2.owners.values()
    assert kept["owners"] == loaded["owners"]


def test_loaded_executables_own_text_gives_the_same_record(
        two_boots, aot_store, monkeypatch):
    """An artifact a sink-off boot saved holds no record: the boot that
    loads it with the sink on reads the loaded executable's own text,
    which carries the name stacks like the compiled one's."""
    first, _, _ = two_boots
    key = programs.ProgramKey(
        "train_step", "tiny-owners",
        programs.flag_items(shape=(2, 32, 48), iterations=2))
    sink = telemetry.get()
    telemetry.deactivate()
    try:
        step, events = _boot(key)
        assert step.aot_saves == 1 and events == []
    finally:
        telemetry.activate(sink)
    programs.reset()
    step2, events = _boot(key)
    assert step2.aot_hits == 1 and step2.compiles == 0
    assert [e["event"] for e in events] == ["hit", "owners"]
    loaded = _record(events)
    assert loaded["source"] == "text" and loaded["seconds"] > 0.0
    for field in SAME:
        assert _record(first)[field] == loaded[field], field


def test_record_serialises_under_a_megabyte(two_boots):
    first, _, _ = two_boots
    text = json.dumps(_record(first))
    assert len(text) < 1_000_000
    assert json.loads(text)["owners"] == _record(first)["owners"]
    telemetry.validate_event(_record(first))


def test_sink_off_takes_no_text_and_keeps_no_record(aot_store, monkeypatch):
    from jax import stages

    def boom(self, *a, **k):
        raise AssertionError("as_text called with the sink off")

    monkeypatch.setattr(stages.Compiled, "as_text", boom)
    # (the module's two boots may have left their sink on)
    before = telemetry.get()
    telemetry.deactivate()
    try:
        assert not telemetry.get().enabled
        key = programs.ProgramKey(
            "train_step", "tiny-owners-off",
            programs.flag_items(shape=(2, 32, 48), iterations=2))
        step, events = _boot(key)
        assert step.aot_saves == 1 and events == []
        assert step.owners == {}
        programs.reset()
        step2, _ = _boot(key)          # the hit takes none either
        assert step2.aot_hits == 1 and step2.owners == {}
    finally:
        if before.enabled:
            telemetry.activate(before)


# -- a program without a recurrence --------------------------------------------


TINY_DICL = {
    "name": "tiny-dicl-owners", "id": "tiny-dicl-owners",
    "model": {
        "type": "dicl/baseline",
        "parameters": {
            "feature-channels": 8,
            "displacement-range": {f"level-{i}": [3, 3] for i in range(2, 7)},
        },
        "arguments": {"raw": True, "dap": True, "ctx": True},
    },
    "loss": {"type": "dicl/multiscale"},
    "input": {"padding": {"type": "modulo", "mode": "zeros",
                          "size": [128, 128]}},
}


def test_dicl_eval_program_names_warp_context_and_mnet(aot_store):
    """The served form of ``dicl/baseline`` (final flow only, u8 wire):
    the record of its eval program names the ladder's phases, leaves
    little to ``other`` and ``unowned``, and its ``save`` event carries
    what the trace noted of the matching volumes and the warps."""
    from raft_meets_dicl_tpu import evaluation

    sink = telemetry.activate(telemetry.Telemetry())
    try:
        spec = models.load(TINY_DICL)
        wire = WireFormat.from_config("u8").bound(spec.input.clip,
                                                  spec.input.range)
        img = jnp.zeros((1, 128, 128, 3))
        variables = spec.model.init(jax.random.PRNGKey(0), img, img)
        fn = evaluation.make_eval_fn(spec.model, {"final_only": True},
                                     wire=wire, model_id=spec.id)
        frames = jnp.asarray(
            np.random.RandomState(0).randint(0, 256, (2, 2, 128, 128, 3)),
            jnp.uint8)
        out, flow = fn(variables, frames[0], frames[1])
        assert len(out) == 1 and flow.shape == (2, 128, 128, 2)
        assert np.isfinite(np.asarray(flow)).all()
        events = [e for e in sink.events if e["kind"] == "aot"
                  and e.get("program") == "eval_step"]
    finally:
        telemetry.deactivate()
    assert [e["event"] for e in events] == ["miss", "save", "owners"]
    rec = events[2]
    assert {"encoders", "warp", "lookup", "context", "up8", "input"} \
        <= set(rec["owners"])
    assert {"matching", "mnet", "dap"} <= set(rec["owners"]["lookup"])
    # no backward pass, no recurrence: nothing under update or corr
    assert not {"update", "corr", "loss", "optimizer"} & set(rec["owners"])
    assert all(set(d) == {"fwd"} for scopes in rec["owners"].values()
               for d in scopes.values())
    count = {phase: sum(len(keys) for d in scopes.values()
                        for keys in d.values())
             for phase, scopes in rec["owners"].items()}
    loose = count.get(owners.OTHER, 0) + count.get(owners.UNOWNED, 0)
    assert loose < 0.10 * rec["instructions"], count
    # five levels' stacked pairs of a batch of 2 (49 hypotheses, 2 x 8
    # channels, float32) at 1/4 ... 1/64 of 128x128; a warp before four
    positions = sum((128 >> lvl) ** 2 for lvl in range(2, 7))
    assert events[1]["matching_volume_bytes"] == 2 * 49 * positions * 16 * 4
    assert events[1]["warp_calls"] == 4
    # the key counts the notes' revision: an older tree's stored program,
    # which states no scope, is not handed to this one
    assert dict(fn.key.flags)["notes"] == "1"
