"""graftcost: the StableHLO cost-model walker, the sharding-contract
collective auditor, the pinned-budget discipline, and the tier-1 budget
gate itself over every registered program (flagship train/eval, the
(4, 2)-mesh ZeRO variant, every ladder rung) — plus the two seeded
regressions the gate exists to catch: an f32 surface regrowing under a
bf16 policy, and a dead partition rule silently replicating params."""

import json
import textwrap
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from raft_meets_dicl_tpu import parallel, telemetry
from raft_meets_dicl_tpu.analysis import collectives, cost

pytestmark = pytest.mark.cost

REPO = Path(__file__).parent.parent


# -- walker: op costs from StableHLO text ------------------------------------


def test_tile_utilization_matches_perf_geometry():
    # a well-tiled square contraction fills the (8, 128) tiles exactly
    assert cost.tile_utilization(128, 128, 128) == 1.0
    # the flagship lookup einsum: a 9-row operand uses a sliver of the
    # array (PERF.md's "9/128 of the systolic array")
    assert cost.tile_utilization(2, 9, 64) < 0.05
    # the (48, 256, 48) lookup matmul: rhs pads 48 lanes of 128
    assert cost.tile_utilization(48, 256, 48) == pytest.approx(0.375)
    assert cost.tile_utilization(96, 1152, 128) == 1.0


DOT_LINE = ('%3 = stablehlo.dot_general %0, %1, contracting_dims = [1] x '
            '[0] : (tensor<8x16xf32>, tensor<16x32xf32>) -> '
            'tensor<8x32xf32>')
CONV_LINE = ('%4 = stablehlo.convolution(%a, %k) dim_numbers = '
             '[b, 0, 1, f]x[0, 1, i, o]->[b, 0, 1, f], window = {} : '
             '(tensor<1x8x8x4xf32>, tensor<3x3x4x16xf32>) -> '
             'tensor<1x8x8x16xf32>')
GATHER_LINE = ('%5 = "stablehlo.gather"(%a, %i) <{slice_sizes = '
               'array<i64: 1, 5>}> : (tensor<4x9xf32>, tensor<4x1xi32>) '
               '-> tensor<4x5xf32>')


def test_walker_dot_flops_and_mkn():
    (op,) = cost.op_costs(DOT_LINE)
    assert op.klass == "dot"
    assert op.flops == 2 * 8 * 16 * 32
    assert op.mkn == (8, 16, 32)
    # operands + result bytes, all f32
    assert op.bytes == 4 * (8 * 16 + 16 * 32 + 8 * 32)
    assert op.verdict == "shape-bound"  # 8x16 fills 16/128 lanes


def test_walker_conv_reads_kernel_spec():
    (op,) = cost.op_costs(CONV_LINE)
    assert op.klass == "conv"
    # co=16 from the o position; k = 3*3*4; m = out elements / co
    assert op.mkn == (64, 36, 16)
    assert op.flops == 2 * 64 * 36 * 16


def test_walker_gather_strip_slice_hazard():
    (op,) = cost.op_costs(GATHER_LINE)
    assert "gather-scalarization" in op.hazards
    # all-1 slices (row gather) and whole-dim slices are fine
    clean = GATHER_LINE.replace("1, 5", "1, 9")
    (op,) = cost.op_costs(clean)
    assert op.hazards == ()


def test_walker_f32_upcast_only_under_bf16_policy():
    (op,) = cost.op_costs(DOT_LINE, expect_bf16=True)
    assert "f32-upcast" in op.hazards
    (op,) = cost.op_costs(DOT_LINE, expect_bf16=False)
    assert op.hazards == ()
    bf16 = DOT_LINE.replace("xf32", "xbf16")
    (op,) = cost.op_costs(bf16, expect_bf16=True)
    assert op.hazards == ()


def test_walker_reduce_and_elementwise_forms():
    text = textwrap.dedent("""
        %5 = stablehlo.reduce(%0 init: %1) applies stablehlo.add across
        %6 = stablehlo.reduce %0 : (tensor<8x16xf32>, tensor<f32>) -> tensor<8xf32>
        %7 = stablehlo.add %0, %1 : tensor<8x16xf32>
        %8 = stablehlo.constant dense<1.0> : tensor<1024x1024xf32>
        """)
    ops = cost.op_costs(text)
    # the reduce continuation line (no type signature) is dropped; the
    # constant is structural
    assert [o.klass for o in ops] == ["reduce", "elementwise"]
    red, add = ops
    assert red.flops == 8 * 16
    assert add.flops == 8 * 16
    assert add.bytes == 3 * 8 * 16 * 4


def test_summarize_tile_waste_has_a_noise_floor():
    big = cost.op_costs(DOT_LINE)[0]          # shape-bound
    tiny = cost.op_costs(DOT_LINE)[0]
    tiny.flops = 1                             # negligible share
    s = cost.summarize([big, tiny])
    assert s["hazards"]["mxu-tile-waste"] == 1
    assert s["verdicts"]["shape-bound"] == 2
    assert s["flops"] == big.flops + 1


# -- collective schedule parsing and the contract diff -----------------------


COMPILED_HLO = textwrap.dedent("""
    %all-gather-start.1 = (f32[2,64]{1,0}, f32[16,64]{1,0}) all-gather-start(f32[2,64]{1,0} %p), replica_groups={}
    %all-gather-done.1 = f32[16,64]{1,0} all-gather-done((f32[2,64]{1,0}, f32[16,64]{1,0}) %all-gather-start.1)
    %add.7 = f32[16,64]{1,0} add(f32[16,64]{1,0} %x, f32[16,64]{1,0} %y)
    %all-reduce.2 = f32[16,64]{1,0} all-reduce(f32[16,64]{1,0} %g), to_apply=%sum
    """)


def test_parse_schedule_counts_starts_not_dones():
    sched = collectives.parse_schedule(COMPILED_HLO)
    assert [op.op for op in sched] == ["all-gather", "all-reduce"]
    # async tuple: the last shaped buffer is the gathered output
    assert sched[0].bytes == 16 * 64 * 4
    assert sched[1].bytes == 16 * 64 * 4
    s = collectives.summarize_schedule(sched)
    assert s["counts"] == {"all-gather": 1, "all-reduce": 1}
    assert s["total_bytes"] == 2 * 16 * 64 * 4
    assert s["order"] == ["all-gather", "all-reduce"]


def test_parse_schedule_counts_every_output_of_a_combined_collective():
    """The combiner passes emit variadic, tuple-valued collectives (the
    flagship's 174 gradient all-reduces arrive as ~30 of them): every
    output counts, not the last. Async gather/permute starts carry
    (operands, outputs, context): only the outputs count."""
    sched = collectives.parse_schedule(textwrap.dedent("""
    %all-reduce.5 = (f32[256]{0}, f32[3,3,8,4]{3,2,1,0}, bf16[64]{0}) all-reduce(%a, %b, %c), channel_id=1
    %all-to-all.3 = (f32[1,24,64,3]{3,2,1,0}, f32[1,24,64,3]{3,2,1,0}) all-to-all(%d, %e), channel_id=2
    %all-gather-start.2 = ((f32[2,64]{1,0}, f32[2,8]{1,0}), (f32[16,64]{1,0}, f32[16,8]{1,0})) all-gather-start(%f, %g)
    %collective-permute-start.1 = (f32[4,8]{1,0}, f32[4,8]{1,0}, u32[], u32[]) collective-permute-start(%h)
    %all-reduce-start.9 = (f32[10]{0}, f32[20]{0}) all-reduce-start(%i, %j)
    """))
    assert [(op.op, op.bytes) for op in sched] == [
        ("all-reduce", 256 * 4 + 3 * 3 * 8 * 4 * 4 + 64 * 2),
        ("all-to-all", 2 * 24 * 64 * 3 * 4),
        ("all-gather", 16 * 64 * 4 + 16 * 8 * 4),
        ("collective-permute", 4 * 8 * 4),
        ("all-reduce", 30 * 4),
    ]


def _mesh_partitioner():
    mesh = parallel.make_mesh((4, 2))
    rules = ((r".*kernel$", P("model")), (r".*", P()))
    return parallel.Partitioner(mesh, rules=rules)


TOY_PARAMS = {"Conv_0": {"kernel": jnp.zeros((8, 4)),
                         "bias": jnp.zeros((4,))}}


def test_expected_schedule_from_partitioner_rules():
    exp = collectives.expected_schedule(
        "train_step", 8, partitioner=_mesh_partitioner(),
        params=TOY_PARAMS)
    assert exp.phases == ("all-gather", "reduce")
    assert exp.sharded_leaves == 1
    assert exp.gather_bytes == 8 * 4 * 4          # the kernel, full bytes
    assert exp.reduce_bytes == (8 * 4 + 4) * 4    # whole gradient mass
    # eval never reduces; single device expects nothing at all
    assert "reduce" not in collectives.expected_schedule(
        "eval_step", 8, partitioner=_mesh_partitioner(),
        params=TOY_PARAMS).phases
    assert collectives.expected_schedule("train_step", 1).phases == ()


def _exp(**kw):
    base = dict(kind="train_step", n_devices=8,
                phases=("all-gather", "reduce"),
                gather_bytes=1 << 20, reduce_bytes=1 << 20,
                sharded_leaves=3)
    base.update(kw)
    return collectives.Expectation(**base)


def _summary(gather=1 << 20, reduce=None, order=("all-gather",
                                                 "all-reduce")):
    reduce = (1 << 20) + (1 << 17) if reduce is None else reduce
    counts, volumes = {}, {}
    for op in order:
        counts[op] = counts.get(op, 0) + 1
    if gather:
        volumes["all-gather"] = gather
    if reduce:
        volumes["all-reduce"] = reduce
    return {"counts": counts, "bytes": volumes,
            "total_bytes": sum(volumes.values()), "order": list(order)}


def test_diff_healthy_schedule_is_clean():
    assert collectives.diff(_exp(), _summary()) == []


def test_diff_flags_gather_collapse_doubling_and_order():
    rules = lambda found: {f.rule for f in found}  # noqa: E731
    # volume collapse, not absence: incidental gathers survive but the
    # param mass is gone
    assert rules(collectives.diff(_exp(), _summary(gather=1 << 16))) == \
        {"collective-missing"}
    # vanished gradient reduce
    assert "collective-missing" in rules(collectives.diff(
        _exp(), _summary(reduce=0, order=("all-gather",))))
    # the PR-6 doubled-reduction signature
    assert rules(collectives.diff(
        _exp(), _summary(reduce=3 << 20))) == {"collective-doubled"}
    # gather scheduled after every reduce: not gather-compute any more
    assert "collective-order" in rules(collectives.diff(
        _exp(), _summary(order=("all-reduce", "all-gather"))))


# -- pinned budget discipline ------------------------------------------------


def _report(key="K", flops=10_000, nbytes=1_000_000, cbytes=1000,
            hazards=None, counts=None):
    return {"key": key, "kind": "train_step", "flops": flops,
            "bytes": nbytes, "intensity": 0.0, "verdicts": {},
            "hazards": hazards or {},
            "collectives": {"counts": counts or {}, "bytes": {},
                            "total_bytes": cbytes, "order": []}}


def _budget(**entry):
    e = {"flops": 10_000, "bytes": 1_000_000, "collective_bytes": 1000,
         "collectives": {"collective-permute": 2}, "verdicts": {}}
    e.update(entry)
    return cost.Budget({"version": 1, "entries": {"K": e}})


def test_budget_tolerances_and_drift():
    b = _budget()
    # within ±5% flops / ±8% bytes / ±2% collective bytes: green
    ok = _report(flops=10_400, nbytes=1_070_000, cbytes=1015,
                 counts={"collective-permute": 2})
    assert b.check(ok) == []
    assert b.unused_entries() == []
    drift = _budget().check(_report(flops=11_000))
    assert [f.rule for f in drift] == ["cost-budget"]
    assert "flops" in drift[0].message and "--update" in drift[0].message
    drift = _budget().check(_report(cbytes=2000))
    assert [f.rule for f in drift] == ["cost-budget"]


def test_budget_unpinned_hazard_growth_and_reshard():
    found = _budget().check(_report(key="other"))
    assert [f.rule for f in found] == ["cost-unpinned"]
    b = _budget(hazards={"f32-upcast": 9})
    # grandfathered count is fine; growth is not
    assert b.check(_report(hazards={"f32-upcast": 9})) == []
    found = _budget(hazards={"f32-upcast": 9}).check(
        _report(hazards={"f32-upcast": 10}))
    assert [f.rule for f in found] == ["cost-hazard"]
    found = _budget().check(_report(counts={"collective-permute": 3}))
    assert [f.rule for f in found] == ["collective-reshard"]
    # a never-checked entry is stale
    assert _budget().unused_entries() == ["K"]


def test_budget_pin_roundtrip_and_version_gate(tmp_path):
    rep = _report(hazards={"f32-upcast": 2}, counts={"all-reduce": 4})
    data = cost.Budget.empty().pinned_data([rep])
    assert data["version"] == 1 and data["programs"] == 1
    path = tmp_path / cost.BUDGET_NAME
    path.write_text(json.dumps(data))
    b = cost.Budget.load(path)
    assert b.check(rep) == []           # pins reproduce the report
    with pytest.raises(ValueError):
        cost.Budget({"version": 99})


# -- the tier-1 gate: every registered program vs the committed pins ---------


@pytest.fixture(scope="module")
def audited():
    """One shared audit pass over the full program set (flagship n=2,
    the (4, 2)-mesh ZeRO variant, every ladder rung, the video warm
    variant, the quant tier, the augmented train step and the synth
    renderer) against the committed budget — the expensive compiles
    happen once per module."""
    entries = cost.build_entries()
    budget = cost.Budget.load(REPO / cost.BUDGET_NAME)
    report = cost.audit_costs(entries=entries, budget=budget)
    return entries, report


def test_budget_gate_green_on_committed_pins(audited):
    _, rep = audited
    assert rep.ok, cost.render_reports(rep)
    assert rep.stale == [], f"stale budget pins: {rep.stale}"
    n = 13 if jax.device_count() >= 8 else 11
    assert len(rep.reports) == n
    # the video warm-start variant is part of the audited set
    assert any("'warm', 'True'" in r["key"] for r in rep.reports)
    # ... as are the quantized matching-tier variants (u8/i8 base rung
    # plus the u8 warm frame)
    assert sum("'quant'" in r["key"] for r in rep.reports) == 3
    # ... and the on-device data engine: the augmented train-step flag
    # variant plus the synth renderer
    assert sum("'augment'" in r["key"] for r in rep.reports) == 1
    assert any("'synth_pair'" in r["key"] for r in rep.reports)
    # every audited program is pinned, and pinned exactly
    pinned = set(json.loads(
        (REPO / cost.BUDGET_NAME).read_text())["entries"])
    assert {r["key"] for r in rep.reports} <= pinned


def test_flagship_verdicts_match_perf_attribution(audited):
    _, rep = audited
    ev = next(r for r in rep.reports
              if r["kind"] == "eval_step" and r["n_devices"] == 2)
    dots = [o for o in ev["ops"] if o["class"] == "dot"]
    convs = [o for o in ev["ops"] if o["class"] == "conv"]
    assert dots and convs
    # PERF.md: the windowed correlation lookup is shape-bound (its 9-row
    # einsums starve the MXU tiles) ...
    lookup = [o for o in dots if min(o["mkn"]) <= 9]
    assert lookup and all(o["verdict"] == "shape-bound" for o in lookup)
    assert all(o["tile_util"] < cost.TILE_OK for o in lookup)
    # ... while the GRU/encoder convolutions (wide in AND out channels)
    # tile cleanly and are MXU-bound; the 2-channel flow-head conv is
    # correctly *not* in this set — its rhs fills 2 of 128 lanes
    big = [o for o in convs if o["mkn"][1] >= 512 and o["mkn"][2] >= 64]
    assert big and all(o["verdict"] == "mxu-bound" for o in big)
    head = [o for o in convs if o["mkn"][2] <= 2]
    assert all(o["verdict"] == "shape-bound" for o in head)
    assert ev["verdicts"].get("shape-bound", 0) >= 1


def test_mesh2d_schedule_matches_the_zero_contract(audited):
    if jax.device_count() < 8:
        pytest.skip("needs the 8-device virtual topology")
    _, rep = audited
    m2 = next(r for r in rep.reports
              if r["kind"] == "train_step" and r["n_devices"] == 8)
    exp = m2["expected_collectives"]
    # the partitioner-derived contract: params gathered, grads reduced
    assert exp["phases"] == ["all-gather", "reduce"]
    assert exp["sharded_leaves"] > 0
    assert exp["gather_bytes"] > 2 ** 20
    actual = m2["collectives"]
    # GSPMD really emits the gather at (or above) the sharded param mass
    assert actual["bytes"]["all-gather"] >= \
        collectives.GATHER_COLLAPSE * exp["gather_bytes"]
    reduce = sum(actual["bytes"].get(op, 0)
                 for op in collectives.REDUCE_OPS)
    assert exp["reduce_bytes"] <= reduce <= \
        collectives.DOUBLED_FACTOR * exp["reduce_bytes"]
    order = actual["order"]
    gathers = [i for i, op in enumerate(order) if op == "all-gather"]
    reduces = [i for i, op in enumerate(order)
               if op in collectives.REDUCE_OPS]
    assert min(gathers) < max(reduces)


# -- seeded regressions: each must flip the gate red -------------------------


def test_seeded_f32_conv_under_bf16_policy_goes_red():
    """Re-introduce the bug the f32-upcast hazard exists for: a model
    whose bf16 policy is dropped lowers every dot/conv in f32; the
    hazard count blows past the grandfathered ladder level and the
    budget check names the right finding class."""
    from raft_meets_dicl_tpu import models
    from raft_meets_dicl_tpu.evaluation import make_rung_fn

    cfg = {
        "name": "cost seed f32", "id": "cost-seed-f32",
        "model": {"type": "raft/baseline",
                  "parameters": {"corr-levels": 2, "corr-radius": 2,
                                 "corr-channels": 32,
                                 "context-channels": 16,
                                 "recurrent-channels": 16,
                                 "mixed-precision": False}},
        "loss": {"type": "raft/sequence"},
        "input": {"padding": {"type": "modulo", "mode": "zeros",
                              "size": [8, 8]}},
    }
    spec = models.load(cfg)
    rng = np.random.RandomState(0)
    img1 = jnp.asarray(rng.rand(1, 48, 64, 3).astype(np.float32))
    img2 = jnp.asarray(rng.rand(1, 48, 64, 3).astype(np.float32))
    variables = spec.model.init(jax.random.PRNGKey(0), img1, img2,
                                iterations=1)
    prog = make_rung_fn(spec.model, 2, model_id=spec.id)
    # lowering only: the walker needs no compile to see the f32 surface
    report, findings = cost.program_cost(
        prog, (variables, img1, img2), expect_bf16=True, do_compile=False)
    assert findings == []
    # the healthy ladder grandfathers 9 f32 dots (the intentionally-f32
    # lookup path); a policy-less model is far beyond that
    seeded = report["hazards"]["f32-upcast"]
    assert seeded > 9
    healthy = cost.Budget({"version": 1, "entries": {report["key"]: {
        "flops": report["flops"], "bytes": report["bytes"],
        "collective_bytes": 0, "collectives": {},
        "hazards": {"f32-upcast": 9, "mxu-tile-waste": 2}}}})
    found = healthy.check(report)
    assert any(f.rule == "cost-hazard" and "f32-upcast" in f.message
               for f in found), [f.message for f in found]


def test_seeded_dead_partition_rule_goes_red(audited):
    """Delete the partition rules and the compiled program degenerates
    to the replicated one (bit-for-bit — partition.py's contract); the
    auditor must flag the vanished param all-gather. The replicated n=2
    train program *is* that degenerate schedule, so no extra compile is
    needed to seed the regression."""
    if jax.device_count() < 8:
        pytest.skip("needs the 8-device virtual topology")
    entries, rep = audited
    kwargs = next(k for _, _, k in entries
                  if k.get("partitioner") is not None)
    exp = collectives.expected_schedule(
        "train_step", 8, partitioner=kwargs["partitioner"],
        params=kwargs["params"])
    assert exp.phases == ("all-gather", "reduce")
    assert exp.sharded_leaves > 0
    replicated = next(r for r in rep.reports
                      if r["kind"] == "train_step"
                      and r["n_devices"] == 2)
    found = collectives.diff(exp, replicated["collectives"],
                             key="seeded-dead-rule")
    assert any(f.rule == "collective-missing" and "all-gather" in
               f.message for f in found), [f.message for f in found]
    # and the root cause is visible on the expectation side too: a rule
    # set that matches nothing shards zero leaves, expecting no gather
    crippled = parallel.Partitioner(
        parallel.make_mesh((4, 2)),
        rules=((r"NoSuchModule/.*kernel$", P("model")), (r".*", P())))
    exp0 = collectives.expected_schedule(
        "train_step", 8, partitioner=crippled, params=kwargs["params"])
    assert exp0.sharded_leaves == 0
    assert "all-gather" not in exp0.phases


# -- reporting surfaces ------------------------------------------------------


def test_cost_events_flow_into_telemetry_report(audited):
    _, rep = audited
    tele = telemetry.Telemetry()          # in-memory sink
    cost.emit_events(rep, tele)
    from raft_meets_dicl_tpu.telemetry import report as trep

    stats = trep.cost_stats(tele.events)
    assert len(stats["programs"]) == len(rep.reports)
    text = trep.render(tele.events)
    assert "== program costs" in text
    for r in rep.reports:
        # the report line truncates long ProgramKey reprs to 72 chars
        assert r["key"][:72] in text


def test_render_reports_shows_findings_and_stale():
    from raft_meets_dicl_tpu.analysis.lint import Finding

    cr = cost.CostReport(
        reports=[_report()],
        findings=[Finding(rule="cost-budget", path="analysis/cost",
                          line=1, message="drift")],
        stale=["gone-key"])
    text = cost.render_reports(cr)
    assert "== program costs ==" in text
    assert "! cost-budget: drift" in text
    assert "stale budget entry: gone-key" in text
    assert not cr.ok
    d = cr.to_dict()
    assert d["ok"] is False and d["stale_budget_entries"] == ["gone-key"]


def test_graftcost_cli_json_schema():
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "graftcost_cli", REPO / "scripts" / "graftcost.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    payload = mod.json_report(cost.CostReport(reports=[_report()]))
    assert payload["schema"] == 1
    assert payload["ok"] is True and payload["exit_code"] == 0
    json.dumps(payload)
