"""The cells ``dicl-serve-mixed`` and ``raft-serve-sintel`` as data, and the
ladder's four readers on programs that say more and less.

    python3 -m pytest benchmark/tests/test_dicl_cell.py -q

On instruction texts, on a small recorded capture (two served batches of
``dicl-serve-mixed`` on one v5e chip with the ``owners`` records the same
run's two executables emitted: my chip run, PR 41; ``tests/dump_ops.py``
made the capture, the records keep the keys of the operations it holds),
and through the CPU rehearsal, twice from one program store: the second
process loads both eval programs and must still report what their traces
noted.
"""

import gzip
import json
import os
import subprocess
import sys
from pathlib import Path
from types import SimpleNamespace

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from benchmark.harness import check, schedule, spec, xtrace  # noqa: E402

CELL, CONTROL = "dicl-serve-mixed", "raft-serve-sintel"
NEW = {"serve_warp_ms", "serve_context_ms", "serve_mnet_ms",
       "serve_matching_mb_per_batch"}
DATA = Path(__file__).parent / "data"
CAPTURE = DATA / "dicl_capture_small.json.gz"
EVENTS = DATA / "dicl_capture_events.json"


def test_the_cells_list_their_metrics_and_every_reader_loads():
    cell, control = spec.load_cell(CELL), spec.load_cell(CONTROL)
    mixed = spec.load_cell("raft-serve-mixed")
    for c in (cell, control):
        assert c.chips == 1
        assert [m["name"] for m in c.end_to_end] == ["serve_p95_ms",
                                                     "setup_s"]
        assert set(check.limits_for(c.name)) == {"serve_flow_gap"}
        for m in c.per_layer:
            assert callable(spec.load_reader(m["name"]))
    assert cell.config["reference"] == "dicl"
    assert control.config == mixed.config          # raft-baseline, unchanged
    serve = {m["name"] for m in mixed.per_layer}
    assert {m["name"] for m in control.per_layer} == serve   # no new reader
    assert {m["name"] for m in cell.per_layer} == serve | NEW
    # the ladder's readers are this cell's alone
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    for m in bench["per_layer"]:
        if m["name"] in NEW:
            assert m["workloads"] == [CELL] and m["moves"] == "serve_p95_ms"


def test_the_traffic_is_serve_mixed_to_the_letter_and_sintel_one_shape():
    mixed = spec.load_cell("raft-serve-mixed").traffic
    ours = spec.load_cell(CELL).traffic
    same = ("kind", "group", "shapes", "clients", "payload_pool", "discard_s",
            "trace_s", "timeout_s", "check_per_shape", "trace_module")
    assert {k: ours[k] for k in same} == {k: mixed[k] for k in same}
    sintel = spec.load_cell(CONTROL).traffic
    assert sintel["shapes"] == [{"size": [436, 1024], "weight": 1}]
    assert (sintel["group"], sintel["clients"]) == (8, 8)
    for traffic in (ours, sintel):
        rate = traffic["rate_per_s"]
        assert rate == int(rate) and "sweep" in traffic["rate_from"]
        # whole groups, and requests due past the window for the trace
        plan = schedule.build(traffic, 7, 50 + traffic["trace_s"])
        assert len(plan) % traffic["group"] == 0
        assert plan[-1][0] >= traffic["discard_s"] + 50
    # eight requests are followed by the reference in both cells
    assert ours["check_per_shape"] * len(ours["shapes"]) == 8
    assert sintel["check_per_shape"] * len(sintel["shapes"]) == 8


def test_the_configuration_is_the_published_one_with_nothing_reduced():
    import yaml

    cfg = spec.load_cell(CELL).config
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    entry = next(c for c in bench["configs"] if c["name"] == "dicl-baseline")
    assert entry["reduced"] == [] == cfg["reduced"]
    model = cfg["model"]
    assert model["id"] == "dicl/baseline"
    upstream = yaml.safe_load((ROOT / "cfg/model/dicl-baseline.yaml")
                              .read_text())
    p = dict(model["model"]["parameters"])
    # the one key written out beside the yaml's is the program's default
    assert p.pop("feature-channels") == 32
    assert p == upstream["model"]["parameters"]
    assert model["model"]["arguments"] == upstream["model"]["arguments"]
    assert model["input"] == upstream["input"]
    assert all(r == [3, 3] for r in p["displacement-range"].values())
    assert cfg["serve"]["buckets"] == "384x1280,512x1024"
    assert (cfg["serve"]["wire-format"], cfg["serve"]["max-wait-ms"],
            cfg["serve"]["queue-limit"]) == ("u8", 50, 64)
    assumed = {e["key"] for e in cfg["assumed"]}
    assert assumed == {"serve.buckets", "serve.batch-size", "model.arguments"}
    assert all(e["why"] for e in cfg["assumed"])
    assert cfg["control_precision"] == "float8_e4m3fn"
    # the written-out widths are the program's own
    from raft_meets_dicl_tpu import models

    got = models.load(upstream).model.get_config()["parameters"]
    assert got["feature-channels"] == 32
    assert got["displacement-range"] == p["displacement-range"]
    # and both buckets take the model's padding
    from raft_meets_dicl_tpu.models.input import ShapeBuckets

    ShapeBuckets.from_config(cfg["serve"]["buckets"]).check_compatible(
        models.load(upstream).input.padding)


# -- the readers on texts ------------------------------------------------------


GATHER = "%gather.96 = f32[8,32768,32]{2,1,0:T(8,128)} gather()"
MNET = ("%conv_general_dilated.6 = f32[392,128,256,96]{0,3,2,1:T(8,128)} "
        "convolution()")
STACK = "%fusion.86 = f32[392,128,256,64]{3,0,2,1:T(8,128)} fusion(), kind=kLoop"
CTX = ("%conv_general_dilated.70 = f32[8,128,256,128]{3,0,2,1:T(8,128)} "
       "convolution()")
ENC = "%conv_general_dilated.43 = bf16[16,256,512,32]{3,0,2,1} convolution()"
OPS = {GATHER: 0.030, MNET: 0.120, STACK: 0.040, CTX: 0.016, ENC: 0.024}

SCOPED = {
    "warp": {"warp": {"fwd": ["gather.96:f32[8,32768,32]"]}},
    "lookup": {"mnet": {"fwd": ["conv_general_dilated.6:f32[392,128,256,96]"]},
               "matching": {"fwd": ["fusion.86:f32[392,128,256,64]"]}},
    "context": {"context": {"fwd": [
        "conv_general_dilated.70:f32[8,128,256,128]"]}},
    "encoders": {"encoders": {"fwd": [
        "conv_general_dilated.43:bf16[16,256,512,32]"]}},
}
# the parent's program states no scope: the module's class names it all
UNSCOPED = {"other": {"DiclModule": {"fwd": [
    k for scopes in SCOPED.values() for d in scopes.values()
    for keys in d.values() for k in keys]}}}


def _record(owners, **extra):
    return {"kind": "aot", "event": "owners", "program": "eval_step",
            "model": "dicl/baseline", "module": "jit_step", "owners": owners,
            "inferred_keys": [], "instructions": 5, "inferred": 0,
            "unowned": 0, "seconds": 0.1, **extra}


def _aot(event="hit", **notes):
    return {"kind": "aot", "event": event, "program": "eval_step", **notes}


def _run(events, op_s=OPS, kind="serve", executions=2, trace=True):
    return {"kind": kind, "events": events,
            "devices": [SimpleNamespace(device_kind="TPU v5 lite")],
            "trace": {"executions": executions, "op_s": dict(op_s),
                      "module": ["jit_step(123)"],
                      "op_count": {k: executions for k in op_s}}
            if trace else None}


def _read(run):
    return {name: spec.load_reader(name)(run) for name in sorted(NEW)}


def test_readers_on_texts_of_a_program_that_states_its_scopes(capsys):
    notes = [_aot(matching_volume_bytes=3_422_552_064, warp_calls=4),
             _aot("save", matching_volume_bytes=3_208_642_560, warp_calls=4),
             # the compile event of the saved one says the same again
             {"kind": "compile", "label": "eval_step", "seconds": 60.0,
              "matching_volume_bytes": 3_208_642_560, "warp_calls": 4}]
    run = _run([_record(SCOPED)] + notes)
    assert _read(run) == pytest.approx({
        "serve_warp_ms": 15.0, "serve_context_ms": 8.0, "serve_mnet_ms": 60.0,
        "serve_matching_mb_per_batch": (3422.552064 + 3208.64256) / 2})
    # the accepted serve readers read their phases of the same table, and
    # 0.0, not nothing, where the ladder has no such phase
    got = {name: spec.load_reader(name)(run) for name in (
        "serve_encoder_ms", "serve_lookup_ms", "serve_update_ms",
        "serve_corr_build_ms", "serve_up8_ms", "serve_unowned_ms")}
    assert got == pytest.approx({
        "serve_encoder_ms": 12.0, "serve_lookup_ms": 80.0,
        "serve_update_ms": 0.0, "serve_corr_build_ms": 0.0,
        "serve_up8_ms": 0.0, "serve_unowned_ms": 0.0})
    out = capsys.readouterr().out
    assert "[owners] warp      fwd     15.00" in out
    assert "lookup by scope: mnet fwd 60.00" in out


@pytest.mark.parametrize("events, trace", [
    ([], True),                                   # no record at all
    ([_record(UNSCOPED)], True),                  # the parent's program
    ([_record(UNSCOPED), _aot()], True),          # ... and it notes nothing
    ([_record(SCOPED)], False),                   # an untraced run
    ([_record(SCOPED, program="train_step")], True),
], ids=["no-record", "parent", "parent-no-notes", "untraced", "train-step"])
def test_no_reader_raises_or_reads_zero_where_the_program_says_nothing(
        events, trace):
    run = _run(events, trace=trace)
    assert _read(run) == dict.fromkeys(sorted(NEW))
    assert _read(run | {"kind": "train"}) == dict.fromkeys(sorted(NEW))


def test_the_note_reader_needs_no_trace():
    run = _run([_aot(matching_volume_bytes=2_000_000)], trace=False)
    assert _read(run)["serve_matching_mb_per_batch"] == 2.0
    assert _read(run | {"kind": "train"})["serve_matching_mb_per_batch"] \
        is None


# -- the recorded capture -------------------------------------------------------


@pytest.fixture(scope="module")
def recorded():
    capture = json.loads(gzip.decompress(CAPTURE.read_bytes()))
    events = json.loads(EVENTS.read_text())
    run = {"kind": "serve", "events": events,
           "devices": [SimpleNamespace(device_kind="TPU v5 lite")],
           "trace": xtrace.reduce(capture, "jit_step")}
    return run


def test_readers_on_the_recorded_capture(recorded, capsys):
    from benchmark.layers import _owners

    got = _read(recorded)
    tab = _owners.table(recorded, "serve")
    assert tab["covered"] >= 0.90 and tab["unowned"] < 0.05
    assert all(v is not None and v > 0.0 for v in got.values()), got
    # what the capture showed (PERF.md section 5): the shift stacks and
    # their masks (scope ``matching``) cost more than the MatchingNets they
    # feed, the warps more than the context networks, and the parts stay
    # inside the whole
    batch = tab["total_ms"]
    lookup = spec.load_reader("serve_lookup_ms")(recorded)
    assert 0.15 * batch < got["serve_mnet_ms"] < 0.40 * batch
    assert lookup - got["serve_mnet_ms"] > got["serve_mnet_ms"]
    assert got["serve_warp_ms"] > 0.10 * batch
    assert 0.01 * batch < got["serve_context_ms"] < got["serve_warp_ms"]
    assert (lookup + got["serve_warp_ms"] + got["serve_context_ms"]
            + spec.load_reader("serve_encoder_ms")(recorded)) < batch
    other = sum(sum(c.values()) for o, c in tab["rows"].items()
                if o[0] == "other")
    assert other < 0.10 * batch
    # the five levels' stacked pairs of a batch of 8, float32, 64 channels,
    # at 1/4 ... 1/64: the mean of the two buckets
    def volume(h, w):
        return 8 * 49 * 64 * 4 * sum((h >> lvl) * (w >> lvl)
                                     for lvl in range(2, 7))
    assert got["serve_matching_mb_per_batch"] == pytest.approx(
        (volume(384, 1280) + volume(512, 1024)) / 2 / 1e6)
    for name in ("serve_update_ms", "serve_corr_build_ms"):
        assert spec.load_reader(name)(recorded) == 0.0, name
    # no kernel: 38 ns of the compiler's own marker custom calls a batch
    assert 0.0 <= spec.load_reader("serve_mosaic_ms")(recorded) < 1e-3
    assert "[owners] warp" in capsys.readouterr().out


# -- the rehearsal ---------------------------------------------------------------


def test_rehearsal_is_correct_and_loaded_programs_keep_their_notes(tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cache"))
    # b2, 49 hypotheses of 2 x 8 channels in float32 on five levels
    volumes = [2 * 49 * 16 * 4 * sum((h >> lvl) * (w >> lvl)
                                     for lvl in range(2, 7))
               for h, w in ((128, 128), (128, 256))]
    for boot in ("cold", "warm"):
        proc = subprocess.run(
            [sys.executable, "benchmark/tests/rehearse_dicl.py", "--trace",
             "1", "--seed", "2147483659"],
            cwd=ROOT, env=env, capture_output=True, text=True, timeout=1500)
        assert proc.returncode == 0, proc.stderr[-2000:]
        result = json.loads(proc.stdout.strip().splitlines()[-1])
        assert result["correct"] and result["device"]["platform"] == "cpu"
        assert result["failed"] == 0 and result["attempted"] >= 8
        metrics = {k.removeprefix("cpu_rehearsal."): v["value"]
                   for k, v in result["metrics"].items()}
        assert metrics["serve_matching_mb_per_batch"] == \
            sum(volumes) / 2 / 1e6, boot
        # the CPU's capture has no device plane: no phase is read
        assert not (NEW - {"serve_matching_mb_per_batch"}) & set(metrics)
        events = [json.loads(ln) for ln in (
            ROOT / "bench_out/rehearsal/toy-dicl/seed2147483659_trace1"
            / "events.jsonl").read_text().splitlines()]
        held = [e for e in events if e["kind"] == "aot"
                and e.get("program") == "eval_step"
                and e["event"] in ("hit", "save")]
        assert [e["event"] for e in held] == [
            "save" if boot == "cold" else "hit"] * 2
        assert [e["matching_volume_bytes"] for e in held] == volumes
        assert [e["warp_calls"] for e in held] == [4, 4]
        records = [e for e in events if e["kind"] == "aot"
                   and e["event"] == "owners"]
        assert len(records) == 2
        assert all({"warp", "context"} <= set(r["owners"]) for r in records)
        compiles = [e for e in events if e["kind"] == "compile"
                    and e.get("label") == "eval_step"]
        assert len(compiles) == (2 if boot == "cold" else 0)
