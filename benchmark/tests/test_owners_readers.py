"""The phase readers (``encoder_ms`` ... ``step_unowned_ms``, ``mnet_ms``, the
``serve_*`` ones) and the join they share, ``layers/_owners.py``.

    python3 -m pytest benchmark/tests/test_owners_readers.py -q

On a small recorded capture, two train steps of ``raft-train-things`` on one
v5e chip with the ``owners`` record the same run's program emitted (my chip
run, PR 37; ``tests/dump_ops.py`` made the capture, the record is the run's
``aot`` event with the keys of operations the capture does not hold left
out), and on instruction texts. A run with no record, with another
program's record, or with two records that own one name differently must
read nothing, or unowned, and say why; none may raise.
"""

import json
import sys
from pathlib import Path
from types import SimpleNamespace

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from benchmark.harness import spec, xtrace  # noqa: E402
from benchmark.layers import _owners  # noqa: E402

DATA = Path(__file__).parent / "data"
CAPTURE = DATA / "owners_capture_small.json.gz"
EVENTS = DATA / "owners_capture_events.json"

TRAIN = ("encoder_ms", "corr_build_ms", "lookup_ms", "update_ms", "up8_ms",
         "loss_ms", "optimizer_ms", "step_unowned_ms")
SERVE = ("serve_encoder_ms", "serve_corr_build_ms", "serve_lookup_ms",
         "serve_update_ms", "serve_up8_ms", "serve_unowned_ms")

CONV = ("%convolution_convert_fusion.16 = bf16[8,48,160,9,9]{4,3,2,1,0:"
        "T(8,128)(2,1)} fusion(), kind=kOutput")
COPY = "%copy.412 = f32[6,50,90,128]{3,0,2,1:T(8,128)} copy()"
NORM = "%fusion.77 = f32[544,2,968,64]{3,2,1,0:T(8,128)} fusion(), kind=kLoop"
UP8 = ("%Up8Network_0.2 = f32[391680,128]{1,0:T(8,128)S(1)} custom-call(), "
       "custom_call_target=\"tpu_custom_call\"")
MNET = ("%convolution_fusion.9 = bf16[486,48,80,96]{3,2,1,0:T(8,128)(2,1)} "
        "fusion(), kind=kOutput")
ADAM = "%fusion.3 = f32[3,3,256,192]{3,2,1,0:T(8,128)} fusion(), kind=kLoop"
STRAY = "%copy-done.5 = f32[64]{0:T(128)S(1)} copy-done()"


def _record(owners, inferred=(), module="jit_step", **extra):
    return {"kind": "aot", "event": "owners", "program": "train_step",
            "model": "m", "module": module, "owners": owners,
            "inferred_keys": list(inferred), "instructions": 7,
            "inferred": len(inferred), "unowned": 0, "seconds": 0.1, **extra}


def _run(events, op_s, kind="train", executions=2):
    return {"kind": kind, "events": events,
            "devices": [SimpleNamespace(device_kind="TPU v5 lite")],
            "trace": {"executions": executions, "op_s": dict(op_s),
                      "module": ["jit_step(123)"],
                      "op_count": {k: executions for k in op_s}}}


def _read(name, run):
    return spec.load_reader(name)(run)


OWNERS = {
    "lookup": {"lookup": {"fwd": ["convolution_convert_fusion.16:"
                                  "bf16[8,48,160,9,9]"]},
               "mnet": {"bwd": ["convolution_fusion.9:bf16[486,48,80,96]"]}},
    "update": {"update": {"bwd": ["copy.412:f32[6,50,90,128]"]}},
    "encoders": {"encoders": {"fwd": ["fusion.77:f32[544,2,968,64]"]}},
    "up8": {"up8": {"fwd": ["Up8Network_0.2:f32[391680,128]"]}},
    "optimizer": {"optimizer": {"fwd": ["fusion.3:f32[3,3,256,192]"]}},
    "unowned": {"": {"fwd": ["copy-done.5:f32[64]"]}},
}
OPS = {CONV: 0.040, COPY: 0.010, NORM: 0.020, UP8: 0.004, MNET: 0.060,
       ADAM: 0.006, STRAY: 0.002}


def test_the_key_is_cut_from_the_events_text():
    assert _owners.key_of(CONV) == \
        "convolution_convert_fusion.16:bf16[8,48,160,9,9]"
    assert _owners.key_of(UP8) == "Up8Network_0.2:f32[391680,128]"
    assert _owners.key_of("%copy-start.308 = (f32[5,1,384,128]{3,2,1,0:"
                          "T(8,128)S(1)}, f32[5,1,384,128]{3,2,1,0}, u32[]"
                          "{:S(2)}) copy-start()") \
        == "copy-start.308:f32[5,1,384,128]"
    # a host span or a name without a type
    assert _owners.key_of("jit_step") == "jit_step:"


def test_readers_on_texts(capsys):
    run = _run([_record(OWNERS, inferred=["copy.412:f32[6,50,90,128]"])], OPS)
    got = {name: _read(name, run) for name in TRAIN + ("mnet_ms",)}
    assert got == pytest.approx({
        "encoder_ms": 10.0, "corr_build_ms": 0.0, "lookup_ms": 50.0,
        "update_ms": 5.0, "up8_ms": 2.0, "loss_ms": 0.0,
        "optimizer_ms": 3.0, "step_unowned_ms": 1.0, "mnet_ms": 30.0})
    # the phases sum to the traced operations' time, class by class
    tab = _owners.table(run, "train")
    assert tab["total_ms"] == pytest.approx(71.0)
    assert sum(sum(c.values()) for c in tab["rows"].values()) == \
        pytest.approx(71.0)
    conv = sum(c.get("conv", 0.0) for c in tab["rows"].values())
    assert conv == pytest.approx(50.0)
    assert tab["covered"] == pytest.approx(1.0)
    assert tab["inferred"] == pytest.approx(5.0 / 71.0)
    assert tab["unowned"] == pytest.approx(1.0 / 71.0)
    out = capsys.readouterr().out
    assert out.count("[owners] modules=") == 1          # printed once a run
    assert "lookup by scope: mnet bwd 30.00  conv=30.00" in out
    assert "up8       fwd      2.00  mosaic=2.00" in out
    assert "unowned: copy-done.5:f32[64] 1.000 ms (no owner found)" in out
    # the serve readers want a serve run
    assert all(_read(name, run) is None for name in SERVE)


def test_serve_readers_take_both_buckets_records(capsys):
    other = ("%convolution_convert_fusion.16 = bf16[8,56,128,9,9]{4,3,2,1,0}"
             " fusion(), kind=kOutput")
    second = {"lookup": {"lookup": {"fwd": [
        "convolution_convert_fusion.16:bf16[8,56,128,9,9]"]}},
        # the same name and type under another owner in the other program
        "encoders": {"encoders": {"fwd": ["copy.412:f32[6,50,90,128]"]}}}
    run = _run([_record(OWNERS) | {"program": "eval_step"},
                _record(second) | {"program": "eval_step"}],
               OPS | {other: 0.030}, kind="serve")
    # the two buckets' look-ups do not answer for each other
    assert _read("serve_lookup_ms", run) == pytest.approx(65.0)
    # two owners for one key: unowned, and said
    assert _read("serve_update_ms", run) == pytest.approx(0.0)
    assert _read("serve_unowned_ms", run) == pytest.approx(6.0)
    assert "copy.412:f32[6,50,90,128] 5.000 ms (two owners)" in \
        capsys.readouterr().out
    assert all(_read(name, run) is None for name in TRAIN)


@pytest.mark.parametrize("events, says", [
    # a program from before the record (the parent's tree)
    ([{"kind": "aot", "event": "hit", "program": "train_step"}],
     "no owners record"),
    # a record of another module of the run
    ([_record(OWNERS, module="jit_train_metrics")], "no owners record"),
    # another tree's program: its instruction names differ
    ([_record({"lookup": {"lookup": {"fwd": [
        "convolution_convert_fusion.16:bf16[8,48,160,9,9]",
        "fusion.9001:f32[1]"]}}})], "cover 28.2% of the traced time"),
])
def test_no_record_or_anothers_record_reads_nothing_and_says_why(
        events, says, capsys):
    run = _run(events, OPS)
    for name in TRAIN + ("mnet_ms",):
        assert _read(name, run) is None
    out = capsys.readouterr().out
    assert says in out and out.count("[owners]") == 1


def test_no_trace_no_reading():
    run = _run([_record(OWNERS)], OPS)
    run["trace"] = None
    assert all(_read(name, run) is None for name in TRAIN + SERVE)


def test_every_new_metric_is_in_the_benchmark_with_a_reader():
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    entries = {m["name"]: m for m in bench["per_layer"]}
    for name in TRAIN + SERVE + ("mnet_ms",):
        m = entries[name]
        assert (m["layer"], m["source"], m["better"], m["unit"]) == \
            ("model step", "device_trace", "lower", "ms")
        assert m["moves"] == ("serve_p95_ms" if name.startswith("serve_")
                              else "train_pairs_per_s")
        assert callable(spec.load_reader(name))
    # none lists its cells: every program of a cell that reports the
    # end-to-end metric gives a record, and a phase it has not reads 0.0
    # (``mnet_ms`` too: the older cells' self-tests hold every train cell's
    # list of metrics against ``raft-train-things``'s)
    assert all("workloads" not in entries[n]
               for n in TRAIN + SERVE + ("mnet_ms",))


@pytest.mark.skipif(not CAPTURE.exists(), reason="no recorded capture")
def test_readers_on_the_recorded_capture(capsys):
    reduced = xtrace.reduce(xtrace.load_saved(CAPTURE), "jit_step")
    run = {"kind": "train", "events": json.loads(EVENTS.read_text()),
           "devices": [SimpleNamespace(device_kind="TPU v5 lite")],
           "trace": reduced}
    assert reduced["executions"] == 2
    got = {name: _read(name, run) for name in TRAIN}
    assert all(v is not None and v >= 0.0 for v in got.values()), got
    tab = _owners.table(run, "train")
    step_ms = 1e3 * sum(reduced["op_s"].values()) / 2
    assert tab["total_ms"] == pytest.approx(step_ms)
    assert tab["covered"] > 0.98
    # phases, input, other scopes and unowned sum to the operations' time
    by_phase = {}
    for (phase, _, _), by_class in tab["rows"].items():
        by_phase[phase] = by_phase.get(phase, 0.0) + sum(by_class.values())
    assert sum(by_phase.values()) == pytest.approx(step_ms)
    in_metrics = sum(got.values())
    assert in_metrics + by_phase.get("input", 0.0) \
        + by_phase.get("other", 0.0) == pytest.approx(step_ms)
    assert got["step_unowned_ms"] < 0.10 * step_ms
    # the new measure agrees with the old where both see the same thing
    for cls in ("conv", "mosaic", "copy"):
        old = 1e3 * reduced["class_s_per_exec"].get(cls, 0.0)
        new = sum(c.get(cls, 0.0) for c in tab["rows"].values())
        assert new == pytest.approx(old), cls
    up8_mosaic = sum(c.get("mosaic", 0.0) for o, c in tab["rows"].items()
                     if o[0] == "up8")
    assert up8_mosaic == pytest.approx(
        1e3 * reduced["class_s_per_exec"]["mosaic"], rel=0.01)
    # the parent's program in the same capture: nothing, and why
    silent = dict(run, events=[e for e in run["events"]
                               if e.get("event") != "owners"])
    silent.pop("owners_table")
    capsys.readouterr()
    assert all(_read(name, silent) is None for name in TRAIN)
    assert "no owners record" in capsys.readouterr().out
