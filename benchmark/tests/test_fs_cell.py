"""The cell ``fs-train-1080p`` as data, the yardstick's arithmetic, and its
readers on programs that say more and less than the change's.

    python3 -m pytest benchmark/tests/test_fs_cell.py -q

The rehearsal drives the cell's driver, reference, check and readers at
toy shapes on the CPU, twice from one program store (the second process
loads the train step and must still report what its trace noted), and
once with every level on the windowed form.
"""

import json
import os
import subprocess
import sys
from pathlib import Path
from types import SimpleNamespace

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from benchmark.harness import check, kernels, spec, wcp_kernel  # noqa: E402

CELL = "fs-train-1080p"
NEW = {"wcp_ms", "wcp_roofline", "wcp_levels_windowed"}


def test_the_cell_lists_its_metrics_and_every_reader_loads():
    cell = spec.load_cell(CELL)
    assert cell.chips == 1 and cell.config["reference"] == "fs"
    assert cell.traffic_name == "train-things"
    assert [m["name"] for m in cell.end_to_end] == ["train_pairs_per_s",
                                                    "setup_s"]
    names = {m["name"] for m in cell.per_layer}
    assert NEW <= names
    assert not {n for n in names if n.startswith(("serve_", "sw_"))}
    assert "up8_combine_roofline" not in names
    for name in names:
        assert callable(spec.load_reader(name))
    # beside its three, what every train cell reports
    other = {m["name"] for m in spec.load_cell("raft-train-things").per_layer}
    assert names - other == NEW
    assert other - names == {"up8_combine_roofline"}
    assert set(check.limits_for(CELL)) == {
        "loss_gap", "flow_gap", "grad_norm_gap", "param_change_gap"}
    # nothing else in the benchmark reads the new metrics
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    for m in bench["per_layer"]:
        if m["name"] in NEW:
            assert m["workloads"] == [CELL]
            assert m["moves"] == "train_pairs_per_s"
            assert m["layer"] == "ops and kernels"


def test_the_configuration_is_the_published_one_with_the_crop_reduced():
    import yaml

    cell = spec.load_cell(CELL)
    model = cell.config["model"]
    assert model["id"] == "raft/fs"
    assert cell.config["reduced"] == ["train.crop"]
    assert set(cell.config["reduced_why"]) == {"train.crop"}
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    entry = next(c for c in bench["configs"] if c["name"] == "raft-fs")
    assert entry["reduced"] == cell.config["reduced"]
    p, a = model["model"]["parameters"], model["model"]["arguments"]
    assert (p["corr-levels"], p["corr-radius"], p["corr-channels"]) == (
        4, 4, 256)
    assert (p["context-channels"], p["recurrent-channels"]) == (128, 128)
    assert p["mixed-precision"] is True and a == {"iterations": 12}
    assert cell.config["train"] == dict(cell.config["train"],
                                        crop=[1088, 1920], batch_per_chip=1)
    assert cell.config["knobs"] == {}
    # what the yaml states it states here too
    upstream = yaml.safe_load((ROOT / "cfg/model/raft-fs.yaml").read_text())
    for key, value in upstream["model"]["parameters"].items():
        assert p[key] == value, key
    assert upstream["model"]["arguments"] == a
    assert upstream["loss"] == model["loss"]
    assert upstream["input"] == model["input"]
    # the written-out defaults are the program's own
    from raft_meets_dicl_tpu import models

    cfg = models.load(upstream).model.get_config()["parameters"]
    assert cfg == p
    # the recipe: one full frame a chip
    recipe = yaml.safe_load((ROOT / "cfg/strategy/highres/"
                             "raft-fs.hd1k-1080p.yaml").read_text())
    assert recipe["stages"][0]["data"]["batch-size"] == 1


# -- the yardstick: hand-computed at the cell's shapes ------------------------

GRID = 136 * 240                         # 32,640 positions
FWD_1 = (GRID * 256 * 2                  # frame one's features, bf16
         + GRID * 256 * 2                # the level-0 map
         + GRID * 2 * 4                  # centres
         + GRID * 81 * 4)                # costs, float32


def test_work_functions_against_hand_computed_bytes_at_136x240():
    config = spec.load_cell(CELL).config
    assert wcp_kernel.shapes(config, 1) == (1, 136, 240, 256, 4, 12, 2, 4)
    shape = (1, 136, 240, 256, 4, 2)
    assert FWD_1 == 44_259_840
    assert wcp_kernel.forward_bytes(*shape, 1) == FWD_1
    assert wcp_kernel.backward_bytes(*shape, 1) == FWD_1 + 2 * GRID * 256 * 2
    assert wcp_kernel.backward_bytes(*shape, 1) == 77_683_200
    assert wcp_kernel.forward_macs(1, 136, 240, 256, 4, 1) == 676_823_040
    # all four levels: the coarser maps are 8160, 2040 and 510 samples
    maps = GRID + 8160 + 2040 + 510
    assert wcp_kernel.forward_bytes(*shape, 4) == (
        GRID * 256 * 2 + maps * 256 * 2 + GRID * 8 + GRID * 4 * 81 * 4)
    # float32 features double the features' and the maps' bytes alone
    assert wcp_kernel.forward_bytes(1, 136, 240, 256, 4, 4, 1) == (
        FWD_1 + 2 * GRID * 256 * 2)


def test_least_time_is_the_larger_floor_and_the_bytes_decide_on_a_v5e():
    config = spec.load_cell(CELL).config
    peaks = kernels.peaks("TPU v5 lite")
    least = wcp_kernel.least_seconds(config, 1, 1, peaks)
    moved = 12 * (44_259_840 + 77_683_200)
    flops = 12 * 3 * 2 * 676_823_040
    assert moved == 1_463_316_480 and flops == 48_731_258_880
    assert least == moved / 819e9
    assert moved / 819e9 > flops / 197e12
    assert abs(1e3 * least - 1.78671) < 1e-4
    # a chip with a fiftieth of the arithmetic: the operations decide
    slow = dict(peaks, flops_bf16=peaks["flops_bf16"] / 50)
    assert wcp_kernel.least_seconds(config, 1, 1, slow) == \
        flops / slow["flops_bf16"]
    # more windowed levels than the model has levels: the model's
    assert wcp_kernel.least_seconds(config, 1, 9, peaks) == \
        wcp_kernel.least_seconds(config, 1, 4, peaks)


# -- the readers --------------------------------------------------------------

FORWARD = ("%wcp.27 = f32[1,136,240,9,9]{4,3,2,1,0:T(8,128)} custom-call("
           "f32[1,136,240,2]{3,2,1,0} %copy-done.17, bf16[1,136,30,8,256] "
           "%bitcast.3703, bf16[1,163,281,256] %pad.3969), "
           "custom_call_target=\"tpu_custom_call\"")
DF1 = ("%wcp.28 = f32[1,136,30,8,256]{4,3,2,1,0:T(8,128)} custom-call("
       "%copy-done.38, %convert_bitcast_fusion.46, %pad.4097), "
       "custom_call_target=\"tpu_custom_call\"")
DF2 = ("%wcp.29 = f32[1,163,281,256]{3,2,1,0:T(8,128)} custom-call("
       "%copy-done.38, %bitcast.3704, %convert_bitcast_fusion.46), "
       "custom_call_target=\"tpu_custom_call\"")
UP8 = ("%Up8Network_0.2 = f32[391680,128]{1,0:T(8,128)} custom-call("
       "%bitcast.120, %copy.3153), custom_call_target=\"tpu_custom_call\"")
MARKER = "%custom-call.7 = f32[1,136,240,9,9]{4,3,2,1,0} custom-call()"
PAD = "%pad.3969 = bf16[1,163,281,256]{3,2,1,0:T(8,128)(2,1)} pad()"


def _run(events, ops=None, executions=2):
    ops = ops if ops is not None else {
        FORWARD: (0.48, 24), DF1: (0.72, 24), DF2: (0.96, 24),
        UP8: (0.02, 2), MARKER: (1e-9, 24), PAD: (0.01, 24)}
    return {"kind": "train", "events": events, "batch": 1,
            "cell": spec.load_cell(CELL),
            "devices": [SimpleNamespace(device_kind="TPU v5 lite")],
            "trace": {"executions": executions,
                      "op_s": {k: s for k, (s, _) in ops.items()},
                      "op_count": {k: n for k, (_, n) in ops.items()},
                      "busy_s": 1.0, "window_s": 1.0}}


def _aot(**notes):
    return {"kind": "aot", "event": "hit", "program": "train_step", **notes}


def _read(run):
    return {name: spec.load_reader(name)(run) for name in sorted(NEW)}


def test_readers_tell_the_kernels_by_their_scopes_name():
    said = _aot(wcp_fused_calls=12, wcp_levels_windowed=1,
                corr_volume_bytes=697_680_000)
    values = _read(_run([said]))
    # 2.16 s over two executions; the Up8 call, the compiler's marker and
    # the scope-less pad are not the correlation's
    assert values["wcp_ms"] == pytest.approx(1080.0)
    assert values["wcp_levels_windowed"] == 1.0
    assert values["wcp_roofline"] == pytest.approx(
        100 * 1_463_316_480 / 819e9 / 1.08)
    assert 0 < values["wcp_roofline"] < 1.0
    # a kernel that changes its result type, its shapes and its order is
    # still the correlation's, and the yardstick does not move with it
    renamed = {"%wcp.3 = (bf16[1,8,99], bf16[2,2]) custom-call(%a, %b)":
               (2.16, 12)}
    assert _read(_run([said], renamed)) == values
    # on the compile event as on the aot event
    compiled = {"kind": "compile", "label": "train_step", "seconds": 1.0,
                "wcp_fused_calls": 12, "wcp_levels_windowed": 1}
    assert _read(_run([compiled])) == values
    # four windowed levels: more work against the same time
    four = _read(_run([_aot(wcp_fused_calls=12, wcp_levels_windowed=4)]))
    assert four["wcp_levels_windowed"] == 4.0
    assert four["wcp_roofline"] > 1.5 * values["wcp_roofline"]


@pytest.mark.parametrize("events, levels, why", [
    # a call fell back: what is left would pass as the correlation's
    ([_aot(wcp_fused_calls=11, wcp_fallback_calls=1,
           wcp_levels_windowed=1)], 1.0, "wcp_fallback_calls=1"),
    # every level a volume: no call at all
    ([_aot(wcp_levels_windowed=0, corr_volume_bytes=3_000_000_000)], 0.0,
     "wcp_fused_calls=0"),
    # the parent's program says nothing of it
    ([_aot()], None, "reports no windowed-correlation path"),
    ([_aot(sw_fused_calls=48, matching_levels_batched=4)], None,
     "reports no windowed-correlation path"),
    # another program's notes are not the train step's
    ([_aot(wcp_fused_calls=12, wcp_levels_windowed=1)
      | {"program": "eval_step"}], None,
     "reports no windowed-correlation path"),
    ([], None, "reports no windowed-correlation path"),
])
def test_readers_return_nothing_and_never_raise(capsys, events, levels, why):
    for run in (_run(events), _run(events) | {"trace": None},
                _run(events, ops={})):
        assert _read(run) == {"wcp_ms": None, "wcp_roofline": None,
                              "wcp_levels_windowed": levels}
    assert why in capsys.readouterr().out
    assert _read(_run(events) | {"kind": "serve"}) == dict.fromkeys(NEW)


def test_named_calls_without_a_trace_of_the_step_read_nothing():
    said = _aot(wcp_fused_calls=12, wcp_levels_windowed=1)
    values = _read(_run([said], executions=0))
    assert values["wcp_ms"] is None and values["wcp_roofline"] is None
    assert values["wcp_levels_windowed"] == 1.0


# -- the rehearsal ------------------------------------------------------------

def _rehearse(env, seed=2147483659):
    proc = subprocess.run(
        [sys.executable, "benchmark/tests/rehearse_fs.py", "--trace", "1",
         "--seed", str(seed)],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=1500)
    assert proc.returncode == 0, proc.stderr[-2000:]
    result = json.loads(proc.stdout.strip().splitlines()[-1])
    assert result["correct"] and result["device"]["platform"] == "cpu"
    metrics = {k.removeprefix("cpu_rehearsal."): v["value"]
               for k, v in result["metrics"].items()}
    events = [json.loads(ln) for ln in (
        ROOT / f"bench_out/rehearsal/toy-fs/seed{seed}_trace1"
        / "events.jsonl").read_text().splitlines()]
    return metrics, events


def test_rehearsal_is_correct_and_a_loaded_program_keeps_its_notes(tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cache"))
    env.pop("RMD_FS_VOLUME_GIB", None)
    # b2 128x128 in bf16: a 16x16 grid against maps of 16, 8, 4 and 2
    volumes = sum(2 * 2 * 256 * (16 >> l) ** 2 for l in range(4))
    for boot in ("cold", "warm"):
        metrics, events = _rehearse(env)
        # at the toy grid every level's volume fits the budget
        assert metrics["wcp_levels_windowed"] == 0.0, boot
        assert not {"wcp_ms", "wcp_roofline"} & set(metrics)
        step = [e for e in events if e["kind"] == "aot"
                and e.get("program") == "train_step"
                and e["event"] in ("hit", "save")]
        assert [e["event"] for e in step] == [
            "save" if boot == "cold" else "hit"]
        assert step[0]["wcp_levels_windowed"] == 0
        assert step[0]["corr_volume_bytes"] == volumes
        assert "wcp_fused_calls" not in step[0]
        assert "wcp_fallback_calls" not in step[0]
        compiles = [e for e in events if e["kind"] == "compile"
                    and e.get("label") == "train_step"]
        assert len(compiles) == (1 if boot == "cold" else 0)
        first = next(e for e in events if e["kind"] == "step")
        assert first["counters"]["corr_volume_bytes"] == volumes


def test_rehearsal_with_every_level_windowed_counts_its_fallbacks(tmp_path):
    # no budget: all four levels on the windowed form, which a CPU
    # computes by the XLA composition, one call an iteration
    env = dict(os.environ, JAX_PLATFORMS="cpu", RMD_FS_VOLUME_GIB="0",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cache"))
    metrics, events = _rehearse(env, seed=2147483660)
    assert metrics["wcp_levels_windowed"] == 4.0
    assert not {"wcp_ms", "wcp_roofline"} & set(metrics)
    step = next(e for e in events if e["kind"] == "compile"
                and e.get("label") == "train_step")
    assert step["wcp_fallback_calls"] == 2 and "wcp_fused_calls" not in step
    assert step["corr_volume_bytes"] == 0
