"""Window-rate, block-median and stall arithmetic on synthetic step records, the serve
schedule, and the characters of ``BENCHMARK.json``. CPU only, no JAX:

    python3 -m pytest benchmark/tests/test_stats.py -q
"""

import json
import re
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from benchmark.harness import schedule, stats  # noqa: E402


def _ends(step=0.37, n=80, stall_at=None, stall=1.5):
    t, out = 0.0, [0.0]
    for i in range(n):
        t += step + (stall if i == stall_at else 0.0)
        out.append(t)
    return out


def test_one_stall_lowers_the_window_rate_and_not_the_block_median():
    clean = stats.train_readings(_ends(), 10, 0, 6)
    stalled = stats.train_readings(_ends(stall_at=33), 10, 0, 6)
    assert clean["blocks"] == stalled["blocks"] == 8
    assert abs(clean["train_pairs_per_s"] - 6 / 0.37) < 1e-9
    # all the work over all the time: 80 steps in 80 * 0.37 + 1.5 s
    assert abs(stalled["train_pairs_per_s"] - 480 / (80 * 0.37 + 1.5)) < 1e-9
    assert stalled["train_pairs_per_s"] < 0.96 * clean["train_pairs_per_s"]
    # the median block does not move, and the stall is on record beside it
    assert abs(stalled["train_block_pairs_per_s"] - 6 / 0.37) < 1e-9
    assert abs(stalled["train_stall_ms"] - (1500.0 - 0.5 * 370.0)) < 1e-6
    assert clean["train_stall_ms"] == 0.0


def test_rate_is_time_between_sync_points_not_a_count_over_seconds():
    # 8 blocks of 10 steps at 0.4 s: 15 pairs/s whatever --seconds was
    r = stats.train_readings(_ends(step=0.4), 10, 0, 6)
    assert abs(r["train_pairs_per_s"] - 15.0) < 1e-9
    assert abs(r["window_s"] - 32.0) < 1e-9


def test_window_must_end_on_a_sync_point():
    try:
        stats.train_readings(_ends(n=75), 10, 0, 6)
    except ValueError:
        return
    raise AssertionError("a window that ends off a sync point was accepted")


def test_open_index_skips_the_warm_up():
    ends = [0.0, 9.0, 18.0] + [18.0 + 0.37 * (i + 1) for i in range(60)]
    r = stats.train_readings(ends, 10, 2, 6)
    assert r["blocks"] == 6 and abs(r["median_step_ms"] - 370.0) < 1e-6


def test_percentile():
    assert stats.percentile([1, 2, 3, 4, 5], 50) == 3
    assert abs(stats.percentile(list(range(101)), 95) - 95) < 1e-9


TRAFFIC = json.loads((ROOT / "benchmark/traffic/serve-mixed.json").read_text())


def test_schedule_mix_is_exact_and_due_times_do_not_depend_on_the_seed():
    a = schedule.build(TRAFFIC, 1, 30)
    b = schedule.build(TRAFFIC, 2 ** 31 + 17, 30)
    assert [d for d, _ in a] == [d for d, _ in b]
    assert [s for _, s in a] != [s for _, s in b]
    for plan in (a, b):
        assert len(plan) == 768            # (2 + 30) s at 24/s, whole groups
        for g in range(0, len(plan), 8):
            shapes = [s for _, s in plan[g:g + 8]]
            assert shapes.count(0) == shapes.count(1) == 4
    gaps = {round(y - x, 9) for (x, _), (y, _) in zip(a, a[1:])}
    assert gaps == {round(1 / 24, 9)}


NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


def test_benchmark_json_names_units_and_files():
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    cells = {w["name"] for w in bench["workloads"]}
    e2e = {m["name"] for m in bench["end_to_end"]}
    assert "setup_s" in e2e
    for c in bench["configs"]:
        assert NAME.match(c["name"]) and (ROOT / c["file"]).is_file()
        assert any(w["config"] == c["name"] for w in bench["workloads"])
    for w in bench["workloads"]:
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert len(w["why"]) <= 200 and w["chips"] in (1, 4)
        assert (ROOT / "benchmark/traffic" / f"{w['traffic']}.json").is_file()
    assert sum(w["chips"] == 4 for w in bench["workloads"]) <= max(
        1, len(cells) // 4)
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
        assert set(m.get("workloads", cells)) <= cells
    for m in bench["end_to_end"]:
        assert 0 < m["bound"] <= 0.1
    for m in bench["per_layer"]:
        assert m["moves"] in e2e
        reader = m["name"].replace(".", "_").replace("-", "_")
        assert (ROOT / "benchmark/layers" / f"{reader}.py").is_file()
