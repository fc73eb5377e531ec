"""The comparison that decides ``correct``: numbers beside their limits."""

import json
import math
from pathlib import Path

REFERENCE_DIR = Path(__file__).resolve().parent.parent / "reference"


def limits_for(cell_name):
    """Limits of one cell, from ``reference/limits/<cell>.json``; each was
    set from readings on the chip that the file records beside it."""
    return json.loads(
        (REFERENCE_DIR / "limits" / f"{cell_name}.json").read_text())["limits"]


class Verdict:
    """Collects each number compared, prints it beside its limit."""

    def __init__(self):
        self.rows = []

    def hold(self, name, value, limit):
        """``value`` must be a finite number at or under ``limit``."""
        ok = (value is not None and math.isfinite(value) and value <= limit)
        self.rows.append({"name": name, "value": value, "limit": limit,
                          "ok": bool(ok)})
        return ok

    @property
    def correct(self):
        return bool(self.rows) and all(r["ok"] for r in self.rows)

    def print(self):
        for r in self.rows:
            mark = "ok" if r["ok"] else "FAILED"
            print(f"[check] {r['name']}: {r['value']!r} (limit {r['limit']!r})"
                  f" {mark}", flush=True)
