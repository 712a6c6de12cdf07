"""The run's last lines: the numbers compared, then the result object.

Every number that decides ``correct`` is printed beside its limit as the
last lines of standard error, and again under ``checks``, the last key of
the result line.  The result line is the last line of standard output.
"""
from __future__ import annotations

import json
import sys


def emit(*, attempted: int, failed: int, metrics: dict, device: dict,
         checks: dict, breakdown: dict | None = None) -> bool:
    """Print the checks and the result line; returns ``correct``.

    ``metrics`` maps a name to ``(value, unit)``; ``checks`` maps a short
    plain name to ``(value, limit)``, a check passing when the value is at
    most its limit."""
    correct = bool(checks) and all(v <= lim for v, lim in checks.values())
    line = {"correct": correct, "attempted": int(attempted),
            "failed": int(failed),
            "metrics": {k: {"value": v, "unit": u}
                        for k, (v, u) in metrics.items()},
            "device": device}
    if breakdown is not None:
        line["breakdown"] = breakdown
    line["checks"] = {k: {"value": v, "limit": lim}
                      for k, (v, lim) in checks.items()}
    sys.stdout.flush()
    for k, (v, lim) in checks.items():
        print(f"check {k}: {v} (limit {lim})", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(line), flush=True)
    return correct
