"""The lines a run prints: earlier lines as JSON notes on standard output,
the numbers compared as the last lines of standard error, and the
contract's one result line last on standard output."""
from __future__ import annotations

import json
import sys


def note(kind: str, **fields) -> None:
    print(json.dumps({"note": kind, **fields}, default=str), flush=True)


def device_record(**extra) -> dict:
    import jax

    dev = jax.devices()[0]
    return {"platform": dev.platform, "kind": dev.device_kind,
            "count": len(jax.devices()), **extra}


def last_lines(correct: bool, attempted: int, failed: int, metrics: dict,
               device: dict, compared: dict, breakdown=None) -> None:
    for name, c in compared.items():
        print(f"compared {name} = {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr)
    sys.stderr.flush()
    line = {"correct": bool(correct), "attempted": int(attempted),
            "failed": int(failed), "metrics": metrics, "device": device}
    if breakdown:
        line["breakdown"] = breakdown
    line["compared"] = compared
    print(json.dumps(line), flush=True)
