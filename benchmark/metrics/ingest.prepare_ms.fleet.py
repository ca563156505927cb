"""Milliseconds per timed round of host gate, encode and host-store dry
run before the launch: the program's ``ingest.prepare`` spans summed over
the window, over the rounds.  The window also holds the epochs' genesis
reloads, one per ``rounds_per_epoch`` rounds, and their prepare counts
here too.  None where the program has no such span."""
from typing import Any, Dict, Optional

from common import hist_delta


def read(obs: Dict[str, Any]) -> Optional[float]:
    n, total = hist_delta(obs["telemetry"], "span.ingest.prepare.seconds")
    rounds = obs["units"].get("rounds", 0)
    return 1000.0 * total / rounds if n and rounds else None
