"""Milliseconds per timed round of the window census before the launch
(the mirror readback and the window plan): the program's
``ingest.window_census`` spans summed over the window, over the rounds.
The window also holds the epochs' genesis reloads, one per
``rounds_per_epoch`` rounds, but the census rejects a genesis load before
its span, so only the rounds count here.  None where the program has no
such span."""
from typing import Any, Dict, Optional

from common import hist_delta


def read(obs: Dict[str, Any]) -> Optional[float]:
    n, total = hist_delta(obs["telemetry"], "span.ingest.window_census.seconds")
    rounds = obs["units"].get("rounds", 0)
    return 1000.0 * total / rounds if n and rounds else None
