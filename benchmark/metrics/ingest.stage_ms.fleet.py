"""Milliseconds per timed round of host staging before the launch (row
split, sort, mark padding, the group-to-replica gather): the program's
``ingest.stage`` spans summed over the window, over the rounds.  The
window also holds the epochs' genesis reloads, one per
``rounds_per_epoch`` rounds, and their staging counts here too.  None
where the program has no such span."""
from typing import Any, Dict, Optional

from common import hist_delta


def read(obs: Dict[str, Any]) -> Optional[float]:
    n, total = hist_delta(obs["telemetry"], "span.ingest.stage.seconds")
    rounds = obs["units"].get("rounds", 0)
    return 1000.0 * total / rounds if n and rounds else None
