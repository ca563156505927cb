"""Milliseconds per timed round of the batched host-to-device copy of the
op tensors before the launch: the program's ``ingest.put`` spans summed
over the window, over the rounds.  ``device_put`` may return before the
copy lands, so this can be the enqueue alone.  The window also holds the
epochs' genesis reloads, one per ``rounds_per_epoch`` rounds, and their
put counts here too.  None where the program has no such span."""
from typing import Any, Dict, Optional

from common import hist_delta


def read(obs: Dict[str, Any]) -> Optional[float]:
    n, total = hist_delta(obs["telemetry"], "span.ingest.put.seconds")
    rounds = obs["units"].get("rounds", 0)
    return 1000.0 * total / rounds if n and rounds else None
