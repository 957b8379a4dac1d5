"""What one run gathered, as a per-layer reader sees it."""

from __future__ import annotations

import dataclasses
from typing import Any, Optional


@dataclasses.dataclass
class RunData:
    seconds: float
    records: list                      # loadgen.Record, the client's side
    prom_start: dict                   # prom.parse(/metrics) at window start
    prom_end: dict                     # ... at window end
    prom_samples: list                 # ... polled inside the window
    endpoints: dict[str, Any]          # JSON bodies fetched at window end
    trace: Optional[Any] = None        # trace.DeviceTrace of the capture
