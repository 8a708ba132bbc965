"""Flight recorder: bounded ring buffer of span/metric events + JSONL
export.
"""
from __future__ import annotations

import json
import os
from collections import deque
from typing import Dict, List

SCHEMA_VERSION = 1


class FlightRecorder:
    """Keeps the most recent `capacity` events; older ones fall off the
    front (``dropped`` counts them) so a long replay can't OOM."""

    def __init__(self, capacity: int = 131072):
        self.capacity = int(capacity)
        self._buf = deque(maxlen=self.capacity)
        self.total = 0

    def record(self, event: Dict):
        self._buf.append(event)
        self.total += 1

    def record_metrics(self, snapshot: Dict, t: float):
        self.record({"kind": "metrics", "t": t, "data": snapshot})

    @property
    def dropped(self) -> int:
        return max(0, self.total - len(self._buf))

    def __len__(self):
        return len(self._buf)

    def events(self) -> List[Dict]:
        return list(self._buf)

    def span_count(self) -> int:
        return sum(1 for e in self._buf if e.get("kind") == "span")

    def clear(self):
        self._buf.clear()
        self.total = 0

    def export_jsonl(self, path: str) -> str:
        """One meta line, then one JSON object per event."""
        d = os.path.dirname(path)
        if d:
            os.makedirs(d, exist_ok=True)
        with open(path, "w") as f:
            meta = {"kind": "meta", "version": SCHEMA_VERSION,
                    "events": len(self._buf), "total": self.total,
                    "dropped": self.dropped, "clock": "perf_counter"}
            f.write(json.dumps(meta) + "\n")
            for ev in self._buf:
                f.write(json.dumps(ev) + "\n")
        return path
