"""Chrome trace-event JSON writer (perfetto / chrome://tracing viewable).

One track, pid 1 "protocol (simulated ticks)": instant events for every
scalar pubsub send/delivery/drop, one tid per agent, with the simulated
tick counter as the timebase (1 tick = 1000 trace-us, so a round spans 4ms
on the timeline and delayed deliveries visibly land in later rounds). The
engines' host phases are not here: they are spans in the profiler's own
trace (``telemetry/timing.py``), on the clock the device shares.

The output is the standard ``{"traceEvents": [...]}`` JSON object; open
it at https://ui.perfetto.dev or chrome://tracing.
"""
from __future__ import annotations

import json
from typing import Dict, List, Optional

PID_PROTOCOL = 1

# one simulated tick = this many trace-timeline microseconds
TICK_US = 1000


class TraceWriter:
    def __init__(self) -> None:
        self.events: List[dict] = []

    # -- protocol track (simulated time) -----------------------------------
    def instant(
        self,
        name: str,
        tick: int,
        tid: int,
        args: Optional[Dict[str, object]] = None,
    ) -> None:
        ev = {
            "name": name,
            "ph": "i",
            "s": "t",
            "ts": tick * TICK_US,
            "pid": PID_PROTOCOL,
            "tid": int(tid),
        }
        if args:
            ev["args"] = args
        self.events.append(ev)

    # -- output --------------------------------------------------------------
    def to_dict(self) -> dict:
        meta = [
            {
                "name": "process_name",
                "ph": "M",
                "pid": PID_PROTOCOL,
                "args": {"name": "protocol (simulated ticks)"},
            },
        ]
        return {"traceEvents": meta + self.events, "displayTimeUnit": "ms"}

    def write(self, path: str) -> None:
        with open(path, "w") as f:
            json.dump(self.to_dict(), f)
