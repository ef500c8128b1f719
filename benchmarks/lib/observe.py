"""What a run observed, in the one shape every metric reader takes. A runner
fills it; a reader takes its metric from it and returns ``None`` where there
is nothing to read. All clock readings are ``time.perf_counter`` seconds."""
from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional


@dataclasses.dataclass
class Observed:
    # plain numbers of the run: setup_seconds, window_s, tokens, steps, chips, ...
    facts: Dict[str, float] = dataclasses.field(default_factory=dict)
    # the measured window on the host clock
    window: Optional[tuple] = None
    # the program's OpProfiler spans inside the window:
    # {"name", "start", "end", "args"}
    spans: List[dict] = dataclasses.field(default_factory=list)
    # the program's RequestTrace events inside the window:
    # {"name", "t", "attrs"}
    events: List[dict] = dataclasses.field(default_factory=list)
    # the load generator's record of every request it owed the window
    requests: List[dict] = dataclasses.field(default_factory=list)
    # the program's gauges, sampled once a second of the window
    gauges: List[Dict[str, float]] = dataclasses.field(default_factory=list)
    # the reduced device trace (benchmarks/lib/xplane.py ``reduce``), only in
    # a traced run on a device that has operation lines
    trace: Optional[dict] = None
