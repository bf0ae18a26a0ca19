"""What one run leaves for the metric readers and the check."""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, List, Optional


@dataclasses.dataclass
class Record:
    config: dict
    settings: dict
    device_kind: str
    n_chips: int
    window_s: float = 0.0
    attempted: int = 0
    failed: int = 0
    setup_s: float = 0.0
    # host-clock observations, by name
    host: Dict[str, Any] = dataclasses.field(default_factory=dict)
    # counters read from the program over the window
    counters: Dict[str, Any] = dataclasses.field(default_factory=dict)
    # compilations JAX made inside the window
    compiles: int = 0
    # the reduced device trace of a ``--trace 1`` run
    trace: Optional[Any] = None
    # what the check compares
    outputs: List[Any] = dataclasses.field(default_factory=list)
