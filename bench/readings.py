"""What a per-layer metric reader gets: ``read(ctx)`` with ``ctx`` a
:class:`Readings` of the traced window of one run."""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, List, Optional

# The engine's own jitted programs, by the module names the trace gives
# them; every other program a tick runs is prefill work (the model's
# ``jit_prefill`` and ``jit_prefill_extend``, one program per chunk shape,
# and the first token's sampling).
TICK = "jit_tick"
ENGINE_PROGRAMS = ("jit_tick", "jit_write_slot_paged", "jit__lambda",
                   "jit_reset_slot")


@dataclasses.dataclass
class Readings:
    dims: Dict[str, Any]  # the architecture module's dims_of(config["model"])
    num_slots: int
    kv_itemsize: int  # bytes per stored K or V element
    act_itemsize: int  # bytes per element of the compute dtype
    peaks: Dict[str, float]  # peaks.peaks(device_kind)
    steps: List[Any]  # drive.Step of every tick in the traced window
    counters: Dict[str, float]  # program counters, deltas over the window
    trace: Optional[Any] = None  # trace_reduce.Trace, cut to the window

    def tick_modules(self):
        return [e for e in self.trace.modules if e[0].startswith(TICK)]

    def prefill_modules(self):
        return [e for e in self.trace.modules
                if not e[0].startswith(ENGINE_PROGRAMS)]
