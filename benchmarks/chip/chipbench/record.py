"""What one run leaves for the metric readers (``metrics/<name>.py``)."""
from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Dict, List, Optional

from chipbench.client import StepRec, Window
from chipbench.counts import Work
from chipbench.peaks import Peaks
from chipbench.system import Call
from chipbench.xtrace import Reduction


@dataclass
class Run:
    window: Window                # steps, requests, the engine's report
    calls: List[Call]             # every jitted prefill / decode call
    dims: Any                     # the configuration's layout's Dims
    config: Dict[str, Any]        # the configuration file
    mix: Dict[str, Any]           # the traffic mix
    peaks: Optional[Peaks]
    chips: int
    setup_s: float
    trace: Optional[Reduction] = None   # the traced part (--trace 1)

    def busy_steps(self) -> List[StepRec]:
        """Steps of the window that ran work, outside the traced part."""
        return [s for s in self.window.steps
                if s.program > 0 and not s.traced]

    def traced_calls(self, phase: Optional[str] = None) -> List[Call]:
        return [c for c in self.calls if c.traced
                and (phase is None or c.phase == phase)]

    def least_seconds_per_chip(self, work: Work) -> float:
        """The roofline's least time for ``work`` spread evenly over the
        cell's chips (an even spread is the least a chip can be given)."""
        per_chip = Work(work.flops / self.chips, work.bytes / self.chips)
        return per_chip.least_seconds(self.peaks.bf16_flops,
                                      self.peaks.hbm_bytes_per_s)[0]
