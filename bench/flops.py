"""The roofline: the least time the chip needs for counted work.

The work itself is counted per architecture, in ``bench/arch/<arch>.py``
(``decode_step_flops``, ``prefill_flops`` and ``KERNEL_COSTS``), from shapes
and live lengths.  Those counts are what the algorithm needs, not what an
implementation happens to do: padding, idle batch rows, dead pages and
masked-out score blocks are not counted.  So a kernel's roofline share and a
step's MFU change only when the time changes, never when the grid, the page
table or the bucket does.
"""

from __future__ import annotations

from typing import Tuple

BF16_BYTES = 2


def roofline_seconds(flops: float, nbytes: float, peak_flops: float,
                     peak_bw: float) -> Tuple[float, str]:
    """The least time the chip needs for the work, and which bound sets it."""
    t_c, t_m = flops / peak_flops, nbytes / peak_bw
    return (t_c, "compute") if t_c >= t_m else (t_m, "memory")
