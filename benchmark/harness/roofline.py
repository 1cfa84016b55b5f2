"""A kernel's share of its roofline.  Standard library only.

The least time the chip could take for a call is the larger of its
operations over the peak operation rate and its bytes over the peak memory
rate (``peaks.json``, by device kind); the share is that over the time the
trace gives the kernel.  The operations and bytes a call needs are counted
from shapes by a function in the reader's own file, never read from the
program.  A share over 1 means the count is too high or the time leaves
out part of the work: it is reported as it is, never clipped.
"""

from __future__ import annotations


def share(seconds: float, flops: float, nbytes: float, peaks: dict):
    """The least time over ``seconds``, as a fraction; None where there is
    no time or no work to speak of."""
    least = max(flops / peaks["bf16_flops_per_s"],
                nbytes / peaks["hbm_bytes_per_s"])
    if seconds <= 0 or least <= 0:
        return None
    return least / seconds
