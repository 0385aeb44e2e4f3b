"""Operations and bytes that a correct implementation cannot skip, from
shapes alone.  Every roofline and ``mfu`` share divides the least time
these give (at the chip's peaks, ``peaks.json``) by a measured time, so each
function counts only required work: a share above 100% means a count here
is too high or the measured time leaves out part of the work.
"""
from __future__ import annotations

from typing import Dict, Tuple


def min_time_s(flops: float, nbytes: float, peaks: Dict[str, float]
               ) -> Tuple[float, str]:
    """The least time for ``flops`` and ``nbytes`` on one chip, and which
    bound sets it (``"flops"`` or ``"bytes"``)."""
    tf = flops / peaks["flops_per_s"]
    tb = nbytes / peaks["bytes_per_s"]
    return (tf, "flops") if tf >= tb else (tb, "bytes")


# ------------------------------------------------------------- aggregation

def stream_stats_work(P: int, n: int, itemsize: int) -> Tuple[float, float]:
    """``G = D Dᵀ`` and ``C = D GMᵀ`` over (P, n) inputs: both inputs read
    once; 2·P²·n FLOPs per product."""
    return 4.0 * P * P * n, 2.0 * P * n * itemsize


def round_work(P: int, n: int, itemsize: int, param_itemsize: int = 4
               ) -> Tuple[float, float]:
    """One contextual round: the statistics pass reads D and GM once, the
    combine reads D once more (the statistics must be complete before the
    weights exist), and the parameters are read and written once.  FLOPs:
    the two (P, P) products.  The P×P solves are negligible and left out."""
    flops, nbytes = stream_stats_work(P, n, itemsize)
    nbytes += P * n * itemsize + 2.0 * n * param_itemsize
    return flops, nbytes

