"""A kernel's share of its roofline, for the per-layer readers.

The least time the chip could take for a kernel's work is the larger of
its operations over the peak rate and its bytes over the HBM bandwidth
(``bench/peaks.json``); the share is that time over the device time the
trace measured.  The operations and bytes are the algorithm's, counted
from the configuration's shapes by a function kept in that
configuration's module (``bench/configs/<config>.py``), never read from
the program.  A reader reports ``100 * share(...)`` as
``<kernel>_roofline`` in ``%``.
"""
from __future__ import annotations

#: A share above this means the operations or bytes are counted too high,
#: or the time leaves out part of the work.
MAX_SHARE = 1.05


def share(flops: float, bytes: float, seconds: float, peaks: dict,
          flop_key: str) -> float:
    """``max(flops / peaks[flop_key], bytes / peaks["hbm_bytes_per_s"])
    / seconds``; raises ValueError for a share above :data:`MAX_SHARE`
    or a time that is not positive."""
    if not seconds > 0:
        raise ValueError(f"device time must be positive, got {seconds!r}")
    least = max(flops / peaks[flop_key], bytes / peaks["hbm_bytes_per_s"])
    s = least / seconds
    if s > MAX_SHARE:
        raise ValueError(
            f"roofline share {s!r} > {MAX_SHARE}: {flops!r} operations and "
            f"{bytes!r} bytes cannot run in {seconds!r} s at "
            f"{peaks[flop_key]!r} op/s and {peaks['hbm_bytes_per_s']!r} B/s")
    return s
