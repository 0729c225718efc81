"""The yardstick of the kernel metrics: the peaks of a chip, and the
bytes that every implementation of one stencil-sweep call has to move.

A sweep call reads each of its input fields over the domain once (the
iterate and the ``env`` fields) and writes the new iterate once; that is
the least any implementation moves per call, whatever its halo margin,
tiling or number of sweeps fused into the call.  The sweeps here do a
handful of floating-point operations per cell against at least 12 bytes,
far below a v5e's ratio of peak operations to bandwidth (about 240 per
byte), so their roofline is bound by bytes: the share is that least
number of bytes over the HBM peak, divided by the kernel's own device
time in the trace.
"""
from __future__ import annotations

import json
import os

PEAKS = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                     "peaks.json")


def peaks(device_kind: str) -> dict:
    """The peaks of one chip, by JAX's ``device_kind``; an unknown kind is
    an error, never a default."""
    with open(PEAKS) as fh:
        table = json.load(fh)["kinds"]
    if device_kind not in table:
        raise ValueError(f"no peaks for device kind {device_kind!r} in "
                         f"{PEAKS}; known: {sorted(table)}")
    return table[device_kind]


def domain_bytes(shape, *, read: int, written: int = 1,
                 itemsize: int = 4) -> int:
    """Least bytes one sweep call moves: ``read`` input fields and
    ``written`` output fields of ``shape``, once each."""
    cells = 1
    for d in shape:
        cells *= int(d)
    return (read + written) * cells * itemsize


def kernel_share(ctx, kernel: str, shape: str = "grid", lanes: int = 1):
    """Percent of the bytes roofline a named kernel reached in the traced
    window, or None where the trace holds no call of it.  A call sweeps
    ``lanes`` domains of the configuration's ``shape`` at once (the
    lane-batched kernel of a farm)."""
    from bench import trace

    if ctx.trace is None:
        return None
    calls, seconds = trace.kernel_time(ctx.trace, kernel)
    if not calls or seconds <= 0:
        return None
    fields = ctx.config["kernel_fields"]
    per_call = lanes * domain_bytes(ctx.config[shape], read=fields["read"],
                                    written=fields["written"])
    least_s = calls * per_call / ctx.peaks["hbm_bytes_per_s"]
    return 100.0 * least_s / seconds
