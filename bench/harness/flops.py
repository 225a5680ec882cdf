"""What every architecture's work counts share: the bytes of each
representation a configuration can state, and the roofline.

The counts themselves (operations and bytes of a model's layers,
computed from the shapes in a configuration file) belong to the
architecture, in ``bench/architectures/<name>.py``.  They are the
numerators of ``mfu`` and of the roofline shares: the work the layer
asks for, whichever kernel does it, so a later change of kernel is
measured against the same work.
"""

from __future__ import annotations

BYTES = {"bfloat16": 2, "float16": 2, "float32": 4, "float8_e4m3fn": 1,
         "float8_e5m2": 1, "int8": 1, "exp7": 1}


def least_seconds(flops: float, nbytes: float, peak_flops: float,
                  peak_bytes_s: float) -> tuple[float, str]:
    """The roofline: the larger of compute time and memory time, and
    which of the two bounds it."""
    tc, tm = flops / peak_flops, nbytes / peak_bytes_s
    return (tc, "compute") if tc >= tm else (tm, "memory")
