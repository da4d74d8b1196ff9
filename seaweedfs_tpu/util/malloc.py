"""glibc malloc's two thresholds, held fixed for a server process.

By default glibc moves its mmap threshold up to the largest block freed so
far and gives a heap's top back to the system once more than twice that is
free there. A volume server's degraded reads allocate blocks of every size
from a few KiB to 10 MiB on sixteen handler threads (the read intervals, the
kernel's zero-tailed input, the joined body), and which arena each thread
lands in differs from one process to the next, so how often pages went back
to the system and were faulted in again did too: on a TPU v5e host the same
reads took 22 to 30 ms of the server's CPU each, from one process to the
next (PERF.md, "Reads of mixed sizes"). With both thresholds fixed, blocks
up to 32 MiB come from the heaps and a heap keeps what it has grown to;
larger blocks (the EC pipeline's batches) are mapped as before.
"""

from __future__ import annotations

import ctypes

M_TRIM_THRESHOLD = -1
M_MMAP_THRESHOLD = -3
# the largest mmap threshold glibc takes on 64-bit (half its heap size)
MMAP_THRESHOLD = 32 * 1024 * 1024
TRIM_THRESHOLD = 1024 * 1024 * 1024


def pin_thresholds() -> bool:
    """Fix glibc's mmap and trim thresholds for this process (either setting
    turns its moving thresholds off); whether the C library took both. A
    library without `mallopt` (not glibc) is left as it is."""
    try:
        mallopt = ctypes.CDLL(None).mallopt
    except (OSError, AttributeError):
        return False
    mallopt.argtypes = (ctypes.c_int, ctypes.c_int)
    mallopt.restype = ctypes.c_int
    return bool(mallopt(M_MMAP_THRESHOLD, MMAP_THRESHOLD)) and bool(
        mallopt(M_TRIM_THRESHOLD, TRIM_THRESHOLD))
