"""A process that serves volumes holds glibc's malloc thresholds fixed
(`util/malloc.pin_thresholds`, called by the `volume` and `server` commands):
blocks up to 32 MiB then come from the heaps, so a read's buffers of any size
are not mapped and unmapped afresh, and larger ones are mapped as before.
Each case runs in a fresh interpreter, whose thresholds are glibc's own."""

from __future__ import annotations

import os
import subprocess
import sys

import pytest

from seaweedfs_tpu.util import malloc

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# bytes in mapped blocks after one 10 MiB and one 64 MiB block were allocated
# (the first thing in a fresh interpreter that is over glibc's first 128 KiB
# threshold), and whether pinning was taken
PROBE = """
import ctypes, sys
from seaweedfs_tpu.util import malloc

class Info(ctypes.Structure):
    _fields_ = [(n, ctypes.c_size_t) for n in (
        "arena", "ordblks", "smblks", "hblks", "hblkhd", "usmblks",
        "fsmblks", "uordblks", "fordblks", "keepcost")]

libc = ctypes.CDLL(None)
libc.mallinfo2.restype = Info
libc.malloc.restype = ctypes.c_void_p
libc.malloc.argtypes = (ctypes.c_size_t,)
took = malloc.pin_thresholds() if sys.argv[1] == "pin" else None
base = libc.mallinfo2().hblkhd
mid = libc.malloc(10 << 20)
mapped_mid = libc.mallinfo2().hblkhd - base
big = libc.malloc(64 << 20)
mapped_big = libc.mallinfo2().hblkhd - base - mapped_mid
print(took, mapped_mid >= 10 << 20, mapped_big >= 64 << 20)
"""


def probe(mode: str) -> str:
    if not hasattr(__import__("ctypes").CDLL(None), "mallinfo2"):
        pytest.skip("not glibc 2.33 or later: no mallinfo2 to read")
    out = subprocess.run([sys.executable, "-c", PROBE, mode], cwd=REPO,
                         capture_output=True, text=True, timeout=60, check=True)
    return out.stdout.split()


@pytest.mark.parametrize("mode,want", [
    ("pin", ["True", "False", "True"]),      # 10 MiB from a heap, 64 MiB mapped
    ("default", ["None", "True", "True"]),   # glibc's first threshold maps both
], ids=["pinned", "glibc-default"])
def test_pinned_thresholds_keep_blocks_up_to_32_mib_off_mmap(mode, want):
    assert probe(mode) == want


def test_the_thresholds_are_the_largest_mmap_one_and_a_trim_one_above_it():
    assert malloc.MMAP_THRESHOLD == 32 << 20
    assert malloc.TRIM_THRESHOLD > 2 * malloc.MMAP_THRESHOLD
    assert malloc.TRIM_THRESHOLD < 2 ** 31  # mallopt takes an int
