"""Keep the C allocator from giving staging buffers back to the OS.

Every message that crosses a process boundary is staged through host
memory: the sender's ``np.asarray`` of a device array, the receiver's
reassembly buffer, the copy ``device_put`` makes. glibc serves a block of
128 KiB or more by ``mmap`` and unmaps it on ``free``, and trims the top
of the heap the same way, so each of these buffers is built from pages the
kernel has to fault in and zero anew, message after message; its
thresholds also move with the sizes a process happens to free, so the same
call runs at one of several speeds. On the chip a window of 64 messages of
4 MiB cost 430-540 ms with the allocator as it comes and 270-300 ms with
the freed memory kept (PERF.md section 6, PR 28).

Open MPI's ``opal/mca/memory/linux`` does the same for its registered
buffers (``mallopt(M_TRIM_THRESHOLD, -1)``, ``mallopt(M_MMAP_MAX, 0)``).
Here the settings are bounded: blocks up to 32 MiB (the largest threshold
glibc takes) come from the heap, the heap grows 64 MiB at a time, and up to
1 GiB of freed heap stays with the process. There is no switch in the
library: a process whose environment already tunes the allocator
(``MALLOC_*_`` variables, or a ``GLIBC_TUNABLES`` that names malloc) is left
alone, and so is a C library without ``mallopt``.
"""

from __future__ import annotations

import ctypes
import os
from typing import Dict

# <malloc.h>
M_TRIM_THRESHOLD, M_TOP_PAD, M_MMAP_THRESHOLD = -1, -2, -3
MMAP_THRESHOLD_MAX = 32 << 20  # glibc's DEFAULT_MMAP_THRESHOLD_MAX, 64-bit
TOP_PAD = 64 << 20
RETAIN = 1 << 30  # freed heap kept: four windows of 64 x 4 MiB

_applied: Dict[str, int] = {}


def tuned_by_environment() -> bool:
    """Whether the process's environment already tunes glibc's malloc."""
    return ("glibc.malloc" in os.environ.get("GLIBC_TUNABLES", "")
            or any(k.startswith("MALLOC_") and k.endswith("_")
                   for k in os.environ))


def ensure() -> Dict[str, int]:
    """Apply the settings once per process; returns what is in effect
    through this module ({} where nothing was changed). Called where the
    wire forms (``Runtime``'s unified world), never for a one-process
    job."""
    if _applied:
        return dict(_applied)
    if tuned_by_environment():
        return {}
    try:
        mallopt = ctypes.CDLL(None).mallopt
    except (OSError, AttributeError):
        return {}  # not glibc
    mallopt.argtypes = [ctypes.c_int, ctypes.c_int]
    mallopt.restype = ctypes.c_int
    wanted = {"M_MMAP_THRESHOLD": (M_MMAP_THRESHOLD, MMAP_THRESHOLD_MAX),
              "M_TOP_PAD": (M_TOP_PAD, TOP_PAD),
              "M_TRIM_THRESHOLD": (M_TRIM_THRESHOLD, RETAIN)}
    for name, (param, value) in wanted.items():
        if mallopt(param, value) == 1:
            _applied[name] = value
    return dict(_applied)
