"""Pin glibc's allocator to one regime for the length of a run.

The served put path allocates and frees buffers of 0.5 to 6 MiB on every
op.  glibc serves such sizes by ``mmap`` until a freed chunk raises its
*dynamic* mmap threshold, and trims the heap's top by a threshold that
moves with it; where the two settle depends on the order in which the
first large chunks happen to be freed, so a process lands, for its whole
life, in one of several regimes.  Measured on the write cell (my chip
runs, PR 24; 12 s windows, one seed, six runs each): default 43.7 MiB/s
on one machine, 48.4 on another, mixed on a third; thresholds fixed high
48.4 on all six; everything over 128 KiB mmapped 32.1.  Ten per cent of
a put, decided by chance, is more than any bound could carry.

So every process of the benchmark fixes both thresholds before it
allocates anything large, which switches the dynamic adjustment off
(``mallopt(3)``): allocations up to 32 MiB come from the heap and are
reused, the heap's top is not given back under 512 MiB.  This is a
setting of the process that hosts the system, as a deployment's choice
of allocator is, not an option of the program; PERF.md lists the
program's own cure (reuse the buffers) for a later PR.
"""
from __future__ import annotations

import ctypes

M_TRIM_THRESHOLD = -1
M_MMAP_THRESHOLD = -3
MMAP_THRESHOLD = 32 << 20          # glibc's own upper limit for it
TRIM_THRESHOLD = 512 << 20


def pin() -> bool:
    """True if both thresholds were set (glibc); False elsewhere."""
    try:
        mallopt = ctypes.CDLL(None).mallopt
    except (OSError, AttributeError):
        return False
    mallopt.argtypes = [ctypes.c_int, ctypes.c_int]
    mallopt.restype = ctypes.c_int
    return bool(mallopt(M_MMAP_THRESHOLD, MMAP_THRESHOLD)) and \
        bool(mallopt(M_TRIM_THRESHOLD, TRIM_THRESHOLD))
