"""The C allocator's retention, set once at the start of a server.

A job's scan decodes, un-permutes and concatenates every column of the
table: about three times the table's bytes, allocated and freed inside
one job by one of the controller's worker threads. glibc gives a thread
an arena of 64 MiB heaps and unmaps a heap once it is free, so the next
job page-faults the same memory in again; whether a heap is free
depends on what else the thread left in it, so of two workers one keeps
its heaps and the other does not, and every other job of a closed loop
is slower by what the faults cost (the ARIMA job over 864,000 rows:
`read` 167-172 ms on one worker, 190-197 on the other, 120-127 on both
when the heaps stay; PERF.md section 6, PR 37).

`retain_freed_memory` tells the allocator to keep what was freed: a
long-running server asks for the same memory again with the next job.
The cost is that the process's resident memory stays at each arena's
high-water mark.
"""

from __future__ import annotations

import ctypes

# <malloc.h>
_M_TRIM_THRESHOLD = -1
_M_TOP_PAD = -2
_M_MMAP_THRESHOLD = -3

#: the largest `M_MMAP_THRESHOLD` glibc takes (half a 64 MiB heap): a
#: scan's column (8 B a row) comes from the arena, not from a mapping
#: of its own that is unmapped when freed
MMAP_THRESHOLD = 32 << 20
#: free memory at the top of the main heap is returned beyond this
TRIM_THRESHOLD = 1 << 30
#: twice a thread arena's heap (64 MiB): glibc unmaps a free heap only
#: if more than the pad stays free below it, which two heaps' worth
#: never is, so a thread's heaps stay mapped
TOP_PAD = 128 << 20


def retain_freed_memory() -> bool:
    """Set the three thresholds; False where the C library has no
    `mallopt` (not glibc) or refuses a value. Setting any of them also
    ends glibc's own adjustment of the first two."""
    try:
        mallopt = ctypes.CDLL(None).mallopt
    except (OSError, AttributeError):
        return False
    mallopt.argtypes = [ctypes.c_int, ctypes.c_int]
    mallopt.restype = ctypes.c_int
    return all([mallopt(_M_MMAP_THRESHOLD, MMAP_THRESHOLD) == 1,
                mallopt(_M_TRIM_THRESHOLD, TRIM_THRESHOLD) == 1,
                mallopt(_M_TOP_PAD, TOP_PAD) == 1])
