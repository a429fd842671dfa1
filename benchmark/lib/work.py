"""Operations and bytes a kernel is ASKED for, from shapes alone:
whatever kernel does the work, the work is the same."""
from __future__ import annotations


def gf_apply_bytes(k: int, r: int, n: int) -> int:
    """An [r, k] GF(2^8) matrix over [k, n] bytes: k*n bytes read, r*n
    written, each once.  The matrix itself (r*k bytes) is left out."""
    if min(k, r, n) <= 0:
        raise ValueError(f"gf_apply_bytes({k}, {r}, {n}): sizes must be > 0")
    return (k + r) * n


BYTES_FUNCTIONS = {"gf_apply_bytes": gf_apply_bytes}
