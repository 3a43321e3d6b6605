"""Operations and bytes of one arena-executor call, from the algorithm.

The served cascade (BlockAMC, arXiv:2401.10042, Algorithm 1) applies, per
stage, INV(A1) twice, MVM(A3) once, INV(A4s) once and MVM(A2) once.  The
arena executor runs every INV leaf as one explicit inverse tile and every
MVM as its tiles on arrays of at most `array_size`, so one call touches
each operator tile once.  The counts below walk that schedule for
(n, stages, array_size) with the paper's split (A1 takes ceil(n/2)); they
never read the program's padded shapes, so they stay the same whatever
implements the cascade.
"""
from __future__ import annotations

F32_BYTES = 4


def _tiles(rows: int, cols: int, s: int):
    """Sizes (r, c) of the tiles of a rows x cols MVM on s x s arrays."""
    out = []
    for r0 in range(0, rows, s):
        for c0 in range(0, cols, s):
            out.append((min(s, rows - r0), min(s, cols - c0)))
    return out


def cascade_tiles(n: int, stages: int, array_size: int):
    """Every operator tile one solve applies, as (rows, cols), in order."""
    if stages == 0 or n <= 1:
        return [(n, n)]
    m = -(-n // 2)
    inv1 = cascade_tiles(m, stages - 1, array_size)
    inv4s = cascade_tiles(n - m, stages - 1, array_size)
    return (inv1 + _tiles(n - m, m, array_size) + inv4s
            + _tiles(m, n - m, array_size) + inv1)


def tile_elements(n: int, stages: int, array_size: int) -> int:
    """Sum of rows x cols over the tiles of one solve (94,208 for the
    paper's two-stage 256 on 64^2 arrays)."""
    return sum(r * c for r, c in cascade_tiles(n, stages, array_size))


def executor_work(n: int, stages: int, array_size: int, instances: int,
                  rhs: int) -> tuple:
    """(flops, bytes) of one executor call over `instances` programmed
    tenants and `rhs` real right-hand sides in all (padding excluded).

    flops = 2 x tile elements x rhs: each rhs meets each of its tenant's
    tiles once.  bytes >= every tenant's tiles read once plus each rhs
    read in and its answer written out, all f32."""
    elems = tile_elements(n, stages, array_size)
    flops = 2 * elems * rhs
    nbytes = F32_BYTES * (elems * instances + 2 * n * rhs)
    return flops, nbytes


def roofline_seconds(flops: float, nbytes: float, peaks: dict) -> tuple:
    """Least time the chip could take, and which bound sets it."""
    t_flops = flops / peaks["flops_per_s"]
    t_bytes = nbytes / peaks["hbm_bytes_per_s"]
    return (t_flops, "flops") if t_flops >= t_bytes else (t_bytes, "bytes")
