"""Shared block-geometry helpers for the Pallas kernels (DESIGN.md §15).

Every kernel here tiles a long reduction axis into VMEM-resident blocks
and lane-pads the short candidate axis; until PR 10 the padding helpers
and the ``BLOCK_V = 2048`` constant were copy-pasted across
``multi_count``/``multi_mass``/``multi_entropy``.  This module is the one
home for that geometry: padding, min-tile clamping, and the VMEM-fit
check the tuner's analytic tier uses to discard infeasible blocks.

The kernels take their block size as a *parameter* (static under jit)
defaulting to the legacy constants; `kernels/ops.py` routes callers
through the tuner's ``KernelKey -> KernelDecision`` tier so tuned blocks
arrive with no signature change.
"""
from __future__ import annotations

LANE = 128          # TPU lane width: last-dim tiles are multiples of this
DEFAULT_BLOCK_V = 2048   # legacy vocab tile (f32: 8 KiB — deep in VMEM)
VMEM_BYTES = 16 * 1024 * 1024   # Mosaic's default scoped-VMEM limit per
# kernel on TPU v5e (of 128 MiB physical; the "tpu" profile's vmem_bytes);
# fit checks budget a fraction of it so double-buffering has headroom


def pad_to(n: int, mult: int) -> int:
    """Smallest multiple of ``mult`` >= ``n`` (n >= 0, mult >= 1)."""
    return -(-int(n) // int(mult)) * int(mult)


def lane_pad(n: int) -> int:
    """Pad a candidate-axis length to the TPU lane width."""
    return pad_to(max(int(n), 1), LANE)


def clamp_block_v(block: int | None, v: int, *, lane: int = LANE) -> int:
    """Legalise a requested vocab block for a length-``v`` axis.

    Rounds up to a lane multiple (the min tile), and caps at the
    lane-padded axis length — a block larger than the axis degenerates to
    one whole-row tile, never an over-wide BlockSpec.  ``None`` falls
    back to :data:`DEFAULT_BLOCK_V`.
    """
    if block is None:
        block = DEFAULT_BLOCK_V
    b = pad_to(max(int(block), 1), lane)
    return min(b, pad_to(max(int(v), 1), lane))


def grid_v(v: int, block: int) -> tuple[int, int]:
    """(padded axis length, grid steps) for a legalised block."""
    v_pad = pad_to(max(int(v), 1), block)
    return v_pad, v_pad // block


def solver_tile_bytes(block_v: int, m: int, *, itemsize: int = 4,
                      acc_rows: int = 1) -> int:
    """Working-set estimate for one solver-kernel grid step.

    One streamed (1, block_v) operand tile, the resident lane-padded
    candidate row, the revisited (1, acc_rows, m_pad) accumulator, and
    the broadcast (1, m_pad, block_v) compare intermediate — the term
    that actually bounds the block on real hardware.
    """
    m_pad = lane_pad(m)
    return itemsize * (block_v + m_pad * (1 + acc_rows) + m_pad * block_v)


def fits_vmem(tile_bytes: int, *, budget: int | None = None,
              fraction: float = 0.5) -> bool:
    """True if a grid step's working set fits the VMEM budget fraction."""
    cap = (VMEM_BYTES if budget is None else budget) * fraction
    return tile_bytes <= cap


def divisor_chunk(n: int, target: int) -> int:
    """Largest divisor of ``n`` that is <= ``target`` (>= 1).

    Used to legalise flash-attention chunk defaults: the kernel requires
    the sequence to divide by its chunks, so a 512-row default must fold
    to 256 on a 256-row sequence (and to whatever odd length a test
    shape carries).
    """
    n, target = int(n), max(1, int(target))
    if n <= target:
        return max(1, n)
    for d in range(target, 0, -1):
        if n % d == 0:
            return d
    return 1
