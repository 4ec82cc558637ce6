"""Operations and bytes the TM's primitives and steps need, from shapes.

Counts are of the work the algorithm needs, not of what an implementation
happens to do: an operand is read once, padding is not counted, and a
multiply-accumulate counts as two operations (as the chip's peaks do). So
the least time they give is a true lower bound, and a share of a roofline
or of a peak computed from them cannot pass 100% unless a count is wrong.

Shapes: ``m`` classes, ``n`` clauses per class, ``L = 2o`` literals.
"""
from __future__ import annotations

import math

WORD = 32


def least_time_s(ops: float, nbytes: float, peak_ops: float,
                 peak_bytes_per_s: float) -> float:
    """The roofline's least time: the larger of the two bounds."""
    return max(ops / peak_ops, nbytes / peak_bytes_per_s)


def indexed_votes(m: int, n: int, L: int, rows: int) -> tuple[int, int]:
    """Matmul-form Eq. 4 over a ``rows``-row batch: each row's false
    literals meet the (m·n, L) membership mask; reads the int32 position
    matrix once, the rows' float32 false-literal indicators, writes int32
    votes."""
    ops = 2 * rows * m * n * L
    nbytes = 4 * m * n * L + 4 * rows * L + 4 * rows * m
    return ops, nbytes


def ta_update(n: int, L: int) -> tuple[int, int]:
    """One class round of feedback: reads and writes the (n, L) int16 TA
    row and reads its float32 uniforms; the literals and per-clause codes
    are read once. Elementwise: bound by bytes (no MXU work counted)."""
    return 0, n * L * (2 + 2 + 4) + 4 * L + 4 * n


def clause_outputs(n: int, L: int) -> tuple[int, int]:
    """One class row's clause outputs for one sample over packed words:
    reads the (n, ceil(L/32)) uint32 include words and the packed literals,
    writes one byte per clause. Bound by bytes."""
    words = math.ceil(L / WORD)
    return 0, 4 * n * words + 4 * words + n


def serve_ops(rows: int, m: int, n: int, L: int) -> int:
    """Dense exhaustive scoring work of ``rows`` rows: every literal test
    of every clause, as multiply-accumulates."""
    return 2 * rows * m * n * L


def train_ops_per_sample(n: int, L: int) -> int:
    """Dense work of one sequential learning sample: two class rounds, each
    evaluating (n, L) literal tests and updating (n, L) TAs."""
    return 2 * (2 * n * L)
