"""Bytes the FRR kernel's blocked distance rows must move
(``holo_tpu/frr/kernel.py rows_block``), from the engine's counters.

One round of a block relaxes every slot of the compact rows graph
(``RowsGraph``: the low plane of every vertex and the overflow plane of
the heavy ones, ``slots`` in all, the same for both directions) for
each row of the block: it reads the slot's source and cost once (two
int32, 8 bytes) and the source's distance once per row (one int32).
So a block of ``r`` valid rows that took ``k`` rounds reads at least
``slots x k x (8 + 4 r)`` bytes, and summed over blocks

    bytes = slots x (8 x rounds + 4 x row_rounds)

with ``rounds`` = ``holo_frr_rounds_total`` (rounds summed over
blocks) and ``row_rounds`` = ``holo_frr_row_rounds_total`` (valid rows
times rounds summed over blocks).  Left out, so the count is a floor:
pad rows of a block, the writes of the distance plane, the scatter-min
of the overflow rows, and all of the post-convergence SPFs and the
selection (their round counts are not returned).  Divided by the whole
FRR program's device time, the share it gives is a floor of the
program's and cannot pass 100% for work the program really does.
"""

from __future__ import annotations

#: bytes of a slot's source and cost, read once a round
SLOT_BYTES = 8
#: bytes of one row's distance at a slot's source
ROW_BYTES = 4


def rows_bytes(slots: float, rounds: float, row_rounds: float) -> float:
    return slots * (SLOT_BYTES * rounds + ROW_BYTES * row_rounds)
