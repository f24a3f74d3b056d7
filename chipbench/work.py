"""The work an algorithm needs, counted from its shapes alone, so that two
implementations of one step are judged against the same count."""
from __future__ import annotations

I32 = 4


def read_phase_bytes(T: int, O: int, V: int) -> int:
    """HBM bytes one wave's read phase needs, whether one fused kernel or
    separate ones do it: for each of the ``T*O`` ops, the version ring of
    its key (creator TID, CID, SID and value, ``V`` slots each) and the
    op's key and kind; out, per op, the selected slot and its value, TID,
    CID and SID, per transaction the rule-3 seed ``s_lo``, and the ``T x
    T`` int8 anti-dependency matrix."""
    rings = T * O * V * 4 * I32
    ops_in = T * O * 2 * I32
    per_op_out = T * O * 5 * I32
    return rings + ops_in + per_op_out + T * I32 + T * T
