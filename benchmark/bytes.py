"""Bytes a kernel call has to move, computed from its shapes.

Kept with the benchmark so that no PR that claims a gain can change the
count. A PR that changes a call's shapes (which pages the decode step reads,
the output type) needs a ``benchmark`` issue to recount.
"""

from __future__ import annotations


def kv_dequant_call_bytes(max_batch: int, max_seq: int, d_model: int,
                          bits: int, bucket: int) -> dict:
    """One ``cgx_dequantize_flat`` call of the decode step as it is today:
    it decodes the whole static page table of every lane (``max_batch *
    max_seq`` token rows of ``d_model`` values, K or V of one layer) from
    ``bits``-bit words plus two float32 per bucket, and writes float32."""
    values = max_batch * max_seq * d_model
    packed = values * bits // 8
    meta = (values // bucket) * 2 * 4
    out = values * 4
    return {"in": packed + meta, "out": out, "total": packed + meta + out}
