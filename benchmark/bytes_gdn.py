"""Bytes one ``cgx_gdn_update`` call has to move, from the configuration.

Kept with the benchmark, like ``bytes_ssm.py``, so that no PR that claims a
gain can change the count. One call updates one gated delta-rule layer's
recurrent state for every lane of the batch: the state ``(lanes, key
dimension, heads x value dimension)`` is read once and written once, in the
type the configuration's ``precision`` states for it, and beside it the
call's small float32 operands as the mathematics has them, not as a layout
spreads or pads them: ``q`` and ``k`` (a key vector a head each), ``v`` in
and ``o`` out (a value vector a head each), ``alpha`` and ``beta`` (one
number a head each).
"""

from __future__ import annotations

ITEM_BYTES = {"float32": 4, "bfloat16": 2, "float16": 2}


def call_bytes(cfg: dict) -> int:
    lanes, heads = cfg["serve"]["max_batch"], cfg["linear_num_value_heads"]
    dk, dv = cfg["linear_key_head_dim"], cfg["linear_value_head_dim"]
    state = lanes * heads * dk * dv
    item = ITEM_BYTES[cfg["precision"]["gdn_state"]]
    small = lanes * heads * (2 * dk + 2 * dv + 2) * 4
    return 2 * state * item + small


def step_bytes(cfg: dict) -> int:
    """All the calls of one decode step: one a delta-rule layer."""
    return cfg["layer_types"].count("linear_attention") * call_bytes(cfg)
