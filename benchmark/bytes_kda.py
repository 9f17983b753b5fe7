"""Bytes one ``cgx_kda_update`` call has to move, from the configuration.

Kept with the benchmark, like ``bytes_gdn.py``, so that no PR that claims a
gain can change the count. One call updates one KDA layer's recurrent state
for every lane of the batch: the state ``(lanes, key dimension, heads x value
dimension)`` is read once and written once, in the type the configuration's
``precision`` states for it, and beside it the call's small float32 operands
as the mathematics has them, not as a layout spreads or pads them: ``q``,
``k`` and ``alpha`` (a key vector a head each: the decay is a number a key
channel), ``v`` in and ``o`` out (a value vector a head each), ``beta`` (one
number a head). Keys and values are both ``head_dim`` wide.
"""

from __future__ import annotations

ITEM_BYTES = {"float32": 4, "bfloat16": 2, "float16": 2}


def kda_layers(cfg: dict) -> int:
    """The KDA layers of the tree: of the published layers it keeps, those
    that are not the last of a group of ``layer_group_size``."""
    kept = cfg.get("layers_kept", range(cfg["num_hidden_layers"]))
    return sum(1 for i in kept if (i + 1) % cfg["layer_group_size"])


def call_bytes(cfg: dict) -> int:
    lanes, heads = cfg["serve"]["max_batch"], cfg["num_attention_heads"]
    d = cfg["head_dim"]
    state = lanes * heads * d * d
    item = ITEM_BYTES[cfg["precision"]["kda_state"]]
    small = lanes * heads * (3 * d + 2 * d + 1) * 4
    return 2 * state * item + small


def step_bytes(cfg: dict) -> int:
    """All the calls of one decode step: one a KDA layer."""
    return kda_layers(cfg) * call_bytes(cfg)
