"""Bytes one ``cgx_ssm_update`` call has to move, from the configuration.

Kept with the benchmark, like ``bytes.py``, so that no PR that claims a gain
can change the count. One call updates one Mamba-2 layer's recurrent state
for every lane of the batch: the state ``(lanes, d_state, heads x head
channels)`` is read once and written once, in the type the configuration's
``precision`` states for it (so the count follows the configuration and
cannot go stale the way ``bytes.py`` did when PR 28 changed what a call
writes), and beside it the call's small float32 operands: two rows over the
channels in (the decay and ``dt * x``), two columns over the state
dimension in (``B`` and ``C``), one row over the channels out (``y``).
"""

from __future__ import annotations

ITEM_BYTES = {"float32": 4, "bfloat16": 2, "float16": 2}


def call_bytes(cfg: dict) -> int:
    lanes = cfg["serve"]["max_batch"]
    channels = cfg["mamba_n_heads"] * cfg["mamba_d_head"]
    state = lanes * cfg["mamba_d_state"] * channels
    item = ITEM_BYTES[cfg["precision"]["ssm_state"]]
    small = lanes * (3 * channels + 2 * cfg["mamba_d_state"]) * 4
    return 2 * state * item + small


def step_bytes(cfg: dict) -> int:
    """All the calls of one decode step: one a Mamba layer."""
    return cfg["layer_types"].count("mamba") * call_bytes(cfg)
