"""Bytes a decode step's expert products have to move, from the
configuration and the held experts the step touched.

Kept with the benchmark, like ``bytes_window.py``, so that no PR that claims
a gain can change the count. A decode step hands every expert a row or two,
so the work of an expert layer's three grouped products is reading the
matrices of the experts that got a token, once each, and nothing else to
speak of: ``hidden_size x moe_intermediate_size`` values for ``gate`` and
``up``, the transpose for ``down``, in the type the configuration states for
its parameters. The program counts the held experts a step touched over its
expert layers (``cgx.serve.moe.experts_touched``). The count is the same
work whichever kernel runs the products (``ragged-dot*`` or
``cgx_grouped_matmul``); one that reads a matrix more than once, or one
nobody chose, moves more than this and reads under its share.
"""

from __future__ import annotations

ITEM_BYTES = {"float32": 4, "bfloat16": 2, "float16": 2}
MATRICES = 3  # gate, up, down


def expert_bytes(cfg: dict) -> int:
    """One routed expert's three matrices."""
    return (MATRICES * cfg["hidden_size"] * cfg["moe_intermediate_size"]
            * ITEM_BYTES[cfg["precision"]["params"]])


def step_bytes(cfg: dict, experts_touched_per_step: float) -> float:
    """A step's expert products: the touched experts' matrices, all layers
    (the program's count is already summed over them)."""
    return experts_touched_per_step * expert_bytes(cfg)
