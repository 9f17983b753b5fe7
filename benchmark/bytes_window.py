"""Bytes a decode step's window-layer cache read has to move, from the
configuration and the step's LIVE window pages.

Kept with the benchmark, like ``bytes.py``, so that no PR that claims a gain
can change the count. A window layer's read decodes pages of a lane's ring
into rows the attention contracts. The work that has to be done is the live
pages' (those that hold a key the lane's token can still see; the program
counts them a step as ``cgx.serve.kv.live_pages.window``, one layer's worth,
from the host's own lengths), whatever the kernel decodes beside them: a
page's wire words and its two float32 a bucket read, its rows written in the
type the configuration states for activations. So the count is the same work
whatever reads it: a kernel that decodes every slot of every ring moves more
than this and reads under its share, one that stops at the live pages rises
towards 100 % and cannot pass it.
"""

from __future__ import annotations

ITEM_BYTES = {"float32": 4, "bfloat16": 2, "float16": 2}


def window_layers(cfg: dict) -> int:
    """The layers kept whose pages are a ring."""
    return sum(cfg["sliding_window_layout"][: cfg["num_hidden_layers"]])


def page_bytes(cfg: dict) -> int:
    """One page of one stream (K or V of one layer), read and written."""
    precision = cfg["precision"]
    values = (cfg["serve"]["page_tokens"] * cfg["num_key_value_heads"]
              * cfg["head_dim"])
    return (values * precision["kv_page_bits"] // 8
            + (values // precision["kv_bucket"]) * 2 * 4
            + values * ITEM_BYTES[precision["activations"]])


def step_bytes(cfg: dict, live_pages_per_step: float) -> float:
    """A step's window reads: K and V of every window layer, over the pages
    live in one layer that step."""
    return 2 * window_layers(cfg) * live_pages_per_step * page_bytes(cfg)
