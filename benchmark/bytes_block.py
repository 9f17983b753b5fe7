"""Bytes one decode step of a block-diffusion decoder has to move, whatever
implements it, from the configuration and what the step's lanes hold.

Kept with the benchmark, like ``bytes_loop.py``, so that no PR that claims a
gain can change the count. A step runs the open block of every lane, ``L =
block_length`` positions, through the ``num_hidden_layers`` layers once,
whether the lane denoises or stores. What has to come from HBM, each counted
once:

* the layers' weights in the type the configuration states for its parameters
  (the norms and the router in float32), every routed expert among them (at
  64 lanes a step makes 2,048 assignments over a layer's 128 experts: all are
  chosen); the final norm and the head once; the embedding's rows of the
  lanes' positions;
* the step's live pages: the committed pages of the lanes (the program's
  count, one layer's and one stream's worth a step:
  ``cgx.serve.kv.decoded_pages.global``) at their packed bytes and two
  float32 a bucket, on every layer and both streams, ONCE for the block's
  ``L`` queries;
* the tails' live rows (the positions past a lane's last committed page and
  before its open block; the block's own keys and values come from the step's
  own products, not from HBM), in float32, on every layer and both streams.

What the reads write (decoded rows, scores, the logits) is not counted, so a
kernel that writes less cannot pass 100 % of the roofline this count gives.
"""

from __future__ import annotations

ITEM_BYTES = {"float32": 4, "bfloat16": 2, "float16": 2}


def layer_weight_bytes(cfg: dict) -> int:
    """One layer's weights: four attention projections and three matrices
    an expert in the parameters' type; the router, two norms of the stream
    and two of a head in float32."""
    d, dh = cfg["hidden_size"], cfg["head_dim"]
    h, hk = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    e, f = cfg["num_experts"], cfg["moe_intermediate_size"]
    item = ITEM_BYTES[cfg["precision"]["params"]]
    return ((2 * d * h * dh + 2 * d * hk * dh + 3 * e * d * f) * item
            + (d * e + 2 * d + 2 * dh) * ITEM_BYTES[
                cfg["precision"]["router"]])


def weight_bytes(cfg: dict, lanes: float) -> float:
    """The weights a step reads: every layer, the final norm (float32) and
    the head once, a row of the embedding a position of the ``lanes``
    lanes' blocks."""
    d, item = cfg["hidden_size"], ITEM_BYTES[cfg["precision"]["params"]]
    return (cfg["num_hidden_layers"] * layer_weight_bytes(cfg) + d * 4
            + d * cfg["vocab_size"] * item
            + lanes * cfg["block_length"] * d * item)


def streams(cfg: dict) -> int:
    """Cache streams a position leaves a row in: K and V on every layer."""
    return 2 * cfg["num_hidden_layers"]


def page_bytes(cfg: dict) -> float:
    """One committed page of one stream of one layer, as the read takes it
    in: packed values and a (unit, minimum) pair of float32 a bucket."""
    precision = cfg["precision"]
    values = (cfg["serve"]["page_tokens"] * cfg["num_key_value_heads"]
              * cfg["head_dim"])
    return (values * precision["kv_page_bits"] / 8
            + values / precision["kv_bucket"] * 2 * 4)


def tail_row_bytes(cfg: dict) -> int:
    """One position of one stream of one layer in the raw tail."""
    return (cfg["num_key_value_heads"] * cfg["head_dim"]
            * ITEM_BYTES[cfg["precision"]["kv_tail"]])


def step_bytes(cfg: dict, lanes: float, live_pages: float,
               live_tail_rows: float) -> float:
    """A block step over ``lanes`` active lanes that hold, between them and
    in one stream of one layer, ``live_pages`` committed pages and
    ``live_tail_rows`` tail positions before their open blocks."""
    return (weight_bytes(cfg, lanes)
            + streams(cfg) * (live_pages * page_bytes(cfg)
                              + live_tail_rows * tail_row_bytes(cfg)))
