"""Bytes one decode step of a looped decoder has to move, whatever implements
it, from the configuration and what the step's lanes hold.

Kept with the benchmark, like ``bytes.py``, so that no PR that claims a gain
can change the count. A step runs every lane's token through the
``num_hidden_layers`` layers ``total_ut_steps`` times. What has to come from
HBM, each counted once a time it is needed:

* the layers' weights once a pass, in the type the configuration states for
  its parameters (the norms in float32): a pass needs every layer, pass ``t +
  1`` needs all of pass ``t``, and no chip keeps 4.93 GB between passes, so
  the ``T``-fold read is work and no waste; the final norm and the exit gate
  a pass; the head once; the embedding's rows of the lanes;
* the step's live pages: the committed pages of the lanes (the program's
  count, one slot's worth a step: ``cgx.serve.kv.decoded_pages.global``) at
  their packed bytes and two float32 a bucket, in every one of the ``T x L x
  2`` slots and streams;
* the tails' live rows (the positions past a lane's last committed page, this
  token's among them), in float32, in every slot and stream.

What the reads write (decoded rows, scores) is not counted, so a kernel that
writes less cannot pass 100 % of the roofline this count gives.
"""

from __future__ import annotations

ITEM_BYTES = {"float32": 4, "bfloat16": 2, "float16": 2}


def layer_weight_bytes(cfg: dict) -> int:
    """One layer's weights: four attention projections, three of the SwiGLU,
    four float32 norms."""
    d, f = cfg["hidden_size"], cfg["intermediate_size"]
    width = cfg["num_attention_heads"] * cfg["head_dim"]
    item = ITEM_BYTES[cfg["precision"]["params"]]
    return (4 * d * width + 3 * d * f) * item + 4 * d * 4


def weight_bytes(cfg: dict, lanes: float) -> float:
    """The weights a step reads: the layers, the final norm and the gate
    (float32) once a pass, the head once, ``lanes`` rows of the embedding."""
    d, item = cfg["hidden_size"], ITEM_BYTES[cfg["precision"]["params"]]
    a_pass = (cfg["num_hidden_layers"] * layer_weight_bytes(cfg)
              + (2 * d + 1) * 4)
    return (cfg["total_ut_steps"] * a_pass + d * cfg["vocab_size"] * item
            + lanes * d * item)


def slots(cfg: dict) -> int:
    """Cache slots a position leaves K and V in: one a pass a layer, two
    streams each."""
    return 2 * cfg["total_ut_steps"] * cfg["num_hidden_layers"]


def page_bytes(cfg: dict) -> float:
    """One committed page of one stream of one slot, as the read takes it
    in: packed values and a (unit, minimum) pair of float32 a bucket."""
    precision = cfg["precision"]
    values = (cfg["serve"]["page_tokens"] * cfg["num_key_value_heads"]
              * cfg["head_dim"])
    return (values * precision["kv_page_bits"] / 8
            + values / precision["kv_bucket"] * 2 * 4)


def tail_row_bytes(cfg: dict) -> int:
    """One position of one stream of one slot in the raw tail."""
    return (cfg["num_key_value_heads"] * cfg["head_dim"]
            * ITEM_BYTES[cfg["precision"]["kv_tail"]])


def step_bytes(cfg: dict, lanes: float, live_pages: float,
               live_tail_rows: float) -> float:
    """A decode step over ``lanes`` active lanes that hold, between them and
    in one slot, ``live_pages`` committed pages and ``live_tail_rows`` tail
    positions."""
    return (weight_bytes(cfg, lanes)
            + slots(cfg) * (live_pages * page_bytes(cfg)
                            + live_tail_rows * tail_row_bytes(cfg)))
