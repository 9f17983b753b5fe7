"""Bytes the decode step's latent-cache read has to move, from its shapes.

Kept with the benchmark, like ``bytes.py``, so that no PR that claims a gain
can change the count. The step of a latent-attention (MLA) server reads two
cache streams a layer, the latent and the rotated key, each through one
``cgx_dequantize_flat`` call that decodes the whole static page table of
every lane.
"""

from __future__ import annotations


def stream_call_bytes(max_batch: int, max_seq: int, width: int, bits: int,
                      bucket: int) -> int:
    """One call: ``max_batch * max_seq`` token rows of ``width`` values read
    as ``bits``-bit words plus two float32 a bucket, written as float32."""
    values = max_batch * max_seq * width
    return values * bits // 8 + (values // bucket) * 2 * 4 + values * 4


def latent_step_bytes(cfg: dict) -> int:
    """All the calls of one decode step: both streams of every layer."""
    serve, precision = cfg["serve"], cfg["precision"]
    return cfg["num_hidden_layers"] * sum(
        stream_call_bytes(serve["max_batch"], serve["max_seq"], width,
                          precision["kv_page_bits"], precision["kv_bucket"])
        for width in (cfg["kv_lora_rank"], cfg["qk_rope_head_dim"])
    )
