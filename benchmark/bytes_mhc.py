"""Bytes a decode step's ``cgx_mhc_pre_decode`` calls have to move, from the
configuration.

Kept with the benchmark, like ``bytes_gdn.py``, so that no PR that claims a
gain can change the count. A hyper-connected model's decode step computes,
in front of each of its two sublayers a layer, the sublayer's input and the
mixes of the way back from the lanes' residual streams, and once more in
front of the final norm the read-out. One call reads the streams of every
lane once, ``hc_mult x hidden_size`` values a lane in the activations'
type, and the sublayer's ``phi`` once in float32, ``hc_mult x hidden_size``
rows of ``2 hc_mult + hc_mult^2`` mixes (the read-out's: ``hc_mult``), and
writes the sublayer's input, ``hidden_size`` values a lane in the
activations' type, and the mixes of the way back as float32, ``hc_mult +
hc_mult^2`` a lane (the read-out: none). ``alpha`` and ``base``, some
dozens of numbers, are left out.
"""

from __future__ import annotations

ITEM_BYTES = {"float32": 4, "bfloat16": 2, "float16": 2}


def call_bytes(cfg: dict, mixes: bool = True) -> int:
    """One call over the step's lanes; ``mixes`` False: the read-out's."""
    lanes, n, d = cfg["serve"]["max_batch"], cfg["hc_mult"], cfg["hidden_size"]
    act = ITEM_BYTES[cfg["precision"]["activations"]]
    coefficient = ITEM_BYTES[cfg["precision"]["hc_coefficients"]]
    columns = 2 * n + n * n if mixes else n
    streams = lanes * n * d * act
    phi = n * d * columns * coefficient
    out = lanes * d * act + (lanes * (n + n * n) * 4 if mixes else 0)
    return streams + phi + out


def step_bytes(cfg: dict) -> int:
    """All the calls of one decode step: two a layer and the read-out."""
    return (2 * cfg["num_hidden_layers"] * call_bytes(cfg)
            + call_bytes(cfg, mixes=False))
