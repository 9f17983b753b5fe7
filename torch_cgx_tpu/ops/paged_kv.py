"""Paged KV-cache pool ops: quantized page commit + gather-dequantize read.

The serving plane (``torch_cgx_tpu/serving/``) stores each sequence's KV
cache as fixed-size pages in a pre-allocated pool. Pages are QUANTIZED
through the same max-min codec every other wire in the system uses
(``ops.dispatch`` — Pallas kernels on TPU, XLA elsewhere), so a page has
one wire representation everywhere it travels: the prefill→decode
transport ships exactly the bytes the pool stores, and the decode
program's KV read dequantizes them for its consumer — the gathered page
rows feed ``dequantize_batch`` immediately before the attention in one
staged program. On TPU dispatch the decode rides the flat Pallas
dequantize kernel, which writes the gathered table once, in the type and
the row order the attention reads (:func:`gather_dequant_pages`); the
pool itself is never decoded.

Layouts (all static per compiled decode program):

* a page's flat payload is ``page_tokens * n_head * d_head`` values
  (one payload per (layer, K|V) pair);
* quantized pool: ``packed (max_pages, words) uint32`` +
  ``meta (max_pages, num_buckets, 2) f32`` per (layer, kind) — row ``p``
  is page ``p``'s rows=1 QTensor, byte-compatible with the host codec's
  wire format (``ops/codec_host.py``), so transport bytes drop straight
  into pool rows;
* raw pool (``bits == 0``, the f16 shipping baseline):
  ``(max_pages, page_tokens, n_head, d_head) f16``.
"""

from __future__ import annotations

import dataclasses
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..config import MAX_BITS, CompressionConfig
from . import codec
from . import dispatch as ops_dispatch


@dataclasses.dataclass(frozen=True)
class PageSpec:
    """Static geometry of one (layer, K|V) page pool."""

    page_tokens: int
    n_head: int
    d_head: int
    bits: int  # 0 = raw f16 pool
    bucket_size: int

    def __post_init__(self):
        if self.page_tokens < 1:
            raise ValueError(f"page_tokens must be >= 1, got {self.page_tokens}")
        if self.bits and not 1 <= self.bits <= MAX_BITS:
            raise ValueError(
                f"page bits must be 0 (raw) or 1..{MAX_BITS}, got {self.bits}"
            )

    @property
    def flat(self) -> int:
        """Values per page payload."""
        return self.page_tokens * self.n_head * self.d_head

    @property
    def quantized(self) -> bool:
        return bool(self.bits)

    @property
    def num_buckets(self) -> int:
        return codec.num_buckets(self.flat, self.bucket_size)

    @property
    def packed_words(self) -> int:
        """uint32 words per page row — the codec packs the bucket-PADDED
        level array (``nb * bucket_size`` values), which exceeds
        ``packed_words(flat, bits)`` when the final bucket's padding
        crosses a 32-lane group (the ``codec_host.wire_layout``
        convention; both wire ends must agree)."""
        if not self.bits:
            return 0
        return codec.packed_words(
            self.num_buckets * self.bucket_size, self.bits
        )

    @property
    def cc(self) -> CompressionConfig:
        """Deterministic codec config of this pool: page quantization is
        one-shot (a page is quantized once at commit and decoded many
        times), so stochastic rounding would add noise with nothing to
        average it out — always deterministic, regardless of the
        training-plane CGX_STOCHASTIC_ROUNDING default."""
        return CompressionConfig(
            bits=self.bits, bucket_size=self.bucket_size, stochastic=False
        )

    def wire_bytes(self) -> int:
        """Transport bytes of one page payload at this spec (meta f32 +
        bucket-padded packed words — the exact frame payload the
        transport ships), raw f16 otherwise."""
        if not self.quantized:
            return 2 * self.flat
        return 2 * self.num_buckets * 4 + self.packed_words * 4

    def raw_bytes(self) -> int:
        """f32 bytes of one page payload (the wire-ratio numerator)."""
        return 4 * self.flat


def default_bucket(flat: int, base: int = 512) -> int:
    """Page bucket size: the training-plane default clipped to the
    payload (a page smaller than one bucket quantizes as a single
    bucket)."""
    return max(1, min(base, flat))


def empty_pool(max_pages: int, spec: PageSpec):
    """(packed, meta) zero pool for a quantized spec, or the raw f16
    pool array for ``bits == 0``."""
    if not spec.quantized:
        return jnp.zeros(
            (max_pages, spec.page_tokens, spec.n_head, spec.d_head),
            jnp.float16,
        )
    return (
        jnp.zeros((max_pages, spec.packed_words), jnp.uint32),
        jnp.zeros((max_pages, spec.num_buckets, 2), jnp.float32),
    )


def quantize_page_rows(rows: jax.Array, spec: PageSpec) -> Tuple[jax.Array, jax.Array]:
    """Quantize ``rows (n, flat) f32`` page payloads -> (packed, meta)
    pool rows. Deterministic (see :meth:`PageSpec.cc`) so the commit
    path, the host-codec transport path and any replay produce identical
    wire bytes."""
    q = ops_dispatch.quantize_batch(rows.astype(jnp.float32), spec.cc)
    return q.packed, q.meta.astype(jnp.float32)


def pool_qtensor(
    packed: jax.Array, meta: jax.Array, page_ids: jax.Array, spec: PageSpec
) -> codec.QTensor:
    """The batched QTensor view of gathered pool rows: ``page_ids (n,)``
    int32 (callers clip sentinel ids to a valid row and mask downstream —
    gathers stay in-bounds, masking stays explicit)."""
    n = page_ids.shape[0]
    return codec.QTensor(
        packed=packed[page_ids],
        meta=meta[page_ids],
        residual=jnp.zeros((n, 0), jnp.float32),
        numel=spec.flat,
        bits=spec.bits,
        bucket_size=spec.bucket_size,
        dtype=np.dtype(np.float32),
    )


def gather_dequant_pages(
    pool, page_table: jax.Array, spec: PageSpec, dtype=jnp.float32
) -> jax.Array:
    """The decode program's paged KV read: gather ``page_table (B, P)``
    rows from the pool and decode them for the consumer -> ``(B,
    P * page_tokens, n_head * d_head)`` in ``dtype``: one row a cached
    position, the heads side by side as a page holds them, in the type
    the attention contracts in (the adapter's ``cfg.dtype``). A value is
    ``float32 decode -> astype(dtype)``, whichever lowering writes it.

    Sentinel entries (< 0) are clipped to row 0 before the gather (XLA
    gathers must stay in bounds) and their decoded tokens are garbage by
    construction — callers mask attention scores by the lane's committed
    token count, never by inspecting decoded values. The dequantize is
    ``ops.dispatch.dequantize_batch``: the Pallas flat decode kernel on
    TPU dispatch (whole-chunk pages), staged XLA elsewhere.

    XLA does NOT fuse the decode into the attention that reads it: the
    kernel's output is a table in HBM, and whatever lies between it and
    the contraction is paid over the whole table. When this returned
    ``(B, T, n_head, d_head)`` float32, a GPT-2 large step spent 104.8 ms
    of its 169.6 in ``copy`` + ``reshape`` + ``convert`` (joining the
    tail, transposing to heads-major, casting; ledger, PR 27) against
    40.5 ms in the kernel. So the kernel is asked for the consumer's type
    and row width (``row_width``: it stores the rows' tiling itself, see
    ``codec_pallas._dequantize_flat_impl``), the attention contracts the
    rows where they lie (``models.attention.decode_attention``,
    ``models.mla_moe.attend_absorbed``) and the tail is attended apart
    (``models.attention.joined_softmax``)."""
    b, p = page_table.shape
    ids = jnp.maximum(page_table.reshape(-1), 0)
    width = spec.n_head * spec.d_head
    if not spec.quantized:
        rows = pool[ids].astype(dtype)
    else:
        packed, meta = pool
        q = pool_qtensor(packed, meta, ids, spec)
        rows = ops_dispatch.dequantize_batch(
            q, out_dtype=dtype, row_width=width
        )
    return rows.reshape(b, p * spec.page_tokens, width)


def commit_page_rows(pool, page_ids: jax.Array, rows: jax.Array, spec: PageSpec):
    """Functionally write ``rows (n, flat)`` payloads into pool rows
    ``page_ids (n,)`` (quantizing when the spec does) — the jitted
    commit path of the decode scheduler's tail→page promotion. Returns
    the updated pool; callers donate the old one."""
    if not spec.quantized:
        pages = rows.reshape(
            -1, spec.page_tokens, spec.n_head, spec.d_head
        ).astype(jnp.float16)
        return pool.at[page_ids].set(pages)
    packed, meta = pool
    p_rows, m_rows = quantize_page_rows(rows, spec)
    return packed.at[page_ids].set(p_rows), meta.at[page_ids].set(m_rows)
