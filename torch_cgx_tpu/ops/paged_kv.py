"""Paged KV-cache pool ops: quantized page commit + paged dequantize read.

The serving plane (``torch_cgx_tpu/serving/``) stores each sequence's KV
cache as fixed-size pages in a pre-allocated pool. Pages are QUANTIZED
through the same max-min codec every other wire in the system uses
(``ops.dispatch`` — Pallas kernels on TPU, XLA elsewhere), so a page has
one wire representation everywhere it travels: the prefill→decode
transport ships exactly the bytes the pool stores, and the decode
program's KV read dequantizes them for its consumer in one staged
program. On TPU dispatch the read is the flat Pallas dequantize kernel
walking the page table: the table's page ids are its scalar-prefetch
operand and each grid step fetches its pages' words and meta from the
pool by id, then writes the table once, in the type and the row order the
attention reads (:func:`gather_dequant_pages`). Nothing gathers, reshapes
or relays the pool in front of the kernel, and the pool itself is never
decoded.

Layouts (all static per compiled decode program):

* a page's flat payload is ``page_tokens * n_head * d_head`` values
  (one payload per (layer, K|V) pair);
* quantized pool: ``words (max_pages, *PageSpec.word_shape) int32`` +
  ``meta (max_pages, 2, num_buckets) f32`` per (layer, kind) — row ``p``
  is page ``p``'s rows=1 QTensor, its meta as two lane-dense planes
  (below). The words of a page are the host
  codec's wire words (``ops/codec_host.py``) in their wire order, as rows
  of 128: the flat kernels' own operand layout and type
  (``_quantize_flat_impl`` emits ``(chunks*bits*rb, 128) int32``,
  ``_dequantize_flat_impl`` reads it), so the kernel's blocks ARE the
  pool's rows — a GPT-2 page of 64 tokens x 1,280 is 160 rows (5 chunks x
  8 bits x 4), fetched as one ``(160, 128)`` block at ``page_ids[i]``.
  Stored as ``(max_pages, words)`` the pool had to be gathered and then
  reshaped to ``(n * words / 128, 128)`` in front of every read, and on
  the chip a ``(n, W)`` and a ``(n * W / 128, 128)`` array tile
  differently, so that reshape was a copy of the whole table (PERF.md
  section 6, PR 30). The bytes and their order are the wire's: a frame's
  payload drops into a pool row by a host-side reshape
  (:func:`pool_words`), and ``PageSpec.wire_bytes`` counts both;
* a page's meta is the wire's ``(num_buckets, 2)`` (unit, minimum) pairs
  kept as two planes, ``meta[p, 0]`` the units and ``meta[p, 1]`` the
  minima, a bucket a lane (:func:`pool_meta`; :func:`wire_meta` undoes
  it). The paged read takes a page's ``(2, num_buckets)`` block as it
  lies and turns the two rows into the columns it multiplies by inside
  the kernel (``codec_pallas._plane_columns``). Kept as the wire's pairs,
  ``(max_pages, num_buckets, 2)``, XLA held the pool with the buckets
  along the lanes and the Pallas call wanted the pair padded out to 128
  lanes, so every read call of every step was preceded by a ``copy`` of
  the whole pool's meta, live pages and dead alike (285 MB written for
  4.5 MB of pairs in Trinity: PERF.md section 6, PR 46). Every writer
  turns the few rows it writes (:func:`quantize_page_rows`, the ingest's
  host payload), never the pool; what leaves the pool for the wire or the
  XLA codec is turned back (:func:`pool_qtensor`), so the wire's bytes
  and ``PageSpec.wire_bytes`` are what they were;
* raw pool (``bits == 0``, the f16 shipping baseline):
  ``(max_pages, page_tokens, n_head, d_head) f16``.
"""

from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..config import MAX_BITS, CompressionConfig
from . import codec
from . import codec_pallas
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

    @property
    def word_shape(self) -> Tuple[int, int]:
        """A page's packed words as a pool row holds them: rows of 128,
        the flat kernels' operand layout, in wire order (a page whose
        words are not whole rows of 128 — a bucket under 128 values, a
        chunk tail — is one row of them all; its read gathers)."""
        lanes = 128 if self.packed_words % 128 == 0 else self.packed_words
        return (self.packed_words // lanes, lanes)

    def paged_read_tile(self, n_pages: int, dtype) -> Optional[int]:
        """Chunks a grid step of the paged read of an ``n_pages`` table
        into rows of ``dtype`` (:func:`gather_dequant_pages`), None where
        the read gathers instead. A static function of the geometry
        (``codec_pallas.pages_tile``): the pool is quantized, a page is
        whole 32-bucket chunks of 128-lane buckets (``page_tokens=16``
        pages of GPT-2 are a chunk tail) and the kernel stores the
        consumer's rows itself in a tile of whole pages (a 64-wide
        rotated-key row is not whole lanes)."""
        if not self.quantized:
            return None
        return codec_pallas.pages_tile(
            n_pages, self.flat, self.bucket_size, self.n_head * self.d_head,
            dtype,
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
    """(words, meta) zero pool for a quantized spec, or the raw f16
    pool array for ``bits == 0``."""
    if not spec.quantized:
        return jnp.zeros(
            (max_pages, spec.page_tokens, spec.n_head, spec.d_head),
            jnp.float16,
        )
    return (
        jnp.zeros((max_pages,) + spec.word_shape, jnp.int32),
        jnp.zeros((max_pages, 2, spec.num_buckets), jnp.float32),
    )


def pool_words(packed, spec: PageSpec):
    """Wire words ``(n, packed_words) uint32`` (a QTensor's ``packed``, a
    frame's payload; device or host array) as pool rows ``(n,
    *spec.word_shape) int32``: the same bytes in the same order."""
    return packed.view(jnp.int32).reshape((-1,) + spec.word_shape)


def wire_words(words, spec: PageSpec):
    """Pool rows back as wire words: :func:`pool_words` undone."""
    return words.reshape(-1, spec.packed_words).view(jnp.uint32)


def pool_meta(meta):
    """Wire meta ``(n, num_buckets, 2)`` (a QTensor's (unit, minimum)
    pairs, a frame's; device or host array) as pool rows ``(n, 2,
    num_buckets)``: the units' plane, then the minima's."""
    return meta.swapaxes(-1, -2)


# Pool rows back as the wire's pairs: the same turn undoes itself.
wire_meta = pool_meta


def quantize_page_rows(rows: jax.Array, spec: PageSpec) -> Tuple[jax.Array, jax.Array]:
    """Quantize ``rows (n, flat) f32`` page payloads -> (words, meta)
    pool rows. Deterministic (see :meth:`PageSpec.cc`) so the commit
    path, the host-codec transport path and any replay produce identical
    wire bytes."""
    q = ops_dispatch.quantize_batch(rows.astype(jnp.float32), spec.cc)
    return pool_words(q.packed, spec), pool_meta(q.meta.astype(jnp.float32))


def pool_qtensor(
    words: jax.Array, meta: jax.Array, page_ids: jax.Array, spec: PageSpec
) -> codec.QTensor:
    """The batched QTensor view of gathered pool rows: ``page_ids (n,)``
    int32 (callers clip sentinel ids to a valid row and mask downstream —
    gathers stay in-bounds, masking stays explicit). The gathered rows'
    meta goes back to the wire's pairs here: ``n`` pages, not the pool."""
    n = page_ids.shape[0]
    return codec.QTensor(
        packed=wire_words(words[page_ids], spec),
        meta=wire_meta(meta[page_ids]),
        residual=jnp.zeros((n, 0), jnp.float32),
        numel=spec.flat,
        bits=spec.bits,
        bucket_size=spec.bucket_size,
        dtype=np.dtype(np.float32),
    )


# A window layer's read of its ring (``serving/adapter.py``, "Window and
# global pages"): the same kernel under a name of its own, so that a trace
# times the two page classes apart, and a lowering counter of its own.
WINDOW_READ = "cgx_dequantize_window"


def gather_dequant_pages(
    pool, page_table: jax.Array, spec: PageSpec, dtype=jnp.float32,
    *, window: bool = False, live: Optional[jax.Array] = None,
    unpack: str = "planes",
) -> jax.Array:
    """The decode program's paged KV read: decode the pool rows
    ``page_table (B, P)`` names for the consumer -> ``(B, P * page_tokens,
    n_head * d_head)`` in ``dtype``: one row a cached position, the heads
    side by side as a page holds them, in the type the attention
    contracts in (the adapter's ``cfg.dtype``). A value is ``float32
    decode -> astype(dtype)``, whichever lowering writes it.

    Sentinel entries (< 0) are clipped to row 0 (every fetch stays in
    bounds) and their decoded tokens are garbage by construction —
    callers mask attention scores by the lane's committed token count,
    never by inspecting decoded values. Without ``live`` every table entry
    is decoded. ``window``: the table is a window layer's ring ``(B,
    ring)``; the kernel is then called :data:`WINDOW_READ` and the call
    site counted as ``cgx.codec.lowering.dequantize_pages.window.*``.
    ``unpack``: how the kernel, where the read takes it, turns 8-bit planes
    into levels: ``"planes"`` is the loop over the planes, ``"bytes"``
    ``codec_pallas._unpack_bytes``, the same levels in fewer vector
    operations. The caller asks (``adapter.attend_paged`` asks for bytes,
    ring and tables alike; the adapters that build their own read leave the
    default: ``adapter.layer_cache_rows``), and the call site counts what
    the kernel then does beside the lowering's name, ``.unpack.bytes`` or,
    at another width than 8, ``.unpack.planes``
    (``codec_pallas.unpack_taken``); a gather has no kernel to ask.
    ``live (B, P) bool`` (the ring's caller has one:
    ``adapter.ring_live``): the entries that hold a key some query can
    see. A dead entry's page is neither fetched nor decoded and its rows
    read zero, finite under the attention's ``p @ v`` whatever its slot
    names (a sentinel, a page that slid out, a vacated lane's ring); a live
    entry's rows are what they are without the guard, bit for bit, in either
    lowering.

    Two lowerings, counted per call site as
    ``cgx.codec.lowering.dequantize_pages.*``: ``pallas_paged`` on Pallas
    dispatch where :meth:`PageSpec.paged_read_tile` gives a tile — the
    flat decode kernel fetches each page from the pool by its id
    (``codec_pallas.dequantize_pages``; nothing in front of it but the
    clip), counted as ``pallas_paged.meta_planes`` after the form the
    pool's meta reaches it in — and ``xla_gather`` everywhere else: an XLA
    gather of the table's rows, then ``ops.dispatch.dequantize_batch`` over
    them, their meta turned back to the wire's pairs (the
    64-wide rotated key of a latent cache, whose rows XLA reshapes; pages
    that are not whole chunks; the XLA codec off the TPU; raw pools, a
    gather and a cast).

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

    def dead_zeroed(rows):  # what the kernel's guard stores, by a ``where``
        if live is None:
            return rows
        rows = rows.reshape(b * p, spec.page_tokens, width)
        return jnp.where(live.reshape(-1, 1, 1), rows, 0)

    if not spec.quantized:
        rows = dead_zeroed(pool[ids].astype(dtype))
        return rows.reshape(b, p * spec.page_tokens, width)
    words, meta = pool
    tile = None
    if ops_dispatch.takes_pallas(spec.flat, spec.cc):
        tile = spec.paged_read_tile(b * p, dtype)
    site = "dequantize_pages.window" if window else "dequantize_pages"
    codec_pallas.note_lowering(
        site, "pallas_paged.meta_planes" if tile else "xla_gather")
    if tile:
        codec_pallas.note_lowering(
            site + ".unpack", codec_pallas.unpack_taken(unpack, spec.bits))
        rows = ops_dispatch.dequantize_pages(
            words, meta, ids, spec.cc, tile=tile, out_dtype=dtype,
            row_width=width, unpack=unpack,
            **({"name": WINDOW_READ} if window else {}),
            live=None if live is None else live.reshape(-1).astype(jnp.int32),
        )
    else:
        rows = dead_zeroed(ops_dispatch.dequantize_batch(
            pool_qtensor(words, meta, ids, spec), out_dtype=dtype,
            row_width=width,
        ))
    return rows.reshape(b, p * spec.page_tokens, width)


def append_tail_rows(tail: jax.Array, tail_idx: jax.Array, fresh: jax.Array,
                     dtype, at_pass=None) -> Tuple[jax.Array, jax.Array]:
    """A decode step's write into the lanes' raw tail, and the tail as the
    attention reads it. ``tail (B, page_tokens, width) f32`` is kept as
    those rows, a position's heads side by side as a page holds them;
    ``fresh (B, ...)``, this token's K or V of ``width`` values a lane, goes
    to row ``tail_idx (B,)`` of its lane as float32. Returns ``(the new
    tail, its rows in dtype)``.

    One row a lane is scattered into the donated tail, the cast fuses into
    the attention's dots, and no program relays a tail between what is kept
    and what is contracted (XLA may still stage a tail through its other
    memory space: PERF.md section 5). (Kept by head, ``(B, page_tokens,
    n_head, d_head)``, every layer of every step rewrote the whole tail
    through a ``where`` and copied it to rows, the two tiling differently;
    the ``where`` over rows was measured too and lost to this by 1.7-2.0
    ms a step: PERF.md section 6, PR 36.)

    ``at_pass`` (a traced index): ``tail`` is a looped adapter's ``(T, B,
    page_tokens, width)``, every pass's rows of the lanes, carried whole
    through the loop over the passes; the row goes to pass ``at_pass``'s
    tail, in place, and that pass's rows alone are read. Returns ``(the new
    (T, ...) tail, the pass's rows in dtype)``."""
    b, _, width = tail.shape[-3:]
    if at_pass is None:
        tail = tail.at[jnp.arange(b), tail_idx].set(
            fresh.reshape(b, width).astype(jnp.float32)
        )
        return tail, tail.astype(dtype)
    tail = tail.at[at_pass, jnp.arange(b), tail_idx].set(
        fresh.reshape(b, width).astype(jnp.float32)
    )
    rows = jax.lax.dynamic_index_in_dim(tail, at_pass, 0, keepdims=False)
    return tail, rows.astype(dtype)


def append_tail_block(tail: jax.Array, tail_len: jax.Array, fresh: jax.Array,
                      store: jax.Array) -> jax.Array:
    """:func:`append_tail_rows` for a step that runs a BLOCK of ``L``
    positions a lane and keeps the block's rows on some lanes alone (a
    model that generates by diffusion over blocks: ``serving/adapter.py``,
    "A step is not a token"). ``fresh (B, L, ...)``, the block's K or V of
    ``width`` values a position, goes to rows ``tail_len (B,)`` onward of
    the lanes in ``store (B,) bool`` as float32; a lane that does not store
    keeps its tail as it is (its rows are scattered out of bounds and
    dropped). ``tail_len`` and ``page_tokens`` are multiples of ``L``, so a
    block never straddles the tail's end. Returns the new tail; the caller
    reads the OLD tail and the block's own rows apart, since the block is
    visible to itself whether it is stored or not."""
    b, rows, width = tail.shape
    n = fresh.shape[1]
    at = tail_len[:, None] + jnp.arange(n, dtype=tail_len.dtype)[None, :]
    at = jnp.where(store[:, None], at, rows)  # out of bounds: dropped
    return tail.at[jnp.arange(b)[:, None], at].set(
        fresh.reshape(b, n, width).astype(jnp.float32), mode="drop")


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
    words, meta = pool
    w_rows, m_rows = quantize_page_rows(rows, spec)
    return words.at[page_ids].set(w_rows), meta.at[page_ids].set(m_rows)
