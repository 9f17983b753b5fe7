"""What a model adapter is, and what it may use of the cache.

The scheduler (``serving/scheduler.py``) serves a MODEL ADAPTER through the
compiled programs of ``serving/programs.py``. An adapter states, per layer,
the cache streams a token leaves behind as ``(name, PageSpec)`` (GPT-2's
``k`` and ``v``, a latent-attention model's latent and rotated key) and gives
a prefill and a decode forward over them. Pools, tails and every program go
over the streams the adapter names; nothing above this module knows what a
stream means. Layers may name different streams (a hybrid model's few
attention layers among its recurrent ones): a layer without a stream has no
pool, tail or payload for it. Beside the pages an adapter may state, per
layer, STATE streams ``(name, shape, dtype)``: a fixed-size recurrent state
a lane, rewritten whole by every decode step, written into a lane at
admission from what the prefill left on the device, and never committed,
paged, forked or evicted by page.

A layer's pages are of one of two CLASSES, which the adapter states
(``page_window(layer)``, "Window and global pages" in docs/SERVING.md).
*Global* pages are the above: the lane's row of ``page_table``, as many as
the sequence is long. A sliding-window layer's pages are a *ring*: ``ring =
ceil(W / page_tokens) + 1`` pool rows a sequence (``kv_cache.alloc_ring``),
named by the lane's row of a second table, ``ring_table (lanes, ring)``;
page ``n`` is written into slot ``n % ring``, over page ``n - ring``, which
no query of the lane can see again. Such a layer's pools hold ``max_batch x
ring + 1`` rows whatever ``max_seq`` is, its decode read goes over the ring
alone, and its mask is made of positions (:func:`ring_masks`). An adapter
that states no window builds exactly the programs and the state it built
before there were classes.

A cache layer is not a weight layer. A looped model runs its ``n_layer``
layers ``T`` times a token and every pass leaves K and V of its own, which
that pass alone reads: the adapter states ``cache_passes = T`` and the
programs give every layer's pools and tails a PASS dimension ("Passes" in
docs/SERVING.md). A stream's pool then holds ``T x (max_pages + 1)`` rows,
page ``p`` of pass ``t`` at row ``t x (max_pages + 1) + p`` (a scratch row
a pass), so one page id names a page's ``2 x n_layer x T`` slabs and the
page allocator, the page table, ``n_pages`` and ``tail_len`` stay one a
lane; a tail is ``(T, lanes, page_tokens, width)``. A pass reads through
the lane's table plus its offset (:func:`pass_view`). With ``cache_passes``
1, which every adapter but ``serving/loop.py``'s states, nothing has the
dimension and every program is what it was.

A step is not a token. A model that generates by diffusion over blocks runs
a BLOCK of ``L`` positions a lane a step: the adapter states ``block_tokens =
L`` ("Blocks" in docs/SERVING.md) and ``state`` then holds ``tokens (B, L)``,
the lane's open block (``mask_token`` where a position is not known yet),
``known (B, L) bool``, ``unmask_step (B, L)``, the denoising step at which a
position became known (-1 for a token of the prompt), and ``block_step
(B,)``, the denoising steps the open block has had. A lane whose block is
all known STORES at the step it is next run (:func:`block_stores`): that
forward's K and V go to the tail (``paged_kv.append_tail_block``), ``pos``
and ``tail_len`` advance by ``L`` and the ``L`` tokens are the step's
output; every other active lane DENOISES: its forward's K and V are
dropped, and some masked positions take their greedy token
(:func:`unmask_block`, the one unmask rule). So a lane-step yields 0 tokens
or ``L``, the device decides which, and the host reads it with the tokens.
A block's queries see the lane's committed pages, its tail's live rows and
the block's own ``L`` keys, all of them, in one softmax
(:func:`attend_paged_block`); a prefill runs under the same mask
(``ops.dispatch.prefill_attention(block=L)``). Prefill produces no token: it
covers the prompt's whole blocks, and the ``n % L`` tokens left open the
lane's first block as known tokens (``admit_lane``). ``page_tokens`` is a
multiple of ``L``, so a block never straddles a page. With ``block_tokens``
1, which every adapter but ``serving/block.py``'s states, ``state`` has none
of those entries and every program is what it was.

:class:`Adapter` is the protocol, with the defaults every adapter shares; a
model is a subclass in a module of its own (``gpt2.py``, ``latent.py``,
``hybrid.py``, ``window.py``, ``loop.py``, ``block.py``) that imports this
module, ``models/`` and ``ops/`` and nothing above. Beside it, what a
``decode_forward`` is made of: the lane's masks (:func:`lane_masks`,
:func:`page_live`, :func:`ring_masks`, :func:`ring_live`,
:func:`block_masks`), a layer's cache as its attention contracts it
(:func:`layer_cache_rows`), and the K/V attention over both
(:func:`attend_paged`, :func:`attend_paged_block`).
"""

from __future__ import annotations

import dataclasses
from typing import List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from .. import config as cfg_mod
from ..models.attention import decode_attention
from ..ops import paged_kv
from .kv_cache import resolve_kv_config
from .transport import DEFAULT_SHIP_DEPTH


@dataclasses.dataclass(frozen=True)
class ServeConfig:
    """Serving geometry (static shapes of the compiled decode step)."""

    page_tokens: int
    max_batch: int
    max_pages: int
    max_seq: int
    ship_depth: int
    eos_token: Optional[int] = None

    def __post_init__(self):
        if self.max_seq < self.page_tokens:
            raise ValueError(
                f"max_seq {self.max_seq} < page_tokens {self.page_tokens}"
            )

    @property
    def pages_per_seq(self) -> int:
        return -(-self.max_seq // self.page_tokens)

    @property
    def commit_lanes(self) -> int:
        """Tails one call of the ``commit`` program promotes (its ``K``).
        With every lane decoding, ``max_batch / page_tokens`` tails fill a
        step; a power of two four times that, and at least 4, leaves a
        second call to the rare tick in which more fill at once (the start
        of a run, a burst of equal prompts): 8 of 96 lanes at 64-token
        pages, 4 of 32 or 64 at 64 or 256."""
        fills = -(-self.max_batch // self.page_tokens)
        return min(self.max_batch, max(4, 1 << (4 * fills - 1).bit_length()))

    @classmethod
    def from_env(cls, model=None,
                 eos_token: Optional[int] = None) -> "ServeConfig":
        """Knobs with the planner filling the zeros: ``CGX_KV_PAGE_TOKENS``
        / ``CGX_KV_SHIP_DEPTH`` unset lets ``planner.solve_serve_plan``
        pick page size and shipping depth from the serve cost curves.
        ``model`` (an adapter or its model config: anything with
        ``n_layer``, ``kv_bytes_per_token()`` and
        ``state_bytes_per_lane()``) says what a token's cache weighs, which
        differs sevenfold between a K/V cache and a latent one, and what a
        lane's recurrent state weighs whatever its length; without it the
        static defaults apply."""
        pt = cfg_mod.kv_page_tokens()
        depth = cfg_mod.kv_ship_depth()
        if (not pt or not depth) and model is not None:
            from ..parallel import planner

            plan = planner.solve_serve_plan(
                prompt_tokens=min(cfg_mod.serve_max_seq(), 128),
                kv_token_bytes=model.kv_bytes_per_token(),
                # a hybrid model's pages are its attention layers' alone
                n_layers=getattr(model, "n_cache_layers", model.n_layer),
                bits=cfg_mod.kv_bits(),
                bucket=cfg_mod.default_compression_config().bucket_size,
                state_lane_bytes=model.state_bytes_per_lane(),
            )
            pt = pt or plan.page_tokens
            depth = depth or plan.ship_depth
        return cls(
            page_tokens=pt or cfg_mod.DEFAULT_KV_PAGE_TOKENS,
            max_batch=cfg_mod.serve_max_batch(),
            max_pages=cfg_mod.serve_max_pages(),
            max_seq=cfg_mod.serve_max_seq(),
            ship_depth=depth or DEFAULT_SHIP_DEPTH,
            eos_token=eos_token,
        )



def page_specs(layer_name: str, page_tokens: int,
               shapes: Sequence[Tuple[int, int]]) -> List[paged_kv.PageSpec]:
    """The page geometry of a layer's cache streams, one per ``(n_head,
    d_head)`` of ``shapes``, under the CURRENT ``kv_page`` resolution of the
    layer (resolved once: this runs every tick, for the program key): the
    registered edge configs (the SLO controller's writes) or the
    ``CGX_KV_BITS`` env default decide bits; the bucket is the resolved
    config's (env-back-filled) bucket clipped to each stream's page
    payload."""
    cc = resolve_kv_config(layer_name)
    if cc is None or not cc.enabled:
        return [paged_kv.PageSpec(page_tokens, h, d, bits=0, bucket_size=1)
                for h, d in shapes]
    return [
        paged_kv.PageSpec(
            page_tokens, h, d, bits=cc.bits,
            bucket_size=paged_kv.default_bucket(page_tokens * h * d,
                                                cc.bucket_size),
        )
        for h, d in shapes
    ]



def lane_masks(serve: ServeConfig, state):
    """What every attention layer of a decode step shares: ``(tail_idx
    (B,)``, the tail row this token's cache payload goes to; ``mask_c (B,
    pages x page_tokens)``, the committed positions; ``mask_t (B,
    page_tokens))``, the tail's live positions, this token's among them."""
    pt = serve.page_tokens
    b = state["tokens"].shape[0]
    tail_idx = jnp.minimum(state["tail_len"], pt - 1)
    committed = state["n_pages"] * pt
    pos_c = jax.lax.broadcasted_iota(
        jnp.int32, (b, serve.pages_per_seq * pt), 1)
    pos_t = jax.lax.broadcasted_iota(jnp.int32, (b, pt), 1)
    mask_c = pos_c < committed[:, None]
    mask_t = pos_t <= tail_idx[:, None]
    return tail_idx, mask_c, mask_t


def page_live(serve: ServeConfig, state):
    """:func:`lane_masks`' ``mask_c`` by page, ``(B, pages_per_seq) bool``:
    the slots of a lane's ``page_table`` row that hold a committed page, its
    first ``n_pages``. A dead slot is one the lane has not reached (a
    vacated lane's whole row); the read of a global layer neither fetches
    nor decodes it (:func:`attend_paged`; a latent layer's ``c``,
    :func:`layer_cache_rows`). A step's sum over the
    held lanes is what the host counts as
    ``cgx.serve.kv.decoded_pages.global``."""
    b = state["tokens"].shape[0]
    slot = jax.lax.broadcasted_iota(jnp.int32, (b, serve.pages_per_seq), 1)
    return slot < state["n_pages"][:, None]


def ring_pages(serve: ServeConfig, window: int) -> int:
    """Pool rows a sequence's ring holds on a layer of window ``window``:
    the pages that can hold a visible key while the tail fills, and the one
    that has slid out, which the next commit writes over."""
    return -(-window // serve.page_tokens) + 1


def _slot_pages(state, slot, ring: int):
    """The newest committed page a lane's ring holds in ``slot (B, ...)``:
    the newest ``n`` with ``n % ring == slot``; under 0, never written."""
    newest = state["n_pages"][:, None] - 1
    return newest - (newest - slot) % ring


def ring_masks(serve: ServeConfig, state, window: int):
    """A window layer's ``mask_c (B, ring x page_tokens)`` beside
    :func:`lane_masks`' (whose tail mask holds as it is: a tail is never
    longer than a page, and a window never shorter). Slot ``s`` of a lane's
    ring holds the newest committed page ``n`` with ``n % ring == s``; its
    row ``r`` is position ``n * page_tokens + r``, live where the lane's
    token at ``pos`` can see it: ``pos - position < window``."""
    pt = serve.page_tokens
    ring = ring_pages(serve, window)
    b = state["tokens"].shape[0]
    at = jax.lax.broadcasted_iota(jnp.int32, (b, ring * pt), 1)
    page = _slot_pages(state, at // pt, ring)
    position = page * pt + at % pt
    return (page >= 0) & (state["pos"][:, None] - position < window)


def ring_live(serve: ServeConfig, state, window: int):
    """:func:`ring_masks` by slot, ``(B, ring) bool``: the slots that hold a
    row the lane's token can see, which is whether it sees the slot's newest
    row. A dead slot is one never written (a short lane's, a vacated lane's
    whole ring) or one whose page has slid out of the window; the read
    neither fetches nor decodes it (``paged_kv.gather_dequant_pages``). A
    step's sum over the held lanes is what the host counts as
    ``cgx.serve.kv.live_pages.window``."""
    pt = serve.page_tokens
    ring = ring_pages(serve, window)
    b = state["tokens"].shape[0]
    slot = jax.lax.broadcasted_iota(jnp.int32, (b, ring), 1)
    page = _slot_pages(state, slot, ring)
    newest_row = page * pt + pt - 1
    return (page >= 0) & (state["pos"][:, None] - newest_row < window)


def pass_view(state, tails, at_pass, serve: ServeConfig):
    """``state`` as pass ``at_pass`` (a traced index into the pass
    dimension) of a looped adapter's decode step reads it: the lane's
    ``page_table`` moved by the pass's offset into the pools, ``at_pass x
    (max_pages + 1)`` rows (a sentinel entry then names the scratch row of
    the pass before, in bounds and dead), and ``tails {stream: each
    layer's (T, B, page_tokens, width)}`` as the step's passes so far left
    them, in the place of the state's. The pools are the state's own: a
    loop over the passes carries the tails and closes over the pools."""
    return {
        **state,
        "page_table": state["page_table"] + at_pass * (serve.max_pages + 1),
        **{f"tail_{name}": layers for name, layers in tails.items()},
    }


def layer_cache_rows(state, layer: int, layer_streams, tail_idx, fresh,
                     dtype, window: bool = False, live=None,
                     paged_only: bool = False, at_pass=None,
                     unpack: str = "planes"):
    """A layer's cache as its attention contracts it, at a decode position:
    for each of the layer's streams, in order, this token's payload (the
    matching entry of ``fresh``, ``(B, ...)`` of the stream's width) written
    into the raw tail (``paged_kv.append_tail_rows``) and the committed
    pages read where they lie (``paged_kv.gather_dequant_pages``). Returns
    ``({stream: pages (B, P * page_tokens, width)}, {stream: tail rows (B,
    page_tokens, width)}``, both in ``dtype``, ``{stream: the new float32
    tail})``. ``window``: the layer's pages are the lane's ring, ``P`` its
    slots, in the ring's order (a softmax does not care). ``live (B, P)
    bool``: the table's entries the read decodes (:func:`page_live`,
    :func:`ring_live`), the others' rows zeros; None reads every entry.
    ``paged_only``: ``live`` goes to the streams alone whose geometry lets
    the kernel fetch pages by id (``PageSpec.paged_read_tile``), where a
    dead entry costs the store of its zero rows; a stream that gathers (a
    latent cache's 64-wide ``kr``; a raw pool) is read whole, since there a
    guard is one more pass over the decoded table and its dead rows, finite
    whatever they hold, are masked anyway. ``at_pass``: ``state`` is a
    looped adapter's :func:`pass_view`, whose tails keep every pass's rows
    ``(T, B, page_tokens, width)``: the payload goes to pass ``at_pass``'s
    row and that pass's rows are the ones read (the new tail returned is
    the whole ``(T, ...)`` array). ``unpack``: the unpack the read's kernel
    is asked for (``paged_kv.gather_dequant_pages``); the adapters that
    call this themselves leave it the plane loop (:func:`attend_paged` says
    why there are two)."""
    table = state["ring_table" if window else "page_table"]
    pages, tails, new = {}, {}, {}
    for (name, spec), value in zip(layer_streams, fresh):
        new[name], tails[name] = paged_kv.append_tail_rows(
            state[f"tail_{name}"][layer], tail_idx, value, dtype,
            # four operands without a pass: what tests put in its place take
            *(() if at_pass is None else (at_pass,))
        )
        guard = live
        if paged_only and live is not None and not spec.paged_read_tile(
                live.size, dtype):
            guard = None
        pages[name] = paged_kv.gather_dequant_pages(
            state["pools"][layer][name], table, spec, dtype, window=window,
            live=guard, unpack=unpack,
        )
    return pages, tails, new


def attend_paged(state, layer: int, layer_streams, masks, q, k, v, dt,
                 score_divisor, window: bool = False, live=None,
                 at_pass=None):
    """One decode position of an attention layer over a lane's cache: this
    token's ``k`` and ``v (B, 1, Hk, dh)`` into the raw tails and the
    committed pages read as ``dt`` rows where they lie
    (:func:`layer_cache_rows`), one ``decode_attention`` of ``q (B, 1, H,
    dh)`` over pages and tail. ``masks`` are :func:`lane_masks`' (with
    ``window``, the layer's pages are the lane's ring and the page mask
    :func:`ring_masks`'). ``live`` is the page mask by table entry, built
    once a step and shared by the layers of a class and both streams: a
    global layer's :func:`page_live`, a window layer's :func:`ring_live`.
    The read skips the entries it leaves out, whose rows the mask hides
    anyway (an adapter that hands it down says so:
    ``Adapter.guards_global_read``). ``at_pass``: the pass of a looped
    adapter whose :func:`pass_view` ``state`` is (:func:`layer_cache_rows`).
    Returns ``(o (B, H * dh), {stream: its new tail})``."""
    tail_idx, mask_c, mask_t = masks
    # The byte unpack for the ring and the tables alike. The adapters that
    # call layer_cache_rows themselves (GPT-2, the latent ones) stay on the
    # plane loop only because their cells' rooflines count a fixed table
    # (ROADMAP A1(a)); after it, A4c(ii) deletes the keyword,
    # ``codec_pallas.unpack_taken`` and the counter: add no third form.
    pages, tails, new = layer_cache_rows(
        state, layer, layer_streams, tail_idx, (k, v), dt, window, live,
        at_pass=at_pass, unpack="bytes",
    )
    o = decode_attention(
        q[:, 0], pages["k"], pages["v"], tails["k"], tails["v"],
        mask=mask_c, tail_mask=mask_t, score_divisor=score_divisor,
    )
    return o, new


# What a block step counts on the device, as ``cgx.serve.<name>``; the last
# entries of a block adapter's ``step_counters``, which ``programs.build``'s
# ``decode_step`` appends to what ``decode_forward`` counted: active lanes
# run, lanes that stored (blocks finished), positions unmasked, and denoise
# lane-steps that unmasked more than the schedule's share.
BLOCK_COUNTERS = ("block.lane_steps", "block.stores", "block.unmasked",
                  "block.early")


def block_stores(state):
    """``(B,) bool``: the lanes that store at the step ``state`` is given
    to: active, and every position of the open block known."""
    return state["active"] & jnp.all(state["known"], axis=-1)


def block_masks(serve: ServeConfig, state):
    """:func:`lane_masks` for a step that runs a block a lane: ``(mask_c (B,
    pages x page_tokens)``, the committed positions; ``mask_t (B,
    page_tokens))``, the tail's live positions, which are the rows before
    ``tail_len``: the block's own keys are read apart
    (:func:`attend_paged_block`), stored or not."""
    pt = serve.page_tokens
    b = state["tokens"].shape[0]
    committed = state["n_pages"] * pt
    pos_c = jax.lax.broadcasted_iota(
        jnp.int32, (b, serve.pages_per_seq * pt), 1)
    pos_t = jax.lax.broadcasted_iota(jnp.int32, (b, pt), 1)
    return (pos_c < committed[:, None],
            pos_t < state["tail_len"][:, None])


def attend_paged_block(state, layer: int, layer_streams, masks, q, k, v, dt,
                       score_divisor, store, live=None):
    """:func:`attend_paged` for a block of ``L`` positions a lane: ``q (B,
    L, H, dh)`` over the lane's committed pages, read ONCE for the ``L``
    queries (:func:`paged_kv.gather_dequant_pages`, the byte unpack, the
    table's ``live`` slots), the tail's live rows and the block's own ``k``
    and ``v (B, L, Hk, dh)``, every one of which every query of the block
    sees: one joined softmax. The block's rows go to the raw tails of the
    lanes in ``store (B,) bool`` alone (``paged_kv.append_tail_block``).
    ``masks`` are :func:`block_masks`'. The queries are folded head-major
    into ``decode_attention``'s head axis, ``H x L`` heads of which ``L x H
    / Hk`` neighbours read one K/V head, and the block's own rows ride
    behind the tail's (``page_tokens + L`` rows a lane): the contraction is
    the single-position one and no key is transposed or copied but those
    few. Returns ``(o (B, L, H * dh), {stream: its new tail})``."""
    mask_c, mask_t = masks
    b, n, h, dh = q.shape
    # The rows behind the pages: the tail's, the block's own, and dead rows
    # up to a whole number of lanes' worth (128), so that the joined
    # scores' last dimension tiles (at 1,280 + 64 + 4 columns XLA's cost
    # model gave up on the softmax's fusions: PERF.md section 6, PR 54).
    pad = -(mask_t.shape[1] + n) % 128
    behind = jnp.concatenate(
        [mask_t, jnp.ones((b, n), bool), jnp.zeros((b, pad), bool)], axis=1)
    rows, new = {}, {}
    for (name, spec), fresh in zip(layer_streams, (k, v)):
        tail = state[f"tail_{name}"][layer]
        new[name] = paged_kv.append_tail_block(
            tail, state["tail_len"], fresh, store)
        rows[name] = (
            paged_kv.gather_dequant_pages(
                state["pools"][layer][name], state["page_table"], spec, dt,
                live=live, unpack="bytes"),
            jnp.concatenate(
                [tail.astype(dt), fresh.reshape(b, n, -1).astype(dt),
                 jnp.zeros((b, pad, tail.shape[-1]), dt)], axis=1),
        )
    o = decode_attention(
        q.transpose(0, 2, 1, 3).reshape(b, h * n, dh),
        rows["k"][0], rows["v"][0], rows["k"][1], rows["v"][1],
        mask=mask_c, tail_mask=behind, score_divisor=score_divisor,
    )
    o = o.reshape(b, h, n, dh).transpose(0, 2, 1, 3)
    return o.reshape(b, n, h * dh), new


def unmask_block(conf, known, step, steps: int, threshold: float):
    """The unmask rule of block diffusion, one function for both published
    schedules. ``conf (B, L)`` float32, the confidence of the greedy token
    at every position; ``known (B, L) bool``; ``step (B,)``, the denoising
    steps the block has had; ``steps = T`` and ``threshold = tau`` the
    adapter's. The schedule's share of step ``s`` is ``n_s = L // T + (s < L
    % T)``, no more than are masked. ``low_confidence_dynamic``: where at
    least ``n_s`` masked positions have ``conf > tau`` all of those are
    unmasked, else the ``n_s`` most confident masked ones;
    ``low_confidence_static`` is the same rule at ``tau >= 1``, which no
    confidence passes. Among equal confidences the earlier position goes
    first (the published ``topk`` leaves that open). Returns ``(unmask (B,
    L) bool, early (B,) bool``: more than ``n_s`` unmasked). Works on
    ``jax`` and ``numpy`` arrays alike."""
    conf = jnp.asarray(conf, jnp.float32)
    masked = ~jnp.asarray(known)
    n = conf.shape[-1]
    step = jnp.asarray(step)
    share = jnp.minimum(n // steps + (step < n % steps),
                        jnp.sum(masked, axis=-1))
    # A masked position's rank among the masked, most confident first.
    ahead = masked[:, None, :] & (
        (conf[:, None, :] > conf[:, :, None])
        | ((conf[:, None, :] == conf[:, :, None])
           & (jnp.arange(n)[None, :] < jnp.arange(n)[:, None])))
    most = masked & (jnp.sum(ahead, axis=-1) < share[:, None])
    confident = masked & (conf > threshold)
    enough = jnp.sum(confident, axis=-1) >= share
    unmask = jnp.where(enough[:, None], confident, most)
    return unmask, jnp.sum(unmask, axis=-1) > share


class Adapter:
    """The protocol between a model and the serving plane, for one ``(model
    config, params)`` pair. What the scheduler and its programs ask of an
    adapter, and what this class answers for every model:

    ``kind``
        a name for the program key (``"gpt2"``, ``"mla_moe"``); the
        subclass's.
    ``geometry``
        hashable model geometry, for the program key: the config
        dataclass's fields.
    ``n_layer``, ``serve``, ``p``
        ``p`` is the parameter tree, an operand of every program: the
        programs keep an adapter without one (``programs.build``) and take
        the tree through :meth:`with_params` as they trace.
    ``step_counters``
        names under ``cgx.serve.`` of what ``decode_forward`` counts each
        step (empty for a model that counts nothing).
    ``guards_global_read``
        whether ``decode_forward`` hands the read of its global layers the
        lane's committed pages as a guard (:func:`page_live`; the K/V
        adapters through :func:`attend_paged`, the latent ones through
        :func:`layer_cache_rows` for the streams the kernel fetches by
        page): the scheduler then counts what the read decodes beside what
        its table holds (``cgx.serve.kv.decoded_pages.global``,
        ``.table_pages.global``). ``GPT2Server`` alone does not
        (``serving/gpt2.py`` says why).
    ``cache_passes``
        how many times a token goes through the ``n_layer`` layers, each
        pass leaving cache entries of its own that it alone reads: 1 for
        every model but a looped one. Above 1 every layer's pools and
        tails carry a pass dimension (the module's text above), ``state``
        holds ``tail_<stream>[l]`` as ``(cache_passes, B, page_tokens,
        width)``, ``prefill_forward`` returns a layer's payload as
        ``(cache_passes, B, S, n_head, d_head)`` and ``decode_forward`` a
        layer's new tail with the same leading dimension.
    ``block_tokens``
        positions a lane a decode step runs: 1 for every model but one that
        generates by diffusion over blocks (the module's text above, "A
        step is not a token"). Above 1, ``L``: ``state["tokens"]`` is ``(B,
        L)`` beside ``known``, ``unmask_step`` and ``block_step``; the
        adapter also states ``mask_token``, ``denoise_steps`` and
        ``unmask_threshold`` (:func:`unmask_block`'s ``T`` and ``tau``); its
        ``step_counters`` end with :data:`BLOCK_COUNTERS`, which the decode
        program counts; ``decode_forward`` returns logits ``(B, L, V)``,
        position ``i``'s row predicting position ``i``'s token, and writes
        the block's rows into the tails of :func:`block_stores`' lanes
        alone; ``prefill_forward`` is given the prompt's whole blocks alone
        and returns None in the logits' place (there is no first token: the
        ``n % L`` tokens the prefill leaves out open the lane's first block
        as known tokens, ``admit_lane``'s ``token`` operand, ``(L,)`` with
        -1 where a position is masked). ``serve.page_tokens`` is a multiple
        of ``L``.
    ``layer_name(l)``
        the layer's ``kv_page`` edge name.
    ``cache_streams(l)``
        the layer's cache streams, ``((name, PageSpec), ...)``, built with
        :func:`page_specs`; ``()`` for a layer that leaves no pages. The
        programs' stream names are the layers' union, in order of first
        appearance. The subclass's.
    ``state_streams(l)``
        the layer's recurrent state a lane, ``((name, shape, dtype),
        ...)``; ``()`` for a layer (or a model) with none.
    ``page_window(l)``
        the class of the layer's pages: 0, global (the lane's
        ``page_table`` row); ``W``, the layer attends the last ``W``
        positions and keeps its pages as a ring (the lane's ``ring_table``
        row, :func:`ring_pages` slots). All window layers of a model state
        one ``W``.
    ``with_params(p)``
        the adapter over another (traced) parameter tree.
    ``kv_bytes_per_token()``
        float32 bytes a token's cache weighs, all layers.
    ``state_bytes_per_lane()``
        float32 bytes of a lane's state streams, all layers.
    ``prefill_forward(tokens, positions, last_idx)``
        the subclass's. Returns logits ``(B, V)``, then one list per cache
        stream name, of each layer's ``(B, S, n_head, d_head)`` f32 cache
        payload, then one list per state stream name, of each layer's ``(B,
        *shape)`` state after position ``last_idx``; None in a list for a
        layer without that stream.
    ``decode_forward(state, streams)``
        the subclass's. Returns logits ``(B, V)``; ``{stream: [each layer's
        new tail (B, page_tokens, n_head * d_head) f32, rows as the
        attention reads them, this token's written by
        paged_kv.append_tail_rows]}`` and, in the same dictionary, ``{state
        stream: [each layer's new state (B, *shape)]}``, None for a layer
        without it; and an int32 vector of ``step_counters`` or None.
        ``state`` holds ``pools[l][stream]``, ``tail_<stream>[l]`` and
        ``state_<state stream>[l]``, laid out by ``programs.fresh_state``.
    """

    kind: str
    step_counters: Tuple[str, ...] = ()
    guards_global_read: bool = False
    cache_passes: int = 1
    block_tokens: int = 1

    def __init__(self, model_cfg, params,
                 serve: Optional[ServeConfig] = None):
        self.cfg = model_cfg
        self.p = params
        self.serve = serve or ServeConfig.from_env(model_cfg)
        self.n_layer = model_cfg.n_layer
        self.geometry = tuple(
            (f.name, str(getattr(model_cfg, f.name)))
            for f in dataclasses.fields(model_cfg)
        )

    def layer_name(self, layer: int) -> str:
        return f"layer_{layer}"

    def state_streams(self, layer: int):
        return ()

    def page_window(self, layer: int) -> int:
        return 0

    def with_params(self, params):
        return type(self)(self.cfg, params, self.serve)

    def kv_bytes_per_token(self) -> int:
        return self.cfg.kv_bytes_per_token()

    def state_bytes_per_lane(self) -> int:
        return 0
