"""The compiled programs of the serving plane, and the state they hand one
another.

:func:`build` makes, for one adapter (``serving/adapter.py``), the seven
jitted programs the scheduler dispatches, each over the streams the adapter
names; :func:`fresh_state` makes the state they donate to one another. The
decode worker runs ONE compiled step program (``decode_step``): for every
lane of a fixed ``CGX_SERVE_MAX_BATCH``-wide batch, gather the lane's
committed KV pages (``ops/paged_kv.gather_dequant_pages``: ``cfg.dtype`` rows
as the attention reads them, Pallas codec on TPU dispatch), attend the
lane's current token against the pages and, apart, the raw f32 tail block,
and emit the greedy next token. ``commit`` promotes the few tails that filled
into pool pages, by lane index. An admission is two more programs over the
same donated state: ``prefill_pages`` (forward, the prompt's pages into the
pools, its tails and its first token left on the device) and ``admit_lane``
(one lane written in place, the first token an operand it takes from the
device); ``release_lanes`` resets the lanes that finished. ``prefill`` is the
forward alone, for the prefill worker (``serving/prefill.py``), and
``ingest`` writes the pages a page stream brought into the pools.

``pools[l][stream]`` is ``paged_kv.empty_pool(max_pages + 1, spec)``
(``max_batch * ring + 1`` for a window layer): for a quantized stream
``(words (max_pages + 1, *spec.word_shape) int32, meta (max_pages + 1, 2,
num_buckets) f32)``, a page's wire words as rows of 128 and its (unit,
minimum) pairs as two lane-dense planes, the flat decode kernel's own
blocks, so the read fetches a page from the pool by its id and nothing
gathers, reshapes or relays the pool first (on the chip a ``(n, W)`` and a
``(n * W / 128, 128)`` array tile differently, and a ``(n, buckets, 2)``
meta was relaid whole in front of every read: ``ops/paged_kv.py``,
"Layouts"); ``(max_pages + 1, page_tokens, n_head, d_head) f16`` for a raw
one. The last row is scratch: a padded slot of the ``commit`` program and a
prefill's last page that is a tail write there.

An adapter whose layers run ``T = cache_passes`` times a token
(``serving/adapter.py``, "A cache layer is not a weight layer") has ``T``
such blocks of rows in every pool, one after another, pass ``t``'s page
``p`` at row ``t x (max_pages + 1) + p``, and tails ``(T, max_batch,
page_tokens, width)``. A page id, a lane's table row, ``n_pages`` and
``tail_len`` are what they are for any adapter; ``commit``,
``prefill_pages`` and ``admit_lane`` write all ``T`` passes' rows of the ids
they are given, a layer and a stream in one call.

An adapter that runs a block of ``L = block_tokens`` positions a lane a step
(``serving/adapter.py``, "A step is not a token") has ``tokens (max_batch,
L)`` beside ``known``, ``unmask_step`` and ``block_step``; its
``decode_step`` is :func:`build`'s ``decode_block_step``, whose output says
what each lane emitted; its prefill leaves no first token and its
``admit_lane`` takes the lane's first block. With ``block_tokens`` 1 the
state has none of those entries and every program is what it was.

Which of the programs built here a scheduler runs now is the scheduler's
decision (its LRU and the key it is held under); nothing here knows of it.
"""

from __future__ import annotations

import copy
from types import SimpleNamespace
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from .. import config as cfg_mod
from ..ops import paged_kv
from .adapter import (
    BLOCK_COUNTERS,
    ServeConfig,
    block_stores,
    ring_pages,
    unmask_block,
)

# The per-lane bookkeeping of the decode state, and what ``release_lanes``
# resets each entry of a finished or evicted lane to.
_LANE_RESET = {"active": False, "n_pages": 0, "tail_len": 0, "page_table": -1}
# The same of the second table, which a model with window layers keeps.
_RING_RESET = {"ring_table": -1}


def _resolved_streams(server) -> Tuple:
    """Every layer's cache streams ``((name, PageSpec), ...)`` under the
    CURRENT kv_page resolution (:func:`page_specs`), as the adapter states
    them; ``()`` for a layer that leaves no pages."""
    return tuple(
        tuple(server.cache_streams(layer)) for layer in range(server.n_layer)
    )


def _resolved_state_streams(server) -> Tuple:
    """Every layer's state streams as ``((name, (shape, dtype name)),
    ...)``: the cache streams' form, a name and what one lane's row is."""
    return tuple(
        tuple((name, (tuple(shape), jnp.dtype(dtype).name))
              for name, shape, dtype in server.state_streams(layer))
        for layer in range(server.n_layer)
    )


def _resolved_windows(server) -> Tuple[int, ...]:
    """Every layer's page class as the adapter states it
    (``page_window``): 0 for global pages, the window for a ring."""
    return tuple(
        int(server.page_window(layer)) for layer in range(server.n_layer)
    )


def _ring(server, streams, windows) -> int:
    """Slots of a lane's ring (:func:`ring_pages` of the window layers' one
    window), 0 for a model without window layers."""
    found = sorted({w for w in windows if w})
    if not found:
        return 0
    if len(found) > 1:
        raise ValueError(
            f"adapter {server.kind!r} states the windows {found}: the lanes "
            "keep one ring table, so every window layer has the same window"
        )
    if found[0] < server.serve.page_tokens:
        raise ValueError(
            f"window {found[0]} is shorter than a page "
            f"({server.serve.page_tokens} tokens): the tail would outlive it"
        )
    bare = [l for l, w in enumerate(windows) if w and not streams[l]]
    if bare:
        raise ValueError(f"layers {bare} state a window and no cache stream")
    return ring_pages(server.serve, found[0])


def _stream_names(streams) -> Tuple[str, ...]:
    """The names the layers' streams (cache or state) go by: their union,
    in order of first appearance."""
    return tuple(dict.fromkeys(
        name for layer in streams for name, _ in layer
    ))


def _holders(streams) -> Dict[str, Dict[int, int]]:
    """``{stream: {layer: its rank among the layers that have the
    stream}}``: where a layer's entry lies in an array stacked over those
    layers (a prefill's tails and states)."""
    out: Dict[str, Dict[int, int]] = {n: {} for n in _stream_names(streams)}
    for layer, layer_streams in enumerate(streams):
        for name, _ in layer_streams:
            out[name][layer] = len(out[name])
    return out


def _leading_specs(streams) -> Tuple[Optional[paged_kv.PageSpec], ...]:
    """Each layer's leading stream's spec: the layer's wire resolution
    (bits are per layer; GPT-2's ``k`` and ``v`` share the whole spec);
    None for a layer without pages."""
    return tuple(layer[0][1] if layer else None for layer in streams)


def _resolved_specs(server) -> Tuple[paged_kv.PageSpec, ...]:
    return _leading_specs(_resolved_streams(server))



def build(server) -> SimpleNamespace:
    """The seven programs for this adapter's geometry and the current
    ``kv_page`` resolution, jitted, with the stream tables they were built
    over. Every program takes the parameter tree as an operand, so what is
    kept here is the adapter without it: a cached program holds no model."""
    server = copy.copy(server)
    server.p = None
    streams = _resolved_streams(server)
    names = _stream_names(streams)
    state_streams = _resolved_state_streams(server)
    state_names = _stream_names(state_streams)
    both = sorted(set(names) & set(state_names))
    if both:
        raise ValueError(
            f"adapter {server.kind!r} names {both} both a cache stream and "
            "a state stream"
        )
    holders = {**_holders(streams), **_holders(state_streams)}
    n_layer = server.n_layer
    sv = server.serve
    windows = _resolved_windows(server)
    ring = _ring(server, streams, windows)
    passes = int(server.cache_passes)
    if passes > 1 and (ring or state_names):
        raise ValueError(
            f"adapter {server.kind!r} states {passes} cache passes beside "
            f"{'window layers' if ring else f'the state streams {state_names}'}"
            ": a pass dimension is the global page pools' and the tails'"
        )
    block = int(server.block_tokens)
    if block > 1:
        if passes > 1 or ring or state_names:
            raise ValueError(
                f"adapter {server.kind!r} states a block of {block} positions"
                " a step beside cache passes, window layers or state "
                "streams: a block step is the global pages' and the tails'"
            )
        if sv.page_tokens % block:
            raise ValueError(
                f"a page of {sv.page_tokens} tokens is not whole blocks of "
                f"{block}: a block would straddle the tail's end"
            )
        if tuple(server.step_counters[-len(BLOCK_COUNTERS):]) != (
                BLOCK_COUNTERS):
            raise ValueError(
                f"adapter {server.kind!r} states a block and step counters "
                f"that do not end with {BLOCK_COUNTERS}"
            )

    def pass_rows(ids):
        """The pool rows the page ids ``ids (n,)`` name in every pass,
        ``(passes x n,)``, pass-major: ``ids`` themselves for an adapter of
        one pass."""
        if passes == 1:
            return ids
        offsets = (sv.max_pages + 1) * jnp.arange(passes, dtype=ids.dtype)
        return (offsets[:, None] + ids[None, :]).reshape(-1)

    def decode_step(params, state):
        """One token for every lane. Returns the new state and what the
        host reads each tick, in one array: the lanes' next tokens, then
        the adapter's ``step_counters`` (none for GPT-2)."""
        srv = server.with_params(params)
        logits, new_tails, counts = srv.decode_forward(state, streams)
        nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        out = dict(state)
        for name in names:
            out[f"tail_{name}"] = tuple(new_tails[name])
        for name in state_names:  # rewritten whole, every lane, every step
            out[f"state_{name}"] = tuple(new_tails[name])
        out["tail_len"] = jnp.where(
            state["active"], state["tail_len"] + 1, state["tail_len"]
        )
        out["pos"] = jnp.where(state["active"], state["pos"] + 1,
                               state["pos"])
        out["tokens"] = jnp.where(state["active"], nxt, state["tokens"])
        if counts is not None:
            nxt = jnp.concatenate([nxt, counts.astype(jnp.int32)])
        return out, nxt

    def decode_block_step(params, state):
        """One forward of the open block of every lane (``block_tokens`` >
        1). A lane whose block is all known (``adapter.block_stores``)
        STORES: the forward's K and V went to its tails, ``pos`` and
        ``tail_len`` advance by ``L``, the block's tokens are emitted and
        the next block opens all masked. Every other active lane DENOISES:
        greedy token and confidence (the softmax's largest, float32) at
        every position, some masked positions unmasked
        (``adapter.unmask_block``), nothing stored. Returns the new state
        and what the host reads each tick, in one int32 array
        (:func:`split_block_step` takes it apart): the blocks
        as this step was given them ``(B x L)`` (a storing lane's are the
        block it stored), the tokens each lane emitted ``(B,)`` (0, or the
        stored block's positions that were generated: ``L`` but in a first
        block, which opens with the prompt's remainder), each position's
        unmask step ``(B x L)`` (-1 for a token of the prompt), whether the lane
        stores at the NEXT step ``(B,)`` (its block is all known now: the
        host's counts of tails and of tokens left follow a step early),
        then the adapter's ``step_counters``, ``adapter.BLOCK_COUNTERS``
        last."""
        srv = server.with_params(params)
        store = block_stores(state)
        logits, new_tails, counts = srv.decode_forward(state, streams)
        active, known = state["active"], state["known"]
        denoise = active & ~store
        best = jnp.max(logits, axis=-1)
        conf = jnp.exp(best - jax.nn.logsumexp(logits, axis=-1))
        unmask, early = unmask_block(
            conf, known, state["block_step"], srv.denoise_steps,
            srv.unmask_threshold)
        unmask &= denoise[:, None]
        tokens = jnp.where(
            unmask, jnp.argmax(logits, axis=-1).astype(jnp.int32),
            state["tokens"])
        known = known | unmask
        out = dict(state)
        for name in names:
            out[f"tail_{name}"] = tuple(new_tails[name])
        stored = store[:, None]
        out["tokens"] = jnp.where(stored, jnp.int32(srv.mask_token), tokens)
        out["known"] = known & ~stored
        out["unmask_step"] = jnp.where(
            stored, 0,
            jnp.where(unmask, state["block_step"][:, None],
                      state["unmask_step"]))
        out["block_step"] = jnp.where(
            store, 0, state["block_step"] + denoise.astype(jnp.int32))
        grown = block * store.astype(jnp.int32)
        out["tail_len"] = state["tail_len"] + grown
        out["pos"] = state["pos"] + grown
        block_counts = jnp.stack([
            jnp.sum(active), jnp.sum(store), jnp.sum(unmask),
            jnp.sum(early & denoise)]).astype(jnp.int32)
        emitted = jnp.sum(state["unmask_step"] >= 0, axis=-1) * store
        return out, jnp.concatenate([
            state["tokens"].reshape(-1), emitted.astype(jnp.int32),
            state["unmask_step"].reshape(-1),
            (denoise & jnp.all(known, axis=-1)).astype(jnp.int32),
            *(() if counts is None else (counts.astype(jnp.int32),)),
            block_counts,
        ])

    def commit(state, lanes, page_ids, ring_ids=None):
        """Promote the full tails of ``lanes (K,)`` into pool pages
        ``page_ids (K,)`` (a window layer's into ``ring_ids (K,)``, rows of
        its own pools: the lane's ring slot ``n_pages % ring``, whose last
        page has slid out of the window), ``K = ServeConfig.commit_lanes``: the K lanes'
        tails alone are gathered (rows as they are kept, flattened to ``(K,
        page_tokens * width)`` payloads), quantized and scattered, a layer
        and a stream at a time (every pass's tail of a lane in the one
        call, into the pass's rows of the pool), and their ``page_table``
        slot, ``n_pages`` and ``tail_len`` written by scatter. Tails fill at
        ``max_batch / page_tokens`` a step, so a program over every lane's
        tail would throw nearly all of its work away. A slot the
        caller has no tail for names any valid lane and the scratch row
        (``max_pages``; pools carry ``max_pages + 1`` rows): its rows land
        there and its lane's counts are left as they are, so one program
        of one width serves any number of full tails."""
        k = lanes.shape[0]
        out = dict(state)
        out["pools"] = tuple(
            {
                name: paged_kv.commit_page_rows(
                    state["pools"][layer][name],
                    ring_ids if windows[layer] else pass_rows(page_ids),
                    state[f"tail_{name}"][layer][lanes].reshape(k, -1)
                    if passes == 1 else
                    state[f"tail_{name}"][layer][:, lanes].reshape(
                        passes * k, -1),
                    spec,
                )
                for name, spec in streams[layer]
            }
            for layer in range(n_layer)
        )
        # Out of bounds for a padded slot: the scatters below drop it.
        at = jnp.where(page_ids < sv.max_pages, lanes, sv.max_batch)
        out["page_table"] = state["page_table"].at[
            at, state["n_pages"][lanes]
        ].set(page_ids, mode="drop")
        if ring:
            out["ring_table"] = state["ring_table"].at[
                at, state["n_pages"][lanes] % ring
            ].set(ring_ids, mode="drop")
        out["n_pages"] = state["n_pages"].at[at].add(1, mode="drop")
        out["tail_len"] = state["tail_len"].at[at].set(0, mode="drop")
        return out

    def ingest(pools, layer_rows, ids):
        """Batch-write received/locally-prefetched page payload rows
        (n, flat) into pool rows ``ids (n,)`` for every layer and stream
        (``layer_rows[layer][stream]``) — the stream-completion path
        (payloads already in pool layout when quantized)."""
        return tuple(
            {
                name: _ingest_pool(
                    pools[layer][name], ids, layer_rows[layer][name], spec
                )
                for name, spec in streams[layer]
            }
            for layer in range(n_layer)
        )

    def prefill(params, tokens, positions, last_idx):
        """Forward alone, every layer's cache payload (and state) out by
        stream: the prefill worker's program (``serving/prefill.py`` ships
        the pages itself)."""
        srv = server.with_params(params)
        logits, *payloads = srv.prefill_forward(tokens, positions, last_idx)
        if block > 1:
            # No first token (the prompt's remainder opens the lane's first
            # block): in its place the count of the non-finite values the
            # last layer cached at ``last_idx``, 0 of a sound prefill, which
            # the host reads as the prefill's end.
            last = jax.lax.dynamic_index_in_dim(
                [x for x in payloads[0] if x is not None][-1], last_idx, 1)
            first = jnp.sum(~jnp.isfinite(last), axis=(1, 2, 3))
        else:
            first = jnp.argmax(logits, axis=-1)
        return (
            first.astype(jnp.int32),
            dict(zip(names + state_names, payloads)),
        )

    observe_qerr = cfg_mod.qerr_stats()  # in the program key's fingerprint

    def prefill_pages(params, pools, tokens, positions, last_idx, ids,
                      tail_len, ring_ids=None):
        """The local prefill of one padded prompt, whole: forward, then
        every page of every layer's streams through ``commit_page_rows``
        into the donated pools at ``ids (padded pages,)``, and the last
        page's first ``tail_len`` rows as the lane's tails ``{stream: (L,
        page_tokens, H * Dh) f32}``, zero from ``tail_len`` on. A last page
        that is a tail has the scratch row for its id, so one program
        serves every prompt length under a padded length, whole pages or
        not. The first token is a scalar, ``admit_lane``'s operand as it
        is. Also ``{layer: its leading stream's rows as quantized}`` of the
        quantized layers, empty unless ``CGX_QERR_STATS`` was on when the
        programs were built. Last, the lane's recurrent state after
        ``last_idx`` as the adapter's prefill left it, ``{state stream: (its
        layers, *shape)}``, empty for a model without state streams. Tails
        and states are stacked over the layers that have the stream. A
        window layer writes the prompt's last ``ring_ids.shape[0]`` padded
        pages alone (at most ``ring + 1``: the pages its ring keeps are
        among them), into ``ring_ids``; the host names the scratch row for
        those that have slid out already. An adapter of several passes
        hands a layer's payload as ``(passes, 1, padded tokens, H, Dh)``:
        every pass's pages go into that pass's rows of ``ids`` in the one
        call, and the tails come out ``(L, passes, page_tokens, H * Dh)``."""
        first, payloads = prefill(params, tokens, positions, last_idx)
        n_pages = ids.shape[0]
        live = jax.lax.broadcasted_iota(
            jnp.int32, (sv.page_tokens, 1), 0
        ) < tail_len
        out, tails, qerr_rows = [], {name: [] for name in names}, {}
        for layer in range(n_layer):
            pool, written = pools[layer], {}
            for name, spec in streams[layer]:
                # (padded tokens, H, Dh); (passes, padded tokens, H, Dh)
                x = (payloads[name][layer][0] if passes == 1
                     else payloads[name][layer][:, 0])
                rows = x.reshape(passes * n_pages, -1)
                written[name] = paged_kv.commit_page_rows(
                    pool[name],
                    *((ring_ids, rows[-ring_ids.shape[0]:])
                      if windows[layer] else (pass_rows(ids), rows)), spec,
                )
                tails[name].append(jnp.where(
                    live,
                    x[-sv.page_tokens:].reshape(sv.page_tokens, -1)
                    if passes == 1 else
                    x[:, -sv.page_tokens:].reshape(
                        passes, sv.page_tokens, -1),
                    0.0,
                ))
                if (observe_qerr and spec.quantized
                        and name == streams[layer][0][0]):
                    qerr_rows[layer] = rows
            out.append(written)
        states = {
            name: jnp.stack([payloads[name][layer][0]
                             for layer in holders[name]])
            for name in state_names
        }
        return (
            first[0], tuple(out),
            {name: jnp.stack(t) for name, t in tails.items()}, qerr_rows,
            states,
        )

    def admit_lane(state, lane, table_row, n_pages, tail_len, token, pos,
                   tails, states, ring_row=None):
        """Write one ready request into lane ``lane`` of the donated
        state: its page-table row, counts, first token (a scalar still on
        the device from the local prefill, or a host one from a page
        stream) and position, its stacked tails ``{stream: (L, page_tokens,
        H * Dh)}``, device or host arrays alike, and its recurrent state
        ``{state stream: (L, *shape)}`` (whatever the lane's last request
        left there is overwritten whole). ``ring_row (ring,)``: the lane's
        row of ``ring_table``, where a layer has a window. An adapter that
        runs a block a step has no first token: ``token (L,)`` is the
        lane's first block, the prompt's tokens past its last whole block
        and -1 at the positions still masked."""
        out = dict(state)
        opens = (("tokens", token),) if block == 1 else (
            ("tokens", jnp.where(token >= 0, token, server.mask_token)),
            ("known", token >= 0),
            ("unmask_step", jnp.where(token >= 0, -1, 0)),
            ("block_step", 0),
        )
        for name, value in (
            ("page_table", table_row), ("n_pages", n_pages),
            ("tail_len", tail_len), *opens, ("pos", pos),
            ("active", True),
        ) + ((("ring_table", ring_row),) if ring else ()):
            out[name] = state[name].at[lane].set(value)
        for prefix, which, written in (("tail", names, tails),
                                       ("state", state_names, states)):
            for name in which:
                out[f"{prefix}_{name}"] = tuple(
                    None if t is None
                    else (t.at[lane] if passes == 1 else t.at[:, lane]).set(
                        written[name][holders[name][layer]])
                    for layer, t in enumerate(state[f"{prefix}_{name}"])
                )
        return out

    def release_lanes(lanes, mask):
        """Reset the lane bookkeeping (``_LANE_RESET``) of the lanes in
        ``mask (B,) bool``: finished or evicted, once a tick."""
        reset = {**_LANE_RESET, **_RING_RESET}
        return {
            name: jnp.where(
                mask.reshape((-1,) + (1,) * (value.ndim - 1)),
                reset[name], value,
            )
            for name, value in lanes.items()
        }

    return SimpleNamespace(
        streams=streams,
        names=names,
        windows=windows,
        window=max(windows),
        ring=ring,
        passes=passes,
        block=block,
        # Cache streams over the layers (and passes) of each class: (global,
        # window).
        class_streams=tuple(
            passes * sum(len(layer) for layer, w in zip(streams, windows)
                         if bool(w) == ringed)
            for ringed in (False, True)
        ),
        state_streams=state_streams,
        state_names=state_names,
        specs=_leading_specs(streams),
        decode_step=jax.jit(decode_step if block == 1 else decode_block_step,
                            donate_argnums=(1,)),
        commit=jax.jit(commit, donate_argnums=(0,)),
        ingest=jax.jit(ingest, donate_argnums=(0,)),
        prefill=jax.jit(prefill),
        prefill_pages=jax.jit(prefill_pages, donate_argnums=(1,)),
        admit_lane=jax.jit(admit_lane, donate_argnums=(0,)),
        release_lanes=jax.jit(release_lanes, donate_argnums=(0,)),
    )


def split_block_step(out, lanes: int, block: int):
    """What the host read of a block step (``build``'s
    ``decode_block_step``), apart: ``(tokens (B, L), emitted (B,),
    unmask_step (B, L), stores next (B,), the step counters)``."""
    tokens, out = out[: lanes * block], out[lanes * block:]
    emitted, out = out[:lanes], out[lanes:]
    unmask, out = out[: lanes * block], out[lanes * block:]
    return (tokens.reshape(lanes, block), emitted,
            unmask.reshape(lanes, block), out[:lanes], out[lanes:])


def fresh_state(prog: SimpleNamespace, serve: ServeConfig) -> Dict:
    """The state the programs of ``prog`` (:func:`build`) donate to one
    another, empty: every layer's pools, every lane's tails, recurrent
    state and bookkeeping."""
    streams = prog.streams
    b = serve.max_batch
    ring = prog.ring
    passes = prog.passes
    pools = tuple(
        {
            # +1 row: scratch, where a padded slot of commit() and a
            # prefill's last page that is a tail write; never read. A
            # window layer holds a ring a lane, whatever ``max_seq``. An
            # adapter of several passes holds as many such blocks of rows.
            name: paged_kv.empty_pool(
                passes * ((b * ring if window else serve.max_pages) + 1),
                spec)
            for name, spec in layer
        }
        for layer, window in zip(streams, prog.windows)
    )
    # A layer without the stream holds None in the stream's tuple, so
    # that every per-layer entry is found at its layer's index. A tail
    # is kept as the rows the attention contracts and the commit
    # quantizes, a position's heads side by side: no program relays it.
    tails = {
        f"tail_{name}": tuple(
            None if spec is None else jnp.zeros(
                ((passes,) if passes > 1 else ())
                + (b, spec.page_tokens, spec.n_head * spec.d_head),
                jnp.float32,
            )
            for spec in (dict(layer).get(name) for layer in streams)
        )
        for name in prog.names
    }
    # The recurrent state, one row a lane: zeros until an admission
    # writes the lane (a free lane's rows go through every decode step
    # like any other's and reach no other lane).
    states = {
        f"state_{name}": tuple(
            None if row is None else jnp.zeros((b,) + row[0], row[1])
            for row in (dict(layer).get(name)
                        for layer in prog.state_streams)
        )
        for name in prog.state_names
    }
    return {
        "pools": pools,
        **tails,
        **states,
        "page_table": jnp.full((b, serve.pages_per_seq), -1, jnp.int32),
        "n_pages": jnp.zeros((b,), jnp.int32),
        "tail_len": jnp.zeros((b,), jnp.int32),
        "tokens": jnp.zeros((b,) if prog.block == 1 else (b, prog.block),
                            jnp.int32),
        "pos": jnp.zeros((b,), jnp.int32),
        "active": jnp.zeros((b,), bool),
        **({"ring_table": jnp.full((b, ring), -1, jnp.int32)}
           if ring else {}),
        # The open block of a lane whose step runs a block of positions.
        **({"known": jnp.zeros((b, prog.block), bool),
            "unmask_step": jnp.zeros((b, prog.block), jnp.int32),
            "block_step": jnp.zeros((b,), jnp.int32)}
           if prog.block > 1 else {}),
    }


def _ingest_pool(pool, ids, rows, spec: paged_kv.PageSpec):
    """Scatter pre-encoded pool rows: quantized rows arrive as (words,
    meta) pairs in pool-row form (the transport's wire bytes ARE the
    pool's, ``paged_kv.pool_words``; its meta pairs as the pool's two
    planes, ``paged_kv.pool_meta``), raw rows as f32 payloads."""
    if not spec.quantized:
        pages = rows.reshape(
            -1, spec.page_tokens, spec.n_head, spec.d_head
        ).astype(jnp.float16)
        return pool.at[ids].set(pages)
    words, meta = pool
    rows_words, rows_meta = rows
    return (
        words.at[ids].set(rows_words),
        meta.at[ids].set(rows_meta),
    )

