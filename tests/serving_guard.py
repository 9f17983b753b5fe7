"""What the adapters' tests of the global read's guard share
(``adapter.page_live`` through ``attend_paged``): a batch served with every
decode step's logits read twice on the state the step is given, by the
adapter's ``decode_forward`` as it stands and by one traced with the guard
taken off."""

from __future__ import annotations

import hashlib
from types import SimpleNamespace

import jax
import numpy as np
import pytest

from torch_cgx_tpu.serving import adapter
from torch_cgx_tpu.serving.scheduler import ContinuousBatchScheduler, Request
from torch_cgx_tpu.utils.logging import metrics

GLOBAL_COUNTERS = tuple(f"cgx.serve.kv.{name}.global" for name in
                        ("decoded_pages", "live_pages", "table_pages"))


def decode_step_sha(server) -> str:
    """sha256 (its head) of the text of ``server``'s ``decode_step`` jaxpr
    over a fresh scheduler's state: what a pin of an adapter that takes no
    guard compares with the same on the parent commit's ``git archive``."""
    sched = ContinuousBatchScheduler(server)
    text = str(jax.make_jaxpr(sched._prog.decode_step)(server.p,
                                                       sched._state))
    return hashlib.sha256(text.encode()).hexdigest()[:16]


def _serve_with(sched, decode_step, requests) -> None:
    """Run ``requests`` (``(prompt, gen)`` pairs, submitted at once) to the
    end with ``decode_step`` in the place of the program's."""
    sched._prog = SimpleNamespace(**{**vars(sched._prog),
                                     "decode_step": decode_step})
    for i, (prompt, gen) in enumerate(requests):
        sched.submit(Request(id=f"r{i}", tokens=prompt, max_new_tokens=gen))
    assert sched.run(deadline_s=600.0)


def steps_with_and_without_the_guard(server, module, requests,
                                     guard="page_live"):
    """Serve ``requests`` (``(prompt, gen)`` pairs, submitted at once)
    through one scheduler over ``server``. Returns one ``(held (B,) bool,
    n_pages (B,), guarded logits (B, V), bare logits (B, V))`` a decode
    step; "bare" is traced while ``module.<guard>`` returns None, which is
    the read of every slot of every lane's table. The two traces differ."""
    sched = ContinuousBatchScheduler(server)
    prog = sched._prog

    def forward():  # a function of its own a trace: jit caches by function
        return lambda p, st: server.with_params(p).decode_forward(
            st, prog.streams)[0]

    probes, seen = {}, []

    def decode_step(p, state):
        if not probes:
            probes["guarded"] = jax.jit(forward())
            text = str(jax.make_jaxpr(forward())(p, state))
            with pytest.MonkeyPatch.context() as m:
                m.setattr(module, guard, lambda *a: None)
                probes["bare"] = jax.jit(forward())
                probes["bare"](p, state)  # traced while the guard is off
                assert str(jax.make_jaxpr(forward())(p, state)) != text
        seen.append(([r is not None for r in sched._lanes],
                     np.asarray(state["n_pages"]),
                     np.asarray(probes["guarded"](p, state)),
                     np.asarray(probes["bare"](p, state))))
        return prog.decode_step(p, state)

    _serve_with(sched, decode_step, requests)
    return seen


def assert_held_lanes_bit_for_bit(seen, pages_per_seq):
    """Every step's logits are finite on every lane either way and equal,
    bit for bit, on every held lane; among the steps are some whose batch
    holds a vacated lane beside a lane with part of its table live. Returns
    the share of the table the guard left open, over the run."""
    mixed = live = 0
    for held, n_pages, guarded, bare in seen:
        assert np.isfinite(guarded).all() and np.isfinite(bare).all()
        np.testing.assert_array_equal(guarded[held], bare[held])
        held = np.asarray(held)
        mixed += bool((~held).any()
                      and (0 < n_pages[held]).any()
                      and (n_pages[held] < pages_per_seq).any())
        live += int(n_pages[held].sum())
    assert mixed >= 5
    return live / (len(seen) * len(seen[0][0]) * pages_per_seq)


def device_and_host_pages(server, requests):
    """Serve ``requests`` and return, a dispatched decode step, ``(the
    device's ``page_live`` summed over the state the step is given, what the
    host added to each of :data:`GLOBAL_COUNTERS` for it)``. The mask is a
    held lane's first ``n_pages`` slots and none of a vacated lane's."""
    sched = ContinuousBatchScheduler(server)
    prog, note = sched._prog, sched._note_live_pages
    device, host = [], []

    def decode_step(p, state):
        live = np.asarray(adapter.page_live(server.serve, state))
        held = np.asarray([r is not None for r in sched._lanes])
        assert not live[~held].any()  # a vacated lane's row is dead whole
        assert (live.sum(-1) == np.asarray(state["n_pages"])).all()
        device.append(float(live.sum()))
        return prog.decode_step(p, state)

    def counted(held):
        before = [metrics.get(name) for name in GLOBAL_COUNTERS]
        note(held)
        host.append([metrics.get(name) - b
                     for name, b in zip(GLOBAL_COUNTERS, before)])

    sched._note_live_pages = counted
    _serve_with(sched, decode_step, requests)
    return device, host
