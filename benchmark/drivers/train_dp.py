"""Data-parallel training: one compiled step over every chip of the cell.

Drives the program's ``make_train_step`` on ``flat_mesh()`` with the
configuration's gradient width in the environment and every other ``CGX_*``
unset. Weights and ``n_batches`` token batches are made on the device from
the seed; the batches are cycled.

* set-up: the plain reference first (it needs the chips to itself; its time
  is not set-up's), then ONE step object with its state, driven from the
  seed through its first steps by the window's own call and feed; the
  reference's numbers are compared with what those steps produced; a few
  more steps;
* window: that same object, steps dispatched back to back, the host
  blocking on the loss every ``sync_every`` steps; ``train_step_ms`` is the
  window's time over its steps;
* traced run: ``trace_steps`` steps under the profiler and no second
  window. (The fabric-off program that ``fabric_overhead_ms`` needed made
  the cold traced run too long and is not built: PERF.md.)
* control run (``benchmark/control.py``): the reference stands in the
  program's place, its gradients rounded to the control's width, and goes
  through the same comparisons; the program is not built and nothing is
  timed.
"""

from __future__ import annotations

import time

import numpy as np

from benchmark import reference, weights


def run(ctx) -> dict:
    import jax
    import jax.numpy as jnp
    import optax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from torch_cgx_tpu.models import GPT2, GPT2Config, lm_loss
    from torch_cgx_tpu.parallel import flat_mesh, make_train_step

    from benchmark import trace_reduce

    cfg, tr, mix = ctx.config, ctx.config["train"], ctx.traffic
    n_dev = len(ctx.device_ids)
    mesh = flat_mesh(jax.devices()[:n_dev])
    replicated = NamedSharding(mesh, P())
    rows, seq = tr["rows_per_chip"] * n_dev, tr["seq"]
    lr, n_ref = tr["learning_rate"], tr["reference_steps"]

    with ctx.phase("weights+batches"):
        params0 = weights.make_params(cfg, ctx.seed, sharding=replicated)
        batches = weights.make_token_batches(
            mix["n_batches"], rows, seq, cfg["vocab_size"], ctx.seed,
            sharding=NamedSharding(mesh, P("dp")),
        )
        jax.block_until_ready((params0, batches))

    with ctx.phase("reference", excluded=True):
        per = tr["reference_block_rows_per_chip"]
        ref_args = dict(cfg=cfg, lr=lr, n_steps=n_ref,
                        n_blocks=tr["rows_per_chip"] // per, row_groups=n_dev)
        ref_fn = reference.make_train_reference(
            **ref_args,
            block_sharding=NamedSharding(mesh, P(None, "dp", None)),
        )
        ref = jax.device_get(ref_fn(params0, tuple(batches[:n_ref])))
        del ref_fn
    names = reference.leaf_names(params0)
    if ctx.control:
        return control(ctx, params0, batches[:n_ref], ref, names, ref_args)

    # -- the one step object and its state ---------------------------------
    model = GPT2(GPT2Config(
        vocab_size=cfg["vocab_size"], n_layer=cfg["n_layer"],
        n_head=cfg["n_head"], d_model=cfg["n_embd"],
        max_seq=cfg["n_positions"],
    ))
    opt = optax.adam(lr)

    def loss_fn(p, b):
        return lm_loss(model.apply({"params": p}, b), b)

    def fresh_state():
        p = weights.make_params(cfg, ctx.seed, sharding=replicated)
        return [p, jax.jit(opt.init, out_shardings=replicated)(p)]

    with ctx.phase("build-step"):
        step = make_train_step(loss_fn, opt, mesh)
        state = fresh_state()
        counter = [0]

    def call(step_fn, st):
        """The window's own call and feed: the next batch of the cycle."""
        i = counter[0]
        counter[0] += 1
        st[0], st[1], loss = step_fn(
            st[0], st[1], batches[i % len(batches)], jnp.int32(i))
        return loss

    norms = jax.jit(reference.leaf_norms)
    delta_norms = jax.jit(lambda a, b: reference.leaf_norms(
        jax.tree.map(jnp.subtract, a, b)))

    with ctx.phase("first-steps"):
        losses, mu_norm = [], None
        for i in range(n_ref):
            losses.append(call(step, state))
            if i == 0:
                mu_norm = norms(state[1][0].mu)
        got_delta = np.asarray(delta_norms(state[0], params0))
        got_loss = np.asarray(jnp.stack(losses), np.float64)
        got_grad = np.asarray(mu_norm, np.float64) / (1 - reference.ADAM_B1)
        differing = replicas_differing(state[0], mesh) if n_dev > 1 else 0

    compare(ctx, names, ref, got_loss, got_grad, got_delta, differing)
    del params0

    with ctx.phase("warm-steps"):
        for _ in range(tr["warm_steps"]):
            loss = call(step, state)
        float(loss)

    # -- the window ----------------------------------------------------------
    found = {"loop": {}}
    sync = mix["sync_every"]
    all_losses, group_ms = [], []

    def timed(step_fn, st, seconds=None, steps=None):
        """Groups of ``sync`` steps until ``seconds`` pass or ``steps`` are
        done; returns (elapsed s, steps)."""
        start, done = time.monotonic(), 0
        while True:
            t = time.monotonic()
            for _ in range(sync):
                with jax.profiler.TraceAnnotation("bench.dispatch"):
                    loss = call(step_fn, st)
                all_losses.append(loss)
            with jax.profiler.TraceAnnotation("bench.block"):
                float(loss)
            now = time.monotonic()
            done += sync
            group_ms.append((now - t) / sync * 1e3)
            if (seconds is not None and now - start >= seconds) or (
                    steps is not None and done >= steps):
                return now - start, done

    ctx.open_window()
    if ctx.trace:
        ctx.start_trace()
        with jax.profiler.TraceAnnotation(trace_reduce.WINDOW_SPAN):
            _, traced = timed(step, state, steps=mix["trace_steps"])
        jax.profiler.stop_trace()
        found["loop"]["traced_steps"] = steps = traced
    else:
        elapsed, steps = timed(step, state, seconds=ctx.seconds)
    ctx.close_window()
    ctx.read_memory_peak()

    finite = np.isfinite(np.asarray(jnp.stack(all_losses)))
    found["attempted"] = steps
    found["failed"] = int(steps - finite.sum())
    if not ctx.trace:
        step_ms = elapsed / steps * 1e3
        n_params = sum(x.size for x in jax.tree.leaves(state[0]))
        tokens_s = rows * seq / (step_ms / 1e3)
        ctx.log(f"window {elapsed:.3f} s: {steps} steps, {step_ms:.3f} "
                f"ms/step (median of groups {np.median(group_ms):.3f}, max "
                f"{np.max(group_ms):.3f}); {tokens_s:.0f} tokens/s; "
                f"6 N tokens/s = {6 * n_params * tokens_s / 1e12:.2f} "
                f"TFLOP/s over {n_dev} chip(s); last loss "
                f"{float(all_losses[-1]):.4f}")
        found["end_to_end"] = {"train_step_ms": step_ms}
    if ctx.trace:
        ctx.read_trace(found)
    return found


def compare(ctx, names, ref, got_loss, got_grad, got_delta, differing):
    """Every number of the first steps beside its limit."""
    limits = ctx.config["limits"]
    loss_gap = float(np.max(np.abs(got_loss - ref["loss"]) / ref["loss"]))
    grad_gap, gi = reference.worst_leaf_gap(got_grad, ref["grad_norm"])
    delta_gap, di = reference.worst_leaf_gap(got_delta, ref["delta_norm"])
    ctx.log(f"first steps: loss {got_loss.tolist()} vs reference "
            f"{ref['loss'].tolist()}; worst gradient-norm leaf {names[gi]} "
            f"({got_grad[gi]:.6g} vs {ref['grad_norm'][gi]:.6g}); worst "
            f"change-norm leaf {names[di]} ({got_delta[di]:.6g} vs "
            f"{ref['delta_norm'][di]:.6g})")
    ctx.compare("loss_rel_gap", loss_gap, limits["loss_rel_gap"])
    ctx.compare("grad_norm_gap", grad_gap, limits["grad_norm_gap"])
    ctx.compare("delta_norm_gap", delta_gap, limits["delta_norm_gap"])
    ctx.compare("replica_leaves_differing", differing,
                limits["replica_leaves_differing"])


def control(ctx, params0, batches, ref, names, ref_args) -> dict:
    """The reference in the program's place, at the control's gradient
    width (``precision.gradient_bits`` of the configuration's ``control``
    block), through the same comparisons."""
    import jax

    precision = ctx.config["precision"]
    with ctx.phase("control"):
        low = jax.device_get(reference.make_train_reference(
            **ref_args, gradient_bits=precision["gradient_bits"],
            gradient_bucket=precision["gradient_bucket"],
        )(params0, tuple(batches)))
    compare(ctx, names, ref, np.asarray(low["loss"], np.float64),
            np.asarray(low["grad_norm"], np.float64),
            np.asarray(low["delta_norm"], np.float64), 0)
    ctx.open_window()
    ctx.close_window()
    ctx.read_memory_peak()
    return {"attempted": 0, "failed": 0}


def replicas_differing(tree, mesh) -> int:
    """How many leaves are not bit-identical on every chip."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    def count(t):
        flags = []
        for x in jax.tree.leaves(t):
            bits = jax.lax.bitcast_convert_type(x, jnp.int32)
            flags.append(jnp.any(
                jax.lax.pmax(bits, "dp") != jax.lax.pmin(bits, "dp")))
        return jnp.sum(jnp.stack(flags).astype(jnp.int32))

    fn = jax.jit(jax.shard_map(count, mesh=mesh, in_specs=P(), out_specs=P(),
                               check_vma=False))
    return int(fn(tree))
