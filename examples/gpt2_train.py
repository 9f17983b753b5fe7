"""GPT-2 pretraining with compressed data parallelism — the flagship
composition example (the reference ships only a CIFAR DDP script,
/root/reference/examples/cifar_train.py; SURVEY.md §2.3 lists TP/PP/SP as
absent there).

One mesh, every axis optional:

* ``--dp N``      data parallelism with 1-8 bit quantized gradient allreduce
* ``--cross M``   hierarchical DP: cross x intra axes (DCN x ICI on real
                  pods), INTRA_BROADCAST leader scheme per config
* ``--tp N``      Megatron-style tensor parallelism (GSPMD inserts the
                  collectives from models.gpt2.tp_param_spec)
* ``--sp N``      ring-attention sequence parallelism for long context

Runs on anything: a v5e pod slice, a single chip, or the virtual CPU mesh
(JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8).
Synthetic next-token data keeps it hermetic; loss printed per step.

    python examples/gpt2_train.py --dp 4 --tp 2 --bits 4 --steps 20
"""

from __future__ import annotations

import argparse
import json
import os
import sys

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _REPO)


def parse_args():
    p = argparse.ArgumentParser(description="GPT-2 compressed-DP training")
    p.add_argument("--dp", type=int, default=0, help="data-parallel ways (0 = all devices)")
    p.add_argument("--cross", type=int, default=1, help="split dp into cross x intra")
    p.add_argument("--tp", type=int, default=1, help="tensor-parallel ways")
    p.add_argument("--sp", type=int, default=1, help="sequence-parallel ways (ring attention)")
    p.add_argument("--bits", type=int, default=4)
    p.add_argument("--bucket-size", type=int, default=512)
    p.add_argument("--stochastic", action="store_true", help="QSGD stochastic rounding")
    p.add_argument("--error-feedback", action="store_true",
                   help="accumulate per-device wire-quantization residuals")
    def _rank(v):
        v = int(v)
        if v < 0:
            raise argparse.ArgumentTypeError("powersgd rank must be >= 0")
        return v

    p.add_argument("--powersgd-rank", type=_rank, default=0,
                   help="replace the quantized allreduce with PowerSGD "
                        "low-rank compression at this rank (0 = off)")
    def _ratio(v):
        v = float(v)
        if v and not 0 < v < 1:
            raise argparse.ArgumentTypeError("topk ratio must be in (0, 1)")
        return v

    p.add_argument("--topk-ratio", type=_ratio, default=0,
                   help="replace the quantized allreduce with top-k "
                        "sparsification shipping this fraction of each "
                        "gradient's coordinates (0 = off)")
    p.add_argument("--steps", type=int, default=20)
    p.add_argument("--batch", type=int, default=8, help="global batch (sequences)")
    p.add_argument("--seq", type=int, default=128)
    p.add_argument("--layers", type=int, default=2)
    p.add_argument("--d-model", type=int, default=128)
    p.add_argument("--heads", type=int, default=4)
    p.add_argument("--vocab", type=int, default=512)
    p.add_argument("--data", choices=["synthetic", "text"], default="synthetic",
                   help="'text' = REAL byte-level LM on this repo's own "
                        "documentation (genuine English prose, zero "
                        "egress); vocab forced to 256, 90/10 val split, "
                        "val_loss reported")
    def _avg_bits(v):
        v = float(v)
        if v and not 2 <= v <= 8:  # solve_bit_allocation's bits_range
            raise argparse.ArgumentTypeError("average bits must be in [2, 8]")
        return v

    def _every(v):
        v = int(v)
        if v < 1:
            raise argparse.ArgumentTypeError("--adapt-every must be >= 1")
        return v

    p.add_argument("--adaptive-bits", type=_avg_bits, default=0,
                   help="adaptive per-layer bit allocation at this AVERAGE "
                        "bit budget (parallel/adaptive.py, L-GreCo lineage); "
                        "re-solved every --adapt-every steps; 0 = off")
    p.add_argument("--adapt-every", type=_every, default=50)
    p.add_argument("--checkpoint-dir", default=None,
                   help="save/resume directory (torch_cgx_tpu.checkpoint): "
                        "resumes from the latest step if one exists, saves "
                        "at the end of the run; the per-layer compression "
                        "registry rides inside the checkpoint")
    p.add_argument("--lr", type=float, default=1e-3)
    p.add_argument("--cpu", action="store_true", help="force the virtual CPU mesh")
    return p.parse_args()


def load_text_corpus(seq: int):
    """Byte-level windows over the repo's Markdown docs — real English
    text available with zero network egress. Returns (train, val) int32
    arrays of (n, seq) token rows (next-token targets are the shifted
    row, as for the synthetic stream).

    The 90/10 split is on CONTIGUOUS BYTES, before any windowing: train
    windows overlap (stride seq/2) for more rows, val windows are
    disjoint (stride seq) and share no bytes with any train window — so
    val_loss is genuinely held out, not memorizable from overlapping
    neighbors."""
    import glob

    import numpy as np

    paths = sorted(
        glob.glob(os.path.join(_REPO, "*.md"))
        + glob.glob(os.path.join(_REPO, "docs", "*.md"))
    )
    blob = b"\n\n".join(open(p, "rb").read() for p in paths)
    tokens = np.frombuffer(blob, np.uint8).astype(np.int32)

    def windows(t, stride):
        n = (len(t) - seq - 1) // stride
        if n <= 0:
            raise SystemExit(
                f"gpt2_train.py: text corpus too small ({len(tokens)} "
                f"bytes across {len(paths)} .md files) for seq {seq} — "
                "run from a repo checkout or shrink --seq"
            )
        return np.stack([t[i * stride : i * stride + seq] for i in range(n)])

    cut = int(0.9 * len(tokens))
    train = windows(tokens[:cut], seq // 2)
    val = windows(tokens[cut:], seq)
    rng = np.random.default_rng(0)
    return train[rng.permutation(len(train))], val


def main():
    args = parse_args()
    picked = [
        f for f, on in (("--powersgd-rank", args.powersgd_rank),
                        ("--topk-ratio", args.topk_ratio),
                        ("--error-feedback", args.error_feedback))
        if on
    ]
    if len(picked) > 1:
        raise SystemExit(
            f"gpt2_train.py: error: {' and '.join(picked)} are mutually "
            "exclusive (each compressor carries its own error feedback)"
        )
    if args.cpu:
        # Force, don't setdefault: append to whatever XLA_FLAGS exists.
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "")
            + " --xla_force_host_platform_device_count=8"
        )
    import jax

    if args.cpu:
        jax.config.update("jax_platforms", "cpu")
    from torch_cgx_tpu.utils import entry

    entry.setup_compile_cache()
    print("device:", entry.require_accelerator(cpu_requested=args.cpu),
          file=sys.stderr)
    import jax.numpy as jnp
    import numpy as np
    import optax
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from torch_cgx_tpu import config as cgx_config
    from torch_cgx_tpu.models import GPT2, GPT2Config, lm_loss
    from torch_cgx_tpu.models.gpt2 import sp_lm_loss, tp_param_spec
    from torch_cgx_tpu.parallel import make_train_step, replicate, shard_batch
    from torch_cgx_tpu.parallel.ring_attention import make_sp_attention
    from torch_cgx_tpu.utils.tree import path_str

    os.environ[cgx_config.COMPRESSION_QUANTIZATION_BITS] = str(args.bits)
    os.environ[cgx_config.COMPRESSION_BUCKET_SIZE] = str(args.bucket_size)
    if args.stochastic:
        os.environ[cgx_config.STOCHASTIC_ROUNDING] = "1"

    devices = jax.devices()
    n = len(devices)
    dp = args.dp or max(1, n // (args.tp * args.sp))
    want = dp * args.tp * args.sp
    if want > n:
        raise SystemExit(f"need {want} devices (dp*tp*sp), have {n}")
    assert dp % args.cross == 0, "--cross must divide dp"
    intra = dp // args.cross

    axis_names = ("cross", "dp", "tp", "sp")
    mesh = Mesh(
        np.asarray(devices[:want]).reshape(args.cross, intra, args.tp, args.sp),
        axis_names,
    )
    dp_axes = ("cross", "dp") if args.cross > 1 else ("dp",)

    if args.sp > 1 and args.cross > 1:
        raise SystemExit("--sp composes with flat --dp only (not --cross)")
    if args.data == "text":
        args.vocab = 256  # byte-level LM
    attn = make_sp_attention("sp", impl="ring") if args.sp > 1 else None
    cfg = GPT2Config.tiny(
        vocab_size=args.vocab,
        n_layer=args.layers,
        n_head=args.heads,
        d_model=args.d_model,
        max_seq=args.seq,
    )
    model = GPT2(cfg, attn_fn=attn) if attn else GPT2(cfg)
    init_model = GPT2(cfg)  # init outside shard_map: plain attention

    val_data = None
    if args.data == "text":
        data, val_data = load_text_corpus(args.seq)
        if len(data) <= args.batch:
            raise SystemExit(
                f"text corpus too small: {len(data)} rows for batch "
                f"{args.batch} at seq {args.seq}"
            )
    else:
        # Synthetic learnable stream: shifted token patterns.
        # Size the synthetic corpus off the batch so any --batch works: the
        # window below needs len(data) > batch, and len(data) - batch must
        # not divide batch or the rotation collapses to one repeated window
        # (2048 and 2049 are coprime, so one of them never divides batch).
        window = 2048 if args.batch % 2048 else 2049
        n_rows = args.batch + window
        data = (np.arange(args.seq)[None, :] + np.arange(n_rows)[:, None]) % args.vocab
        data = data.astype(np.int32)

    tokens0 = jnp.asarray(data[: max(2, args.batch)])
    params = init_model.init(jax.random.PRNGKey(0), tokens0)["params"]

    if args.tp > 1:
        flat, treedef = jax.tree_util.tree_flatten_with_path(params)
        specs = jax.tree_util.tree_unflatten(
            treedef, [tp_param_spec(path_str(p), l) for p, l in flat]
        )
        params = jax.tree.map(
            lambda x, s: jax.device_put(x, NamedSharding(mesh, s)),
            params,
            specs,
        )
    else:
        params = replicate(params, mesh)

    opt = optax.adamw(args.lr)
    opt_state = (
        opt.init(params) if args.tp > 1 else replicate(opt.init(params), mesh)
    )

    if args.sp > 1:

        def loss_fn(p, batch):
            # global positions for the local sequence shard
            s_local = batch.shape[1]
            pos = jax.lax.axis_index("sp") * s_local + jnp.arange(s_local)
            logits = model.apply({"params": p}, batch, positions=pos)
            return sp_lm_loss(logits, batch, "sp")

    else:

        def loss_fn(p, batch):
            return lm_loss(model.apply({"params": p}, batch), batch)

    sp_axis = "sp" if args.sp > 1 else None
    step = make_train_step(
        loss_fn,
        opt,
        mesh,
        axes=dp_axes,
        sp_axis=sp_axis,
        stochastic_seed=cgx_config.global_seed() if args.stochastic else None,
        donate=False,
        error_feedback=args.error_feedback,
        powersgd_rank=args.powersgd_rank or None,
        topk_ratio=args.topk_ratio or None,
    )
    state = None
    if args.powersgd_rank:
        from torch_cgx_tpu.parallel import init_powersgd_state

        state = init_powersgd_state(
            params, mesh, rank=args.powersgd_rank, axes=dp_axes,
            sp_axis=sp_axis,
        )
    elif args.topk_ratio:
        from torch_cgx_tpu.parallel import init_topk_state

        state = init_topk_state(
            params, mesh, args.topk_ratio, axes=dp_axes, sp_axis=sp_axis,
        )
    elif args.error_feedback:
        from torch_cgx_tpu.parallel import init_error_feedback

        state = init_error_feedback(
            params, mesh, axes=dp_axes, sp_axis=sp_axis,
        )

    if args.adaptive_bits:
        if args.sp > 1:
            raise SystemExit("--adaptive-bits composes with sp=1 only "
                             "(the measurement grad runs outside shard_map)")
        if args.powersgd_rank or args.topk_ratio:
            raise SystemExit("--adaptive-bits has no effect under "
                             "--powersgd-rank / --topk-ratio (those "
                             "reducers do not consult the quantization "
                             "registry)")
        from torch_cgx_tpu.parallel.adaptive import adapt_bits

        grad_for_stats = jax.jit(jax.grad(loss_fn))

    # Checkpoint/resume (torch_cgx_tpu.checkpoint): restore picks up the
    # training pytree AND the per-layer compression registry — a resumed
    # run compresses from its first step (the restart gap the reference
    # leaves open, SURVEY.md §5.4).
    start_step = 0
    if args.checkpoint_dir:
        if args.tp > 1:
            raise SystemExit("--checkpoint-dir in this example composes "
                             "with tp=1 only (restore re-replicates; tp "
                             "resharding is left to the checkpoint API)")
        if args.error_feedback or args.powersgd_rank or args.topk_ratio:
            raise SystemExit(
                "--checkpoint-dir in this example does not checkpoint the "
                "error-feedback residuals / PowerSGD factors / top-k "
                "residuals; resuming would silently reset that state "
                "(checkpoint the `state` pytree alongside params via "
                "torch_cgx_tpu.checkpoint in real training loops)")
        from torch_cgx_tpu import checkpoint as ckpt

        last = ckpt.latest_step(args.checkpoint_dir)
        if last is not None:
            tree = ckpt.restore(
                args.checkpoint_dir, last,
                target={"params": jax.device_get(params),
                        "opt_state": jax.device_get(opt_state)},
            )
            params = replicate(tree["params"], mesh)
            opt_state = replicate(tree["opt_state"], mesh)
            start_step = last

    losses = []
    bit_allocs = 0
    import time as _time

    t0 = steady0 = _time.time()
    for i in range(start_step, start_step + args.steps):
        lo = (i * args.batch) % (len(data) - args.batch)
        raw = jnp.asarray(data[lo : lo + args.batch])
        if args.adaptive_bits and i % args.adapt_every == 0:
            # One extra backward every --adapt-every steps; the registry
            # version bump retraces the train step with the new per-layer
            # bits (adaptive.py:adapt_bits docstring).
            g = jax.device_get(grad_for_stats(params, raw))
            adapt_bits(g, avg_bits=args.adaptive_bits,
                       bucket_size=args.bucket_size)
            bit_allocs += 1
        batch = shard_batch(raw, mesh, dp_axes, sp_axis=sp_axis)
        if state is not None:
            params, opt_state, state, loss = step(
                params, opt_state, state, batch, jnp.int32(i)
            )
        else:
            params, opt_state, loss = step(params, opt_state, batch, jnp.int32(i))
        losses.append(float(loss))
        if i == start_step:
            steady0 = _time.time()  # exclude the compile from the step rate
        done = i - start_step + 1
        if done % max(1, args.steps // 5) == 0:
            print(f"step {i + 1} ({done}/{args.steps} this run): "
                  f"loss={losses[-1]:.4f}")

    summary = {
        "example": "gpt2_train",
        "mesh": {a: int(mesh.shape[a]) for a in axis_names},
        "data": args.data,
        "bits": args.adaptive_bits or args.bits,
        # Each re-allocation bumps the registry version and retraces the
        # step INSIDE the steady timing window — steps_per_s under
        # adaptive bits includes that recompile cost.
        **({"bit_reallocs": bit_allocs} if args.adaptive_bits else {}),
        **({"powersgd_rank": args.powersgd_rank} if args.powersgd_rank else {}),
        **({"topk_ratio": args.topk_ratio} if args.topk_ratio else {}),
        "first_loss": losses[0],
        "final_loss": losses[-1],
        "compile_s": round(steady0 - t0, 2),
        **({"resumed_from": start_step} if start_step else {}),
    }
    if args.steps > 1:  # steady window needs at least one post-compile step
        summary["steps_per_s"] = round(
            (args.steps - 1) / max(_time.time() - steady0, 1e-9), 3
        )
    if args.checkpoint_dir:
        end = start_step + args.steps
        ckpt.save(args.checkpoint_dir,
                  {"params": params, "opt_state": opt_state}, end)
        summary["saved_step"] = end
    if val_data is not None and args.sp == 1:
        # Held-out loss on real text: one fixed-shape plain jit (loss_fn
        # has no collectives outside sp mode; sharded/replicated params
        # are ordinary jit inputs). sp mode skips val (its loss_fn uses
        # axis_index and must run inside shard_map).
        rows = val_data
        if len(rows) < args.batch:  # tiny corpora: tile up to one batch
            reps = -(-args.batch // len(rows))
            rows = np.concatenate([rows] * reps)
        n_batches = max(1, min(4, len(rows) // args.batch))
        eval_loss = jax.jit(loss_fn)
        vals = [
            float(
                eval_loss(
                    params,
                    jnp.asarray(rows[b * args.batch : (b + 1) * args.batch]),
                )
            )
            for b in range(n_batches)
        ]
        summary["val_loss"] = round(sum(vals) / len(vals), 4)
    print(json.dumps(summary))


if __name__ == "__main__":
    main()
