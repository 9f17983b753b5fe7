"""BERT MLM fine-tuning with 8-bit compressed data parallelism —
BASELINE.md's "BERT-base fine-tune DDP, 8-bit, layer_min_size filter on
LN/bias" config row as a runnable script (the reference ships only a CIFAR
DDP example, /root/reference/examples/cifar_train.py).

The LN/bias filter is the same two-part gate the reference's DDP hook
applies (cgx_utils/allreduce_hooks.py:42-45): tensors of dim <= 1 stay
uncompressed, and anything smaller than ``CGX_COMPRESSION_MINIMAL_SIZE``
(--min-size) bypasses compression entirely (compressor.cc:421-425). The
summary reports how many parameter leaves each rule left raw, so the
filter's effect is observable, not implied.

    python examples/bert_finetune.py --cpu --steps 10          # smoke
    python examples/bert_finetune.py --layers 12 --d-model 768 \
        --heads 12 --seq 512 --steps 100                        # base-ish
"""

from __future__ import annotations

import argparse
import json
import os
import sys

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _REPO)


def parse_args():
    p = argparse.ArgumentParser(description="BERT compressed-DP MLM fine-tune")
    p.add_argument("--bits", type=int, default=8)
    p.add_argument("--bucket-size", type=int, default=512)
    p.add_argument("--min-size", type=int, default=16,
                   help="CGX_COMPRESSION_MINIMAL_SIZE: leaves smaller than "
                        "this stay uncompressed")
    p.add_argument("--steps", type=int, default=20)
    p.add_argument("--batch", type=int, default=16)
    p.add_argument("--seq", type=int, default=64)
    p.add_argument("--layers", type=int, default=2)
    p.add_argument("--d-model", type=int, default=128)
    p.add_argument("--heads", type=int, default=4)
    p.add_argument("--vocab", type=int, default=512)
    p.add_argument("--mask-every", type=int, default=4,
                   help="mask every Nth position for the MLM objective")
    p.add_argument("--lr", type=float, default=2e-3)
    p.add_argument("--cpu", action="store_true",
                   help="force the 8-device virtual CPU mesh")
    return p.parse_args()


def main():
    args = parse_args()
    if args.steps < 1:
        raise SystemExit("--steps must be >= 1")
    if args.cpu:
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

    from torch_cgx_tpu import config as cgx_config
    from torch_cgx_tpu.models import Bert, BertConfig, mlm_loss
    from torch_cgx_tpu.parallel import (
        flat_mesh,
        make_train_step,
        replicate,
        shard_batch,
    )

    os.environ[cgx_config.COMPRESSION_QUANTIZATION_BITS] = str(args.bits)
    os.environ[cgx_config.COMPRESSION_BUCKET_SIZE] = str(args.bucket_size)
    os.environ[cgx_config.COMPRESSION_MINIMAL_SIZE] = str(args.min_size)

    cfg = BertConfig.tiny(
        vocab_size=args.vocab,
        n_layer=args.layers,
        n_head=args.heads,
        d_model=args.d_model,
        max_seq=args.seq,
    )
    model = Bert(cfg)

    # Learnable synthetic MLM stream (hermetic): periodic token rows;
    # every --mask-every'th position is replaced by the [MASK] id and must
    # be reconstructed.
    rows = args.batch * 4
    tokens = (
        (np.arange(args.seq)[None, :] + np.arange(rows)[:, None])
        % min(args.vocab, 50)
    ).astype(np.int32)
    mask = np.zeros_like(tokens)
    mask[:, :: args.mask_every] = 1
    inputs = np.where(mask == 1, 3, tokens).astype(np.int32)  # 3 = [MASK]

    mesh = flat_mesh()
    n_dev = int(np.prod(list(mesh.shape.values())))
    if args.batch % n_dev:
        raise SystemExit(f"--batch {args.batch} must divide over {n_dev} devices")
    params = replicate(
        model.init(jax.random.PRNGKey(0), jnp.asarray(inputs[:2]))["params"],
        mesh,
    )
    opt = optax.adamw(args.lr)
    opt_state = replicate(opt.init(params), mesh)

    def loss_fn(p, batch):
        logits = model.apply({"params": p}, batch["x"])
        return mlm_loss(logits, batch["y"], batch["m"])

    step = make_train_step(loss_fn, opt, mesh, donate=False)

    # Observable filter effect: which leaves does the LN/bias + min-size
    # gate leave raw? Counted with the SAME gate the runtime applies
    # (parallel/allreduce.py:is_compressible), so the summary reflects
    # actual wire behavior, not a parallel reimplementation.
    from torch_cgx_tpu.parallel.allreduce import is_compressible

    leaves = jax.tree.leaves(params)
    compressed = sum(1 for l in leaves if is_compressible(l))
    raw_dim = sum(
        1 for l in leaves if not is_compressible(l)
        and is_compressible(l, compress_small=True)
    )  # rejected by the dim<=1 rule alone
    raw_small = len(leaves) - compressed - raw_dim  # size/dtype floor

    import time as _time

    losses = []
    t0 = steady0 = _time.time()
    for i in range(args.steps):
        lo = (i * args.batch) % (rows - args.batch)
        batch = {
            "x": jnp.asarray(inputs[lo : lo + args.batch]),
            "y": jnp.asarray(tokens[lo : lo + args.batch]),
            "m": jnp.asarray(mask[lo : lo + args.batch].astype(np.float32)),
        }
        params, opt_state, loss = step(
            params, opt_state, shard_batch(batch, mesh), jnp.int32(i)
        )
        losses.append(float(loss))
        if i == 0:
            steady0 = _time.time()  # exclude compile from the step rate
        if (i + 1) % max(1, args.steps // 5) == 0:
            print(f"step {i + 1}/{args.steps}: mlm_loss={losses[-1]:.4f}")

    summary = {
        "example": "bert_finetune",
        "devices": n_dev,
        "bits": args.bits,
        "min_size": args.min_size,
        "leaves_compressed": compressed,
        "leaves_raw_dim_filter": raw_dim,
        "leaves_raw_min_size": raw_small,
        "first_loss": losses[0],
        "final_loss": losses[-1],
        "compile_s": round(steady0 - t0, 2),
    }
    if args.steps > 1:
        summary["steps_per_s"] = round(
            (args.steps - 1) / max(_time.time() - steady0, 1e-9), 3
        )
    print(json.dumps(summary))
    return 0 if losses[-1] < losses[0] else 1


if __name__ == "__main__":
    sys.exit(main())
