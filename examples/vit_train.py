"""ViT training with hierarchical compressed data parallelism —
BASELINE.md's "ViT-L/16 multi-host DDP, INTRA_BROADCAST hierarchical
allreduce" config row as a runnable script (the reference ships only a
CIFAR DDP example, /root/reference/examples/cifar_train.py; its two-level
scheme lives in mpi_allreduce_operations.cc:139-185).

The mesh is cross x intra (DCN x ICI on a real pod): gradients reduce
inside each "host" first, leaders exchange across, and the result
broadcasts back — the INTRA_BROADCAST leader scheme, quantized at every
hop per the per-config gates (CGX_INTRA_COMPRESS, config.py).

    python examples/vit_train.py --cpu --steps 10            # smoke
    python examples/vit_train.py --vit-large --intra 4       # pod slice
"""

from __future__ import annotations

import argparse
import json
import os
import sys

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _REPO)


def parse_args():
    p = argparse.ArgumentParser(description="ViT hierarchical compressed-DP")
    p.add_argument("--bits", type=int, default=4)
    p.add_argument("--bucket-size", type=int, default=512)
    p.add_argument("--intra", type=int, default=4,
                   help="devices per 'host' (the intra axis; cross = total/intra)")
    p.add_argument("--steps", type=int, default=20)
    p.add_argument("--batch", type=int, default=32)
    p.add_argument("--image-size", type=int, default=32)
    p.add_argument("--patch-size", type=int, default=8)
    p.add_argument("--classes", type=int, default=10)
    p.add_argument("--layers", type=int, default=2)
    p.add_argument("--d-model", type=int, default=64)
    p.add_argument("--heads", type=int, default=4)
    p.add_argument("--vit-large", action="store_true",
                   help="ViT-L dims (d_model 1024 x 24 layers x 16 heads)")
    p.add_argument("--lr", type=float, default=1e-3)
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
    from torch_cgx_tpu.models import ViT, ViTConfig
    from torch_cgx_tpu.parallel import (
        make_train_step,
        mesh as mesh_mod,
        replicate,
        shard_batch,
    )

    os.environ[cgx_config.COMPRESSION_QUANTIZATION_BITS] = str(args.bits)
    os.environ[cgx_config.COMPRESSION_BUCKET_SIZE] = str(args.bucket_size)

    if args.vit_large:
        cfg = ViTConfig.large(
            image_size=args.image_size,
            patch_size=args.patch_size,
            num_classes=args.classes,
        )
    else:
        cfg = ViTConfig.tiny(
            image_size=args.image_size,
            patch_size=args.patch_size,
            num_classes=args.classes,
            d_model=args.d_model,
            n_layer=args.layers,
            n_head=args.heads,
        )
    model = ViT(cfg)

    mesh = mesh_mod.hierarchical_mesh(intra_size=args.intra)
    axes = (mesh_mod.CROSS_AXIS, mesh_mod.INTRA_AXIS)
    n_dev = int(mesh.shape[axes[0]] * mesh.shape[axes[1]])
    if args.batch % n_dev:
        raise SystemExit(f"--batch {args.batch} must divide over {n_dev} devices")

    # Learnable synthetic image stream: class-conditional means + noise.
    rng = np.random.default_rng(0)
    rows = args.batch * 4
    labels = (np.arange(rows) % args.classes).astype(np.int32)
    means = rng.normal(size=(args.classes, 1, 1, 3)).astype(np.float32)
    images = (
        means[labels]
        + 0.3 * rng.normal(size=(rows, args.image_size, args.image_size, 3))
    ).astype(np.float32)

    params = replicate(
        model.init(jax.random.PRNGKey(0), jnp.asarray(images[:2]))["params"],
        mesh,
    )
    opt = optax.adamw(args.lr)
    opt_state = replicate(opt.init(params), mesh)

    def loss_fn(p, batch):
        logits = model.apply({"params": p}, batch["x"])
        onehot = jax.nn.one_hot(batch["y"], args.classes)
        return optax.softmax_cross_entropy(logits, onehot).mean()

    step = make_train_step(loss_fn, opt, mesh, axes=axes, donate=False)

    import time as _time

    losses = []
    t0 = steady0 = _time.time()
    for i in range(args.steps):
        lo = (i * args.batch) % (rows - args.batch)
        batch = {
            "x": jnp.asarray(images[lo : lo + args.batch]),
            "y": jnp.asarray(labels[lo : lo + args.batch]),
        }
        params, opt_state, loss = step(
            params, opt_state, shard_batch(batch, mesh, axes), jnp.int32(i)
        )
        losses.append(float(loss))
        if i == 0:
            steady0 = _time.time()  # exclude compile from the step rate
        if (i + 1) % max(1, args.steps // 5) == 0:
            print(f"step {i + 1}/{args.steps}: loss={losses[-1]:.4f}")

    summary = {
        "example": "vit_train",
        "mesh": {a: int(mesh.shape[a]) for a in axes},
        "bits": args.bits,
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
