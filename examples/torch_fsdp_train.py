"""Fully-sharded (ZeRO-3 style) training through the ``"cgx"``
torch.distributed backend — the workflow the reference CANNOT run: its
ProcessGroup throws on both ``_allgather_base`` and ``_reduce_scatter_base``
(/root/reference/src/ProcessGroupCGX.cc — it only plumbs group names "for
FSPD"), while this bridge implements ``all_gather_into_tensor`` and a
QUANTIZED ``reduce_scatter_tensor``, i.e. both ZeRO-3 traffic directions.

Each rank owns a 1/ws shard of the flat parameters; every step gathers the
full parameters for compute and reduce-scatters averaged gradient shards —
exactly the two collectives torch's FSDP wrapper is built from (the wrapper
itself refuses CPU-only hosts, so this example runs the equivalent manual
loop; on a GPU/TPU-VM host the same process group drops straight into it).

Wire compression:
  * gradient reduce-scatter rides the quantized SRA scatter-reduce half
    (``CGX_COMPRESSION_QUANTIZATION_BITS`` / --bits);
  * the parameter all-gather optionally compresses too
    (``CGX_FSDP_ALLGATHER_BITS`` / --allgather-bits — every rank decodes
    identical bytes, so replicas stay bit-identical).

Run:
    python examples/torch_fsdp_train.py --nproc 2 --bits 8 --allgather-bits 8
"""

from __future__ import annotations

import argparse
import json
import os
import sys

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _REPO)


def parse_args():
    p = argparse.ArgumentParser(description="CGX torch-bridge ZeRO-3 example")
    p.add_argument("--nproc", type=int, default=2)
    p.add_argument("--steps", type=int, default=50)
    p.add_argument("--batch-size", type=int, default=16, help="per rank")
    p.add_argument("--lr", type=float, default=0.05)
    p.add_argument("--bits", type=int, default=8,
                   help="gradient reduce-scatter quantization bits")
    p.add_argument("--allgather-bits", type=int, default=0,
                   help="CGX_FSDP_ALLGATHER_BITS: compress the parameter "
                        "all-gather too (0 = raw)")
    p.add_argument("--d-in", type=int, default=64)
    p.add_argument("--d-hidden", type=int, default=128)
    p.add_argument("--d-out", type=int, default=8)
    p.add_argument("--seed", type=int, default=0)
    return p.parse_args()


def train(rank: int, ws: int, init_method: str, args) -> None:
    # The codec runs on the host. Set, not default: an inherited
    # JAX_PLATFORMS=tpu (the Dockerfile sets it) would have every
    # spawned rank claim the one chip.
    os.environ["JAX_PLATFORMS"] = "cpu"
    os.environ["CGX_COMPRESSION_QUANTIZATION_BITS"] = str(args.bits)
    if args.allgather_bits:
        os.environ["CGX_FSDP_ALLGATHER_BITS"] = str(args.allgather_bits)
    import torch
    import torch.distributed as dist

    import torch_cgx_tpu.torch_backend  # noqa: F401 — registers "cgx"

    dist.init_process_group(
        "cgx", init_method=init_method, rank=rank, world_size=ws
    )

    # Two-layer MLP as ONE flat parameter vector, sharded 1/ws per rank
    # (ZeRO-3's partitioned state). Same init on every rank, then each
    # keeps only its shard.
    torch.manual_seed(args.seed)
    shapes = [
        (args.d_in, args.d_hidden),
        (args.d_hidden,),
        (args.d_hidden, args.d_out),
        (args.d_out,),
    ]
    flat = torch.cat([
        (torch.randn(s) * (0.5 / s[0] ** 0.5) if len(s) > 1
         else torch.zeros(s)).reshape(-1)  # zero-init biases
        for s in shapes
    ])
    n = flat.numel()
    shard_n = -(-n // ws)
    padded = torch.cat([flat, torch.zeros(shard_n * ws - n)])
    my_shard = padded[rank * shard_n : (rank + 1) * shard_n].clone()

    def unflatten(vec):
        out, off = [], 0
        for s in shapes:
            numel = 1
            for d in s:
                numel *= d
            out.append(vec[off : off + numel].reshape(s))
            off += numel
        return out

    # Same teacher on every rank; rank-local batch shards.
    g = torch.Generator().manual_seed(args.seed + 1)
    teacher = torch.randn(args.d_in, args.d_out, generator=g)
    g_local = torch.Generator().manual_seed(args.seed + 2 + rank)

    first = last = None
    for step in range(args.steps):
        # ZeRO-3 forward gather: materialize full params from shards.
        full = torch.zeros(shard_n * ws)
        dist.all_gather_into_tensor(full, my_shard)
        params = [p.detach().requires_grad_(True) for p in unflatten(full[:n])]
        w1, b1, w2, b2 = params

        x = torch.randn(args.batch_size, args.d_in, generator=g_local)
        y = x @ teacher
        pred = torch.relu(x @ w1 + b1) @ w2 + b2
        loss = ((pred - y) ** 2).mean()
        loss.backward()

        # ZeRO-3 backward: reduce-scatter AVERAGED gradient shards
        # (quantized wire; every rank receives its own shard only).
        gflat = torch.cat([p.grad.reshape(-1) for p in params])
        gpad = torch.cat([gflat, torch.zeros(shard_n * ws - n)])
        gshard = torch.zeros(shard_n)
        dist.reduce_scatter_tensor(gshard, gpad, op=dist.ReduceOp.AVG)
        my_shard = my_shard - args.lr * gshard

        if first is None:
            first = loss.item()
        last = loss.item()
        if rank == 0 and (step + 1) % max(1, args.steps // 5) == 0:
            print(f"step {step + 1}/{args.steps}: loss={last:.4f}", flush=True)

    if rank == 0:
        print(json.dumps({
            "example": "torch_fsdp_train",
            "world_size": ws,
            "bits": args.bits,
            "allgather_bits": args.allgather_bits,
            "params": n,
            "shard_per_rank": shard_n,
            "first_loss": first,
            "final_loss": last,
        }), flush=True)
    dist.barrier()
    dist.destroy_process_group()
    if last >= first:
        raise SystemExit("loss did not decrease")


def main():
    from _launch import run_ranks

    args = parse_args()
    return run_ranks(train, args.nproc, args, prefix="cgx_fsdp_example_")


if __name__ == "__main__":
    sys.exit(main())
