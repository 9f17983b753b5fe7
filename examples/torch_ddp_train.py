"""DDP training through the ``"cgx"`` torch.distributed backend — the
counterpart of the reference's mpirun-launched example
(/root/reference/examples/cifar_train.py:61-150: init_process_group('cgx'),
DDP wrap, ``register_comm_hook(CGXState, cgx_hook)``).

The reference bridges OMPI env vars to MASTER_ADDR/RANK; TPU hosts have no
MPI, so this script self-spawns its ranks (or honors torchrun's RANK /
WORLD_SIZE env when present) and rendezvouses over a file store.

Run:
    python examples/torch_ddp_train.py --nproc 2 --quantization-bits 4
"""

from __future__ import annotations

import argparse
import json
import os
import sys

# Allow `python examples/torch_ddp_train.py` from a source checkout.
_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _REPO)


def parse_args():
    p = argparse.ArgumentParser(description="CGX torch-bridge DDP example")
    p.add_argument("--nproc", type=int, default=2)
    p.add_argument("--steps", type=int, default=30)
    p.add_argument("--batch-size", type=int, default=64, help="per rank")
    p.add_argument("--lr", type=float, default=0.05)
    p.add_argument("--quantization-bits", type=int, default=4)
    p.add_argument("--quantization-bucket-size", type=int, default=1024)
    p.add_argument("--simulate-hosts", type=int, default=1,
                   help="split ranks over N simulated hosts "
                        "(CGX_SHM_HOST_ID override): >1 exercises the "
                        "two-level leader reduction exactly as a real "
                        "multi-host launch would")
    p.add_argument("--seed", type=int, default=0)
    return p.parse_args()


def train(rank: int, ws: int, init_method: str, args) -> None:
    # The codec runs on the host. Set, not default: an inherited
    # JAX_PLATFORMS=tpu (the Dockerfile sets it) would have every
    # spawned rank claim the one chip.
    os.environ["JAX_PLATFORMS"] = "cpu"
    if args.simulate_hosts > 1:
        if "RANK" in os.environ and "WORLD_SIZE" in os.environ:
            # External (torchrun) launch may span REAL machines: a shared
            # simhost id would engage /dev/shm between processes that
            # share no memory. Only the self-spawned single-machine mode
            # may simulate hosts.
            raise SystemExit(
                "--simulate-hosts requires the self-spawned launcher; "
                "under torchrun the real host topology applies"
            )
        # Balanced contiguous split yielding exactly min(hosts, ws)
        # non-empty groups (ceil-division could merge two requested
        # hosts when ws % hosts != 0).
        os.environ["CGX_SHM_HOST_ID"] = (
            f"simhost{rank * args.simulate_hosts // ws}"
        )
    import torch
    import torch.distributed as dist
    import torch.nn as nn

    import torch_cgx_tpu.torch_backend as tb  # registers backend "cgx"

    dist.init_process_group(
        "cgx", init_method=init_method, rank=rank, world_size=ws
    )

    torch.manual_seed(args.seed)
    model = nn.Sequential(
        nn.Flatten(),
        nn.Linear(32 * 32 * 3, 256),
        nn.ReLU(),
        nn.Linear(256, 128),
        nn.ReLU(),
        nn.Linear(128, 10),
    )
    ddp = nn.parallel.DistributedDataParallel(model)
    state = tb.CGXState(
        None,
        compression_params={
            "bits": args.quantization_bits,
            "bucket_size": args.quantization_bucket_size,
        },
    )
    ddp.register_comm_hook(state, tb.cgx_hook)

    opt = torch.optim.SGD(ddp.parameters(), lr=args.lr, momentum=0.9)
    loss_fn = nn.CrossEntropyLoss()

    # Synthetic CIFAR-shaped data with a fixed linear teacher (same trick as
    # examples/cifar_train.py) — rank-local shards.
    g = torch.Generator().manual_seed(args.seed)
    teacher = torch.randn(32 * 32 * 3, 10, generator=g)
    g_local = torch.Generator().manual_seed(args.seed + 1 + rank)

    first = last = None
    for step in range(args.steps):
        x = torch.randn(args.batch_size, 3, 32, 32, generator=g_local)
        y = (x.reshape(args.batch_size, -1) @ teacher).argmax(dim=1)
        opt.zero_grad()
        loss = loss_fn(ddp(x), y)
        loss.backward()
        opt.step()
        if first is None:
            first = loss.item()
        last = loss.item()
        if rank == 0 and (step + 1) % 10 == 0:
            print(f"step {step + 1}/{args.steps}: loss={last:.4f}", flush=True)

    if rank == 0:
        pg = dist.distributed_c10d._get_default_group()
        print(json.dumps({
            "example": "torch_ddp_train",
            "world_size": ws,
            "bits": args.quantization_bits,
            "hosts": len(set(getattr(pg, "_host_by_rank", []) or ["one"])),
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
    return run_ranks(train, args.nproc, args, prefix="cgx_ddp_example_")


if __name__ == "__main__":
    sys.exit(main())
