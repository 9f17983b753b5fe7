# Deploy recipe — the reference assembles an NGC PyTorch + OpenMPI + ssh
# image (/root/reference/Dockerfile:1-11). The TPU-native equivalent is far
# thinner: TPU VMs already expose the accelerator to any process with
# libtpu, so the image is just Python + jax[tpu] + this package. Run with
# host networking on each host of a pod slice (the TPU runtime and
# jax.distributed discover peers through the metadata the VM provides).
#
#   docker build -t torch-cgx-tpu .
#   docker run --rm --privileged --net=host torch-cgx-tpu \
#       python examples/cifar_train.py --synthetic --steps 100
#
# See README.md "Deploying on Cloud TPU" for the bare-VM (no Docker)
# bootstrap and the multi-host pod-slice launch.

FROM python:3.12-slim

RUN apt-get update && apt-get install -y --no-install-recommends \
        build-essential git \
    && rm -rf /var/lib/apt/lists/*

WORKDIR /app
COPY pyproject.toml README.md ./
COPY torch_cgx_tpu ./torch_cgx_tpu
COPY examples ./examples
COPY tools ./tools

# jax[tpu] pulls libtpu from the Google releases index; torch stays CPU
# (the bridge stages through DLPack — no CUDA anywhere, unlike the
# reference's NGC base).
RUN pip install --no-cache-dir \
        "jax[tpu]==0.9.0" -f https://storage.googleapis.com/jax-releases/libtpu_releases.html \
        flax==0.12.3 optax orbax-checkpoint chex einops ml_dtypes numpy \
    && pip install --no-cache-dir torch --index-url https://download.pytorch.org/whl/cpu \
    && pip install --no-cache-dir -e .

ENV JAX_PLATFORMS=tpu
CMD ["python", "-c", "import jax, torch_cgx_tpu; print(jax.devices())"]
