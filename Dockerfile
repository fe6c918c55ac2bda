# Dynamo-TPU runtime image.
#
# ONE image serves every role in the stack — operator controller-manager,
# OpenAI frontend, engine workers (jetstream / vllm_tpu / trtllm_tpu
# profiles), and the TPU metrics exporter — each pod picks its role via
# `command:` in its manifest. This is the artifact the reference *consumes*
# as nvcr.io/nvidia/ai-dynamo/<backend>-runtime
# (/root/reference/examples/deploy/vllm/agg.yaml:17,27); a from-scratch
# framework has to produce it.
#
# Build:  make image                      (dynamo-tpu/runtime:latest)
#         make image RELEASE_VERSION=0.5.0 JAX_EXTRA=tpu
# The default build installs jax[tpu] (libtpu wheel). JAX_EXTRA= (empty)
# builds a CPU-only image for CI and operator-only clusters; a worker in it
# starts only with JAX_PLATFORMS=cpu set on purpose (no CPU fallback).

ARG BASE_IMAGE=python:3.12-slim
FROM ${BASE_IMAGE}

# g++ stays in the final image: runtime/native.py rebuilds the transport /
# router .so on demand if the prebuilt one is missing (cache-dir wipe,
# source patch), and engine configs may point at out-of-tree kernels.
RUN apt-get update \
    && apt-get install -y --no-install-recommends g++ \
    && rm -rf /var/lib/apt/lists/*

WORKDIR /opt/dynamo-tpu
COPY pyproject.toml README.md ./
COPY dynamo_tpu ./dynamo_tpu

ARG JAX_EXTRA=tpu
RUN if [ -n "${JAX_EXTRA}" ]; then \
        pip install --no-cache-dir ".[${JAX_EXTRA}]"; \
    else \
        pip install --no-cache-dir .; \
    fi

# Pre-build the native transport + router libraries so first worker start
# pays no compile; DYNAMO_TPU_BUILD_DIR pins them into the image layer.
ENV DYNAMO_TPU_BUILD_DIR=/opt/dynamo-tpu/native
RUN python -c "from dynamo_tpu.runtime import native; \
native.build_library(); \
assert native.get_lib() is not None; \
assert native.get_router_lib() is not None"

# Persistent XLA compilation cache mount point (the TRT-engine-cache
# analogue): manifests mount the model-cache PVC here.
ENV JAX_COMPILATION_CACHE_DIR=/workspace/model-cache/jax-comp-cache

EXPOSE 8000
# Role is chosen by the pod spec; the bare image documents itself.
CMD ["python", "-c", "print('dynamo-tpu runtime image. Roles: python -m dynamo_tpu.operator | dynamo_tpu.frontend | dynamo_tpu.jetstream | dynamo_tpu.vllm_tpu | dynamo_tpu.trtllm_tpu | dynamo_tpu.exporter')"]
