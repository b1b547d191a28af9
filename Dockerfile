# Two-stage image for the cedar_tpu webhook (parity with the reference's
# two-stage distroless build, Dockerfile:28-39 — adapted to the Python/JAX
# serving stack with the C++ native encoder precompiled at build time).
#
# Stage 1: build — compile the native SAR encoder so the runtime image
# needs no toolchain.
FROM python:3.12-slim AS build
RUN apt-get update && apt-get install -y --no-install-recommends g++ \
    && rm -rf /var/lib/apt/lists/*
WORKDIR /src
COPY cedar_tpu/ cedar_tpu/
# portable arch: the image may run on older CPUs than the build host
ENV CEDAR_NATIVE_ARCH=x86-64
RUN python -c "from cedar_tpu.native.build import ensure_built; print(ensure_built())"

# Stage 2: runtime — jax[cpu] by default; swap the extra for a TPU-enabled
# jax wheel on TPU node pools.
FROM python:3.12-slim
RUN pip install --no-cache-dir "jax[cpu]" numpy pyyaml
# This image carries the CPU wheel, so it asks for the CPU plane by name:
# --backend tpu refuses to start when JAX finds no TPU (it never falls back
# on its own). A TPU image swaps the wheel above and drops this line.
ENV JAX_PLATFORMS=cpu
COPY --from=build /src/cedar_tpu /app/cedar_tpu
COPY cedarschema/ /app/cedarschema/
WORKDIR /app
ENV PYTHONUNBUFFERED=1
# must match the build stage: ensure_built() keys the .so filename on the
# arch, and the runtime image has no g++ to rebuild — without this the
# webhook would silently fall back to the pure-Python path
ENV CEDAR_NATIVE_ARCH=x86-64
EXPOSE 10288 10289
ENTRYPOINT ["python", "-m", "cedar_tpu.cli.webhook"]
CMD ["--config", "/cedar-authorizer/cedar-config.yaml", "--backend", "tpu", \
     "--cert-dir", "/var/run/cedar-authorizer/certs"]
