# Deployable ordering service — the routerlicious Dockerfile analog
# (reference: server/routerlicious/Dockerfile). Runs the socket front door
# over the partitioned-lambda pipeline with the device-apply stage.
#
# CPU image by default (jax[cpu]); on a TPU host, swap the pip line for the
# matching jax[tpu] wheel — the service code is identical.

FROM python:3.11-slim AS build

RUN apt-get update \
    && apt-get install -y --no-install-recommends g++ make \
    && rm -rf /var/lib/apt/lists/*

WORKDIR /app
COPY pyproject.toml README.md ./
COPY fluidframework_tpu ./fluidframework_tpu
COPY native ./native

# Native runtime components (ticket loop, coordination, partition log,
# content-addressed store) build here; utils/native.py also rebuilds on
# demand if sources change inside the container.
RUN make -C native

RUN pip install --no-cache-dir "jax[cpu]" numpy && \
    pip install --no-cache-dir --no-deps .

FROM python:3.11-slim

RUN apt-get update \
    && apt-get install -y --no-install-recommends g++ make \
    && rm -rf /var/lib/apt/lists/*

WORKDIR /app
COPY --from=build /usr/local/lib/python3.11/site-packages /usr/local/lib/python3.11/site-packages
COPY --from=build /app/native ./native
COPY config ./config

# The compile cache is placed from outside (utils.enable_compile_cache
# honours JAX_COMPILATION_CACHE_DIR and names no other directory); mount
# a volume here to keep compiled kernels across container restarts.
ENV FLUID_HOST=0.0.0.0 \
    FLUID_PORT=7070 \
    FLUID_NATIVE_DIR=/app/native \
    JAX_COMPILATION_CACHE_DIR=/var/cache/fluid-jax

EXPOSE 7070

CMD ["python", "-m", "fluidframework_tpu.service.server_main", \
     "--config", "config/config.json"]
