# Serving image (deployment-artifact parity with the reference's
# /root/reference/Dockerfile:1, which ships a single static server binary).
# This image serves a model over HTTP :8000 / gRPC :9000 / metrics :2121.
#
# Build:  docker build -t gofr-tpu .
# Run  :  docker run -p 8000:8000 -p 9000:9000 -p 2121:2121 \
#             -e TPU_MODEL=llama-1b -e TPU_QUANT=int8 gofr-tpu
#
# The pip line below installs jax's CPU wheel, so this image serves on
# the CPU. For a TPU VM install "jax[tpu]" instead; nothing else changes,
# because no platform is pinned here and the engine runs on the backend
# JAX finds (health reports it: details.tpu.details.platform).

FROM python:3.12-slim

WORKDIR /app

RUN pip install --no-cache-dir \
    jax flax optax orbax-checkpoint chex einops numpy grpcio safetensors

COPY gofr_tpu/ gofr_tpu/
COPY examples/tpu-http/ examples/tpu-http/

ENV PYTHONPATH=/app \
    TPU_ENABLED=1 \
    TPU_MODEL=llama-tiny

EXPOSE 8000 9000 2121

# The tpu-http example is the canonical serving app: App + container TPU
# member + /generate route + health/metrics endpoints.
CMD ["python", "examples/tpu-http/main.py"]
