# Parity with the reference Dockerfile (build + test in one container).
# CPU image: the TPU runtime is provided by the deployment environment
# (libtpu + a real chip); this image runs the full test suite on the CPU
# backend with a virtual 8-device mesh.
FROM python:3.12-slim

RUN apt-get update && apt-get install -y --no-install-recommends \
    g++ make && rm -rf /var/lib/apt/lists/*

WORKDIR /app
COPY . .

RUN pip install --no-cache-dir "jax[cpu]" numpy pytest hypothesis
# the native host library (TSV ingestion, wire codec, and the served query-
# vector parse sptag_parse_query_vectors) is built by its own loader, which
# stamps it with source, flags and this machine's CPU; a container started
# on another machine finds the stamp stale and rebuilds when its first
# SearchServer is constructed (g++ stays in the image)
RUN python -c "from sptag_tpu import native; assert native.load() is not None"

RUN python -m pytest tests/ -q

CMD ["python", "-m", "sptag_tpu.serve.server", "--help"]
