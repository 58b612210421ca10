"""repro: JAX framework reproducing 'Lossless Compression of Vector IDs for
Approximate Nearest Neighbor Search' (Severo et al., 2025) as a
compressed-id vector-search service: id codecs, IVF/graph/flat indexes,
batched scan engines with Pallas kernels, micro-batched and sharded
serving."""

__version__ = "0.1.0"
