# Pallas TPU kernels: the paper's speculative data movement
# (spec_gather.py, spec_scatter.py) plus attention and grouped-matmul
# kernels; ref.py holds the pure-jnp oracles the tests compare against,
# backend.py decides compiled vs interpret mode for every kernel call.
