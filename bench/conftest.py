import os

# The benchmark's tests run on the CPU; only its chip runs see a TPU.
os.environ.setdefault("JAX_PLATFORMS", "cpu")
