# Pallas kernels (each with a jnp oracle in ref.py).  Callers pass
# ``interpret=True`` explicitly to run one off the TPU.
