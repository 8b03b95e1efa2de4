"""Pallas TPU kernels (+ jnp oracles in ref.py, jit wrappers in ops.py).

The selective scan's oracle is ``models.ssm.chunked_ssm_outputs``."""
