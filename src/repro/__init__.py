"""repro — supervised algorithm selection for NT matmuls, grown into a
policy-dispatched jax/pallas serving + training stack."""

