"""Pallas kernels for the fabric tick's hot path, their pure-jnp oracles
(`ref`), and the backend dispatch between the two (`ops`)."""
