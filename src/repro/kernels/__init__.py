"""The Pallas event kernel behind the JAX backend's event core."""
