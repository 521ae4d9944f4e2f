"""Plain references: the published models in jax.numpy and float32."""
