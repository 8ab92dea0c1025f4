"""Plain references: straight jax.numpy in float32, nothing of paddle_tpu."""
