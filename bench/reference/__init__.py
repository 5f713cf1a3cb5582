"""Plain references: straightforward jax.numpy in float32 at the highest
matmul precision.  They import nothing of the program."""
