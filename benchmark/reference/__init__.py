"""Plain references: float32 jax.numpy at `highest` matmul precision, no
kernels, no cache, no batching tricks. They import nothing of the program."""
