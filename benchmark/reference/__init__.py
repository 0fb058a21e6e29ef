"""Plain references: float32 ``jax.numpy`` at ``highest`` matmul precision,
no kernels, no cache, no batching tricks. Nothing here imports the program."""
