"""Plain references that decide ``correct``: straightforward jax.numpy in
float32 at the highest matmul precision, importing nothing of the program."""
