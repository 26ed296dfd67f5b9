"""The on-chip benchmark of ray_tpu: harness, yardstick and data files.

Everything a later PR may not change lives here (see README.md). Importing
this package imports neither jax nor ray_tpu.
"""
