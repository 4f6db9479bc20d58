"""Plain references that decide each cell's ``correct``.

They import nothing of the program and take nothing it made: every
input comes from the configuration file and the run's seed.  They run
in float32 at the highest matmul precision; ``dtype=jnp.bfloat16``
gives the lower-precision control that the limits are set against.
"""
