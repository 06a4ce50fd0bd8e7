"""Plain float32 reference of one NGHF MPE update of an acoustic model.

Written from the paper (arXiv:2103.07554, Secs. 3-7) in straightforward
``jax.numpy``: no lattice engine, no kernels, no sharding, and nothing
imported from the program.  ``nghf.Reference`` runs it in blocks of rows,
one jitted piece at a time, under ``highest`` matmul precision.
"""
