"""Pallas TPU kernels for the paper's quantized hot spots + jnp oracles.

Each kernel lives in its own module with a matching ``*_ref`` oracle in
``ref.py``; ``ops.py`` is the public dispatch surface (Pallas on TPU, oracle
elsewhere, ``FORCE``/``REPRO_KERNELS_FORCE=interpret`` to override).
"""
