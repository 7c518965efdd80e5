"""Pallas kernels for the SCAN service (``bucket_probe``, ``hamming``,
``simhash``, ``triangle_count``), their pure-jnp oracles in ``ref.py``,
and the per-call lane dispatch in ``ops.py``."""
