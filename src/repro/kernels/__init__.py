"""The engines' Pallas kernels (one module each), their pure-jnp oracles
(``ref.py``) and the backend registry that runs them (``registry.py``)."""
