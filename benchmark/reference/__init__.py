"""Plain references: the configurations' mathematics in straightforward
NumPy / jax.numpy, importing nothing of ``alink_tpu``."""
