"""Small runtime utilities (``compile_cache``: JAX's persistent compile
cache for the chip entry points)."""
