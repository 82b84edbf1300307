"""Warm runtime and JIT: backend compiles and persistent-cache loads
during the measured window, heard through ``jax.monitoring``."""


def read(record):
    return record["compiles_in_window"]
