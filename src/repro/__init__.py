"""Reproduction package: the run-time reconfigurable multi-precision
multiplier (Arish & Sharma 2019) grown into a jax_pallas system.  See
DESIGN.md."""
