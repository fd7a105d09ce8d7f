"""repro — OpenCL + OpenSHMEM hybrid programming model reproduction in JAX."""
