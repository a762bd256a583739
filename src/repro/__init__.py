"""repro — a JAX reproduction of dMath (distributed linear algebra for DL)."""
