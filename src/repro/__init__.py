"""repro — production-scale JAX/Pallas reproduction of adaptive sampled
softmax with an inverted multi-index (see DESIGN.md for the architecture)."""
