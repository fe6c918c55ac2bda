"""Plain float32 reference implementations of the architectures the chip
benchmark serves: no kernels, no cache, no batching. One module a model."""
