"""Plain float32 references of the architectures the system serves."""
