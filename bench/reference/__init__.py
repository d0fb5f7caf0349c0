"""Plain float32 references of the benchmarked models."""
