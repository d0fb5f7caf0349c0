"""The benchmark harness: cell runner, traffic, checks, trace reduction."""
