"""The benchmark harness: cell specs, traffic, weights, the timed window,
the trace reduction and the comparison that decides ``correct``."""
