"""The general part of the benchmark: registry, run loop, load generator,
trace reduction and roofline arithmetic."""
