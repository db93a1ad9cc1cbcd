"""The shared yardstick: clocks, arrivals, percentiles, peaks, the device, and the reduction from a profiler trace to numbers."""
