"""One reader per per-layer metric, found by the metric's name.  A reader
takes the run's context and returns the value, or None where it finds
nothing to read."""
