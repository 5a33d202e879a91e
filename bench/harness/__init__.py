"""The benchmark's own code: set-up, drivers, trace reduction, checks."""
