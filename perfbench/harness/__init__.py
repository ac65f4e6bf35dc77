"""The general parts of the benchmark: registry, arrival schedule, the
scheduled delta source, the measured window, trace reduction, peaks and
the merge's byte count."""
