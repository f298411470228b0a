"""Plain references the benchmark compares the served path against."""
