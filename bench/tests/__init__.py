"""CPU tests of the benchmark's own code at small sizes."""
