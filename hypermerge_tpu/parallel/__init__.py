"""Multi-chip scale-out: the slab round-robin and the program table."""
