"""The chip benchmark's harness: traffic, weights, cell runners, trace reduction,
work counts and the comparison that decides ``correct``.  Nothing here is
imported by the program; the program is imported from ``src/``."""
