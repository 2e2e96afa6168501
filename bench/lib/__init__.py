"""The benchmark's own yardstick: data and traffic generation, the plain
reference, trace reduction, peaks and per-kernel work. Nothing here imports
the program under test."""
