"""The harness behind bench/run.py: traffic generation, the closed loop,
the plain reference, trace reduction and the table of peaks."""
