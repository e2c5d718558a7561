"""The chip benchmark's own library: the loader of the matrix generators
(``generators/``), traffic, work counts, peaks, trace reduction, the plain
reference and the run harness.

It is kept apart from the solver under ``src/`` so that the yardstick
(what is generated, counted and compared) cannot move with the program.
Only ``harness`` imports the solver; ``reference`` imports nothing of it.
"""
