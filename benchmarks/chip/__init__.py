"""The chip benchmark: one entry point (``run.py``), data files that
define its cells, the model families they name (``families/``), and the
yardstick (reference, peaks, trace reduction) that later changes to the
program are measured against."""
