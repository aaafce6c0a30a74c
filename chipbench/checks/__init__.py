"""Checks of the benchmark's own yardsticks, run by hand (see each
module's docstring)."""
