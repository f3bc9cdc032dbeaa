"""The chip benchmark's harness: name resolution, traffic, fleet, run,
check and trace reduction. Everything here is the benchmark's own; the
program under test is reached only through ``repro.core.engine``."""
