"""CPU tests of the benchmark: rehearsal, faults, control, traces."""
