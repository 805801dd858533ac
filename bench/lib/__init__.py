"""The benchmark's harness: spec loading, the device guard, the loops that
drive the program, the check that decides ``correct``, and the reduction
of a profiler trace to device busy time."""
