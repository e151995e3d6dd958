"""Milliseconds per optimizer step in the Mosaic recurrence kernels (forward
and backward, every layer), from the device trace of chip 0: the operations
whose HLO opcode is ``custom-call``. The program gives its kernels no names of
their own yet (PERF.md §7), so a second family of Pallas kernels in the
train step would be counted here too."""
import trace_reduce


def read(result, cell):
    s = trace_reduce.kernel_seconds_per_step(result, cell)
    return None if not s else 1e3 * s
