"""The longest the benchmark's own 20 ms heartbeat overslept inside the
window (`serve_cell.Watch`). A pause of the whole process, by the host's
scheduler or a stopped machine, shows here; a stall of the program or the
device does not, and shows in `delivery_gap_max_ms` alone."""
import math


def read(result, cell):
    late = result["host_pause_max_s"]
    return 1e3 * late if math.isfinite(late) else None
