"""Each completed reply's own queue phase (`Request.phase_summary_ms`), 95th
percentile over the requests due in the window."""
import flops


def read(result, cell):
    waits = [o.phases_ms.get("queue_ms", 0.0) for o in result["outcomes"] if o.ok]
    return flops.percentile(waits, 95) if waits else None
