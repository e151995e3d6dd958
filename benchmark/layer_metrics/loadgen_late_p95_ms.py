"""How late the generator's requests left against their schedule, 95th
percentile over the requests due in the window."""
import flops


def read(result, cell):
    late = [o.late_s for o in result["outcomes"] if o.sent_at == o.sent_at]
    return 1e3 * flops.percentile(late, 95) if late else None
