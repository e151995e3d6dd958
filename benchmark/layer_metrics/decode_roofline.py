"""The decode programs' share of their roofline: the bytes one decode step
must read whatever the batch (layer weights and the head, at the size they
are stored in, from shapes) over the HBM peak, over the device time the
trace gives one decode step: busy time inside the decode-window programs
over the decode steps the batcher's counters say were dispatched between the
trace's edges. Decode at these batch sizes is bound by bytes, not FLOPs."""
import re

import flops
import serve_cell
import trace_reduce


def read(result, cell):
    import jax

    w = trace_reduce.traced_window(result, cell)
    if w is None or "trace_counters" not in result:
        return None
    trace, lo, hi, chips = w
    c0, c1 = result["trace_counters"]
    steps = sum(k * n for k, n in serve_cell.windows_between(c0, c1).items())
    rx = re.compile(serve_cell.DECODE_PROGRAM)
    busy = sum(trace_reduce.busy_seconds(chips[0], max(m.start, lo), min(m.end, hi))
               for m in chips[0].modules
               if rx.search(m.name) and m.end > lo and m.start < hi)
    if not steps or not busy:
        return None
    m = cell.config["model"]
    peak = flops.peaks(jax.devices()[0].device_kind)
    least = flops.decode_step_bytes(
        m["vocab_size"], m["hidden_size"], m["num_layers"], m.get("embed_size"),
        result["param_bytes"]) / (peak["hbm_gbytes_per_s"] * 1e9)
    result["samples"]["decode_roofline"] = (
        f"{steps} decode steps, {busy:.4f} s busy in decode programs, "
        f"{1e3 * least:.4f} ms least per step at {result['param_bytes']} "
        "bytes a parameter")
    return 100.0 * least / (busy / steps)
