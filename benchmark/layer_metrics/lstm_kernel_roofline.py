"""The recurrence kernels' share of their roofline: the least time one
optimizer step's recurrence could take on this chip — the larger of its
FLOPs over the bf16 peak and the bytes its kernel boundary must move over
the HBM peak, both from shapes (`flops.py`) — over the time the trace gives
the kernels. The earlier line of the run says which of the two bounds."""
import flops
import trace_reduce


def read(result, cell):
    import jax

    measured = trace_reduce.kernel_seconds_per_step(result, cell)
    if not measured:
        return None
    m = cell.config["model"]
    rows = result["batch"] // cell.chips          # one chip's rows
    peak = flops.peaks(jax.devices()[0].device_kind)
    compute_bytes = 2 if m["compute_dtype"] == "bfloat16" else 4
    t_flops = flops.recurrence_train_flops_per_step(
        rows, result["seq_len"], m["hidden_size"], m["num_layers"]
    ) / (peak["bf16_tflops"] * 1e12)
    t_bytes = flops.recurrence_train_bytes_per_step(
        rows, result["seq_len"], m["hidden_size"], m["num_layers"], compute_bytes
    ) / (peak["hbm_gbytes_per_s"] * 1e9)
    result["samples"]["lstm_kernel_roofline"] = (
        f"bound by {'compute' if t_flops >= t_bytes else 'HBM bytes'}: "
        f"{1e3 * t_flops:.4f} ms of FLOPs, {1e3 * t_bytes:.4f} ms of bytes, "
        f"{1e3 * measured:.4f} ms measured per step")
    return 100.0 * max(t_flops, t_bytes) / measured
