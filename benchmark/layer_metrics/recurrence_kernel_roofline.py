"""The recurrence kernels' share of their roofline, as
`lstm_kernel_roofline` computes it — the larger of one optimizer step's
recurrence FLOPs over the bf16 peak and the bytes its kernel boundary must
move over the HBM peak (`flops.py`) — over the time
`recurrence_kernel_ms_per_step` gives the kernels named ``lstm_rec_``
alone. The earlier line of the run says which of the two bounds."""
import flops
import op_scopes


def read(result, cell):
    import jax

    ms = op_scopes.ms_per_step(
        result, cell, lambda e, scope: op_scopes.RECURRENCE in e.label)
    if not ms:
        return None
    measured = ms / 1e3
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
    result["samples"]["recurrence_kernel_roofline"] = (
        f"bound by {'compute' if t_flops >= t_bytes else 'HBM bytes'}: "
        f"{1e3 * t_flops:.4f} ms of FLOPs, {1e3 * t_bytes:.4f} ms of bytes, "
        f"{ms:.4f} ms measured per step")
    return 100.0 * max(t_flops, t_bytes) / measured
