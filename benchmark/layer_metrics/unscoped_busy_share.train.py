"""Share of chip 0's busy time in the train program's runs (traced window)
spent in operations under no device scope and no kernel name: what the
per-layer times `train_*_ms_per_step` and `dp_sync_ms_per_step` leave
unattributed. The split by scope, in ms per step, and the fusions that hold
more than one scope go to ``samples.ms_per_step_by_scope``
(`op_scopes.py`)."""
import op_scopes


def read(result, cell):
    split = op_scopes.breakdown(result, cell)
    if split is None or not split["busy_ms_per_step"]:
        return None
    result["samples"]["ms_per_step_by_scope"] = split
    return 100.0 * split["ms_per_step"].get("unscoped", 0.0) / \
        split["busy_ms_per_step"]
