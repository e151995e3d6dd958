"""Percent of the (token, expert) pairs the router made in the window that
landed on the experts held here: 25 if the router were even over the four
chips' 160 experts."""
import decoder_readers


def read(result, cell):
    d = decoder_readers.delta(result, "counters", "decoder")
    if not d or not d["moe_pairs_total"]:
        return None
    return 100.0 * d["moe_pairs_here"] / d["moe_pairs_total"]
