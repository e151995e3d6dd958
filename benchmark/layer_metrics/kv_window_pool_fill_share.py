"""Percent of the K/V cache's window pages held by sessions when the window
closed."""


def read(result, cell):
    pair = result.get("counters")
    if not pair or "window_pages_total" not in pair[1].get("cache", {}):
        return None
    c = pair[1]["cache"]
    return 100.0 * c["window_pages_in_use"] / c["window_pages_total"]
