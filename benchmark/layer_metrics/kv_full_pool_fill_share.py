"""Percent of the K/V cache's full pages held by sessions when the window
closed."""


def read(result, cell):
    pair = result.get("counters")
    if not pair or "full_pages_total" not in pair[1].get("cache", {}):
        return None
    c = pair[1]["cache"]
    return 100.0 * c["full_pages_in_use"] / c["full_pages_total"]
