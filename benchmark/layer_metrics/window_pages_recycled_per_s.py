"""Window pages that running sessions returned to the free list, a second of
the window: the cache's counter at its two edges."""


def read(result, cell):
    pair = result.get("counters")
    if not pair or "window_pages_recycled" not in pair[1].get("cache", {}):
        return None
    c0, c1 = pair
    return (c1["cache"]["window_pages_recycled"]
            - c0["cache"]["window_pages_recycled"]) / result["window_s"]
