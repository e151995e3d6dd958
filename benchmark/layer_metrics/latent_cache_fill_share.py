"""Percent of the latent pool's pages held by sessions when the window
closed."""


def read(result, cell):
    pair = result.get("counters")
    if not pair or "cache" not in pair[1]:
        return None
    c = pair[1]["cache"]
    return 100.0 * c["latent_pages_in_use"] / c["latent_pages_total"]
