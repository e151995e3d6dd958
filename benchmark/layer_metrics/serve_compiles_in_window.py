"""Programs the engine compiled between the window's edges (`/stats`
``compiles``). Must be 0; the cell reports `correct: false` otherwise."""


def read(result, cell):
    c0, c1 = result["counters"]
    return float(c1["compiles"] - c0["compiles"])
