"""Backend compiles (cache fetches included) after the window opened. Must
be 0; the cell reports `correct: false` otherwise."""


def read(result, cell):
    return float(len(result["late_compiles"]))
