"""Set-up: from the start of the process to the opening of the window
(imports, weights, index, data, compilation or cache loads, warm-up)."""


def read(rec):
    return rec.get("setup_s")
