"""Set-up: from the start of the run to the opening of the window
(loading, building the fabric, warming every shape, compiling)."""


def read(rec):
    return rec["setup_s"]
