"""Set-up: process start to the window's start (imports, weights, engine,
warm-up and any compilation)."""
UNIT = "s"


def read(rec):
    return rec["setup_s"]
