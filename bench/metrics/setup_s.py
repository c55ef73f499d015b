"""Set-up: from process start to the window's first query (host clock)."""


def read(rec):
    return rec["setup_s"]
