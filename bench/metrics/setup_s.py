"""Set-up: from process start to the first due request of the window."""


def read(rec):
    return rec["setup_s"]
