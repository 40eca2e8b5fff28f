"""Set-up: from process start to the first timed request or step, with
loading, warm-up and any compilation."""


def read(rec):
    return rec["setup_s"]
