"""The window over the steps completed in it; the window ends when the last
step's result is ready on the device."""


def read(rec):
    return 1e3 * rec["window_s"] / rec["steps"]
