"""Device idle share of the traced slice, %: 1 - busy / window, where busy
is the union of the first chip's operation intervals in the slice."""


def read(sl):
    if sl.busy_s <= 0 or sl.window_s <= 0:
        return None
    return 100.0 * (1.0 - sl.busy_s / sl.window_s)
