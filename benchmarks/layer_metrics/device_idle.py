"""Share of the traced window in which no operation ran on the device: the
reader of `device_idle.train` and `device_idle.serve` alike."""


def read(view):
    if not any(view.events):
        return None
    return 100.0 * (1.0 - view.busy_s / view.window_s)
