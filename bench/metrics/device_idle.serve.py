"""Share of the traced window in which no operation ran on the device, in %."""


def read(layer):
    if layer.window_s <= 0.0 or layer.trace["n_devices"] == 0:
        return None
    return 100.0 * (1.0 - layer.trace["busy_s"] / layer.window_s)
