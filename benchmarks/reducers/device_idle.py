"""Share of the traced slice in which no op ran, averaged over the
chips: 100 * (1 - union of device-op intervals / slice)."""


def reduce(obs):
    sliced = obs["slice"]
    if sliced is None or sliced.window_s <= 0:
        return None
    return 100.0 * (1.0 - sliced.busy_s() / sliced.window_s)
