"""Device busy time in the traced slice over a program counter read
across the same slice (``counter``), in milliseconds: for serving, the
device time one dispatch costs."""


def reduce(obs, counter):
    sliced, n = obs["slice"], obs["counters"].get(counter, 0)
    if sliced is None or not n:
        return None
    return sliced.busy_s() * 1e3 / n
