"""Share of one chip's busy time spent in ops whose names hold any of
``patterns`` (self time: an op's own, outside the ops nested in it)."""


def reduce(obs, patterns, device=0):
    sliced = obs["slice"]
    if sliced is None or device not in sliced.devices:
        return None
    ops = sliced.devices[device]
    busy = ops.busy_ns()
    if busy <= 0:
        return None
    hit = sum(ns for name, ns in ops.self_ns_by_name().items()
              if any(p in name for p in patterns))
    return 100.0 * hit / busy
