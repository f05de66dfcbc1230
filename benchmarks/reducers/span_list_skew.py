"""How far the fullest shard is above the mean, over the window's spans
named ``span`` that carry the per-shard list ``arg``: ``scale`` x (sum
of each list's largest entry / sum of each list's mean - 1).  With
``Tree::grow.shard_rows_partitioned`` (each shard's own sum of parent
rows over the tree's splits) it is the share of scan work the fullest
shard does beyond its even share, a floor on what the others wait.  0
is a reading: every shard did the same.  None where no span carries the
list: a program that does not count by shard."""


def reduce(obs, span, arg, scale=1.0):
    lists = [e["args"][arg] for e in obs["spans"]
             if e["name"] == span and e.get("args", {}).get(arg)]
    mean = sum(sum(v) / len(v) for v in lists)
    if not mean:
        return None
    return scale * (sum(max(v) for v in lists) / mean - 1.0)
