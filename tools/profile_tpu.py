"""Phase-level TPU profiling for the boosting hot path.

Measures, on the real chip:
  * grow() device time (blocked, steady-state)
  * objective gradient + tail dispatch overhead
  * full booster.update() loop throughput
at several (rows, leaves) points to see how cost scales.

Run: python tools/profile_tpu.py
"""
from __future__ import annotations

import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def sync(x):
    import jax
    jax.block_until_ready(x)
    # execution barrier: a host pull
    import jax.numpy as jnp
    return float(jnp.sum(x[0]) if hasattr(x, "__getitem__") else jnp.sum(x))


def profile_point(n_rows: int, num_leaves: int, iters: int = 8):
    import jax
    import jax.numpy as jnp
    import lightgbm_tpu as lgb
    from bench import make_higgs_like

    x, y = make_higgs_like(n_rows)
    train = lgb.Dataset(x, label=y, params={"max_bin": 255})
    params = {"objective": "binary", "num_leaves": num_leaves,
              "learning_rate": 0.1, "verbosity": -1, "max_bin": 255}
    booster = lgb.Booster(params=params, train_set=train)
    inner = booster._inner

    # ---- steady-state grow() alone ----
    g, h = inner._compute_gradients(inner.get_training_score())
    inbag = inner._valid_rows
    fm = inner._feature_mask(0)
    args = (inner.dd.bins, g[0], h[0], inbag, fm, inner.dd.num_bins,
            inner.dd.has_nan, inner.dd.is_cat, 0)
    ta, leaf_id = inner.grow(*args)   # compile
    sync(leaf_id)
    t0 = time.perf_counter()
    reps = 4
    for _ in range(reps):
        ta, leaf_id = inner.grow(*args)
    sync(leaf_id)
    grow_t = (time.perf_counter() - t0) / reps

    # ---- gradient compute alone ----
    t0 = time.perf_counter()
    for _ in range(reps):
        g, h = inner._compute_gradients(inner.get_training_score())
    sync(g)
    grad_t = (time.perf_counter() - t0) / reps

    # ---- full update loop ----
    for _ in range(2):
        booster.update()
    sync(inner.train_score)
    t0 = time.perf_counter()
    for _ in range(iters):
        booster.update()
    sync(inner.train_score)
    full_t = (time.perf_counter() - t0) / iters

    print(f"rows={n_rows} leaves={num_leaves}: "
          f"grow={grow_t*1e3:.1f}ms grad={grad_t*1e3:.1f}ms "
          f"full_iter={full_t*1e3:.1f}ms "
          f"(tail+dispatch={max(full_t-grow_t-grad_t,0)*1e3:.1f}ms)")


def main():
    for n_rows, leaves in [(1_000_000, 255), (1_000_000, 63),
                           (250_000, 255), (250_000, 63),
                           (1_000_000, 31)]:
        profile_point(n_rows, leaves)


if __name__ == "__main__":
    main()
