"""Analytical per-phase HBM-bytes / FLOPs cost model (ISSUE 5
tentpole 2).

Generalizes ``tools/profile_partition.py``'s per-point
``dma_bytes_per_logical_row`` accounting into one module every
consumer shares: the kernel-level byte formulas below are EXACT
contracts (pinned against the kernel-contract tests in
``tests/test_obs_tools.py``, which derive the same numbers
independently from the row-movement oracle in
``tests/test_partition_perm.py``), and the phase-level aggregates turn
a traced bench record's device counters into predicted bytes/FLOPs
that ``python -m lightgbm_tpu.obs report --roofline`` joins with the
measured phase walls.

Byte contracts (physical comb layout, ``ops/pallas/layout.py``):

* every logical row occupies one comb line: ``C_phys * itemsize``
  bytes, ``C_phys`` a whole number of 128-lane planes;
* a partition split over ``cnt`` rows streams each row through the
  scan once (1 read + 1 write: left rows land in place, right rows in
  scratch) and the copyback moves the right segment back
  (1 read + 1 write of ``cnt - nleft`` rows);
* a comb-direct histogram build reads each in-window row once and
  writes the [f_pad, padded_bins, 2] f32 histogram once (accumulation
  lives in VMEM);
* the fused split kernel pays the partition traffic plus ONE child's
  histogram write (ISSUE 30: the child the finder's record calls
  smaller; zeros where its hook was skipped).  A comb-direct build of
  the smaller child follows at a split whose record named the larger
  child (``side_miss_splits`` of them, ``rows_rehistogrammed`` rows in
  all) and at every split whose parent is past the hook's crossover
  (ISSUE 35: ``splits - hook_splits`` of them; their children's rows
  are not counted apart and are taken as the smaller children's rows
  in the share of the row visits that were not hooked) - so the
  smaller-child re-read the unfused pipeline pays at every split is
  what the hook deletes, where it runs and up to the misses;
* a stream refresh pass reads and rewrites every comb line once
  (plus one root-histogram write when the fused root carry is on).

FLOPs are documented estimates, not contracts: the MXU work of the
one-hot contractions (2 flops per MAC), good to the leading term.
"""
from __future__ import annotations

import os
import sys
from typing import Any, Dict, List, Optional

LANE = 128          # ops/pallas/layout.py contract (no jax import here)
HIST_CH = 2         # grad / hess histogram channels
F32 = 4             # histogram accumulator width (always f32)

# What is known of a chip, keyed by the ``device_kind`` JAX reports and
# a record's provenance block carries: its generation (the key of the
# VMEM/HBM size tables below) and its roofline peaks.  Source: Google
# Cloud documentation, "TPU v5e" (819 GB/s HBM, 197 TFLOP/s bf16).  A
# kind that is not in the table is an error, never a v5e default; a
# roof can still be named explicitly per run (env) or per report
# (--peak-bw / --peak-tflops).
PEAK_BW_ENV = "LGBM_TPU_PEAK_BW_GBPS"
PEAK_TFLOPS_ENV = "LGBM_TPU_PEAK_TFLOPS"
DEVICE_KINDS = {
    "TPU v5 lite": {"gen": "v5e", "bw_gbps": 819.0, "tflops": 197.0},
}
# off-chip (the static analyzer, CPU tests) the VMEM/HBM budgets
# describe the chip this repo is built for, not the host
ANALYSIS_TARGET_GEN = "v5e"


def device_generation() -> str:
    """Generation the VMEM/HBM budgets are priced for: the live TPU's,
    by its ``device_kind``; off-chip, the analysis target."""
    jax = sys.modules.get("jax")   # no jax import from a cost model
    dev = jax.devices()[0] if jax is not None else None
    if dev is None or dev.platform != "tpu":
        return ANALYSIS_TARGET_GEN
    if dev.device_kind not in DEVICE_KINDS:
        raise ValueError(
            f"unknown TPU device_kind {dev.device_kind!r}: add it to "
            f"costmodel.DEVICE_KINDS (known: {sorted(DEVICE_KINDS)}) or "
            f"set {VMEM_GEN_ENV}/{HBM_GEN_ENV}")
    return DEVICE_KINDS[dev.device_kind]["gen"]


def _generation(gen: Optional[str], env_name: str) -> str:
    g = gen or os.environ.get(env_name)
    return (device_generation() if g in (None, "", "auto") else g).lower()

# ---------------------------------------------------------------------
# VMEM budget (the static analyzer's vmem-budget pass, ISSUE 7).
# Physical VMEM per core by generation; consistent with the on-chip
# evidence in ops/pallas/apply_find.py (Mosaic compiled a 78.4 MB
# scoped need under a 96 MiB limit on v5e).  The usable BUDGET keeps a
# reserve below the physical size: Mosaic packs its own pipeline
# buffers and temporaries around explicit allocations, so a kernel
# sized to 100% of VMEM fails in practice.  Override the generation
# with LGBM_TPU_VMEM_GEN, or pin an absolute budget with
# LGBM_TPU_VMEM_LIMIT_MB.
# ---------------------------------------------------------------------
VMEM_GEN_ENV = "LGBM_TPU_VMEM_GEN"
VMEM_LIMIT_ENV = "LGBM_TPU_VMEM_LIMIT_MB"
VMEM_BYTES_BY_GEN = {
    "v4": 128 << 20,
    "v5e": 128 << 20,
    "v5p": 128 << 20,
}
VMEM_RESERVE_FRACTION = 0.25     # compiler headroom below physical


def vmem_generation_bytes(gen: Optional[str] = None):
    """(physical VMEM bytes, generation name) for ``gen``, else
    LGBM_TPU_VMEM_GEN, else the device's generation."""
    g = _generation(gen, VMEM_GEN_ENV)
    if g not in VMEM_BYTES_BY_GEN:
        raise ValueError(
            f"unknown TPU generation {g!r} for the VMEM budget; known: "
            f"{sorted(VMEM_BYTES_BY_GEN)} (or set {VMEM_LIMIT_ENV})")
    return VMEM_BYTES_BY_GEN[g], g


def vmem_limit_bytes(gen: Optional[str] = None) -> int:
    """Usable per-kernel VMEM budget: LGBM_TPU_VMEM_LIMIT_MB when set,
    else physical VMEM minus the compiler reserve."""
    env_mb = os.environ.get(VMEM_LIMIT_ENV, "")
    if env_mb and env_mb.lower() != "off":
        return int(float(env_mb) * 2**20)
    phys, _ = vmem_generation_bytes(gen)
    return int(phys * (1.0 - VMEM_RESERVE_FRACTION))


def buffer_bytes(shape, itemsize: int) -> int:
    """Bytes of one dense buffer (the analyzer's footprint unit)."""
    n = 1
    for d in shape:
        n *= int(d)
    return n * int(itemsize)


# ---------------------------------------------------------------------
# HBM budget (the static analyzer's hbm-budget pass + obs mem, ISSUE 9)
# Physical HBM per chip by generation; the usable BUDGET keeps a small
# reserve below the physical size (the runtime's own buffers, the
# infeed/outfeed staging and XLA's temp arena live there too — a
# program sized to 100% of HBM OOMs in practice; the v5e allocator
# reports ~15.75 GiB usable of the 16 GiB part, which is exactly the
# 1/64 reserve).  Override the generation with LGBM_TPU_HBM_GEN, or
# pin an absolute budget with LGBM_TPU_HBM_LIMIT_GB (GiB, float).
# ---------------------------------------------------------------------
HBM_GEN_ENV = "LGBM_TPU_HBM_GEN"
HBM_LIMIT_ENV = "LGBM_TPU_HBM_LIMIT_GB"
HBM_BYTES_BY_GEN = {
    "v4": 32 << 30,
    "v5e": 16 << 30,
    "v5p": 96 << 30,
}
HBM_RESERVE_FRACTION = 1.0 / 64.0   # 16 GiB -> 15.75 GiB usable


def hbm_generation_bytes(gen: Optional[str] = None):
    """(physical HBM bytes, generation name) for ``gen``, else
    LGBM_TPU_HBM_GEN, else the device's generation."""
    g = _generation(gen, HBM_GEN_ENV)
    if g not in HBM_BYTES_BY_GEN:
        raise ValueError(
            f"unknown TPU generation {g!r} for the HBM budget; known: "
            f"{sorted(HBM_BYTES_BY_GEN)} (or set {HBM_LIMIT_ENV})")
    return HBM_BYTES_BY_GEN[g], g


def hbm_limit_bytes(gen: Optional[str] = None) -> int:
    """Usable per-chip HBM budget: LGBM_TPU_HBM_LIMIT_GB when set,
    else physical HBM minus the runtime reserve.  A non-positive
    override is a configuration error, not a zero budget (every
    consumer divides by / compares against this)."""
    env_gb = os.environ.get(HBM_LIMIT_ENV, "")
    if env_gb and env_gb.lower() != "off":
        limit = int(float(env_gb) * 2**30)
        if limit <= 0:
            raise ValueError(
                f"{HBM_LIMIT_ENV}={env_gb!r} is not a usable HBM "
                "budget (need a positive GiB value, or 'off' for the "
                "per-generation default)")
        return limit
    phys, _ = hbm_generation_bytes(gen)
    return int(phys * (1.0 - HBM_RESERVE_FRACTION))


def logical_row_bytes(*, itemsize: int = F32, c_phys: int = LANE) -> int:
    """Bytes one LOGICAL row moves per line touch (the
    ``dma_bytes_per_logical_row`` of profile_partition.py): a row is a
    line."""
    return c_phys * itemsize


# ---------------------------------------------------------------------
# kernel-level contracts (exact; pinned by tests/test_obs_tools.py)
# ---------------------------------------------------------------------
def partition_split_bytes(cnt: int, nleft: int, *, itemsize: int = F32,
                          c_phys: int = LANE) -> int:
    """Exact HBM bytes one partition split over ``cnt`` logical rows
    moves: scan read + scan write of every row, copyback read + write
    of the ``cnt - nleft`` right-segment rows."""
    lrb = logical_row_bytes(itemsize=itemsize, c_phys=c_phys)
    return (2 * cnt + 2 * (cnt - nleft)) * lrb


def cat_bitset_words(padded_bins: int) -> int:
    """i32 words in one categorical membership bitset: one bit per
    padded bin, 32 bins per word (the packing of
    ops/predict.py:_members_to_words and the partition kernels'
    in-SMEM decode)."""
    b = int(padded_bins)
    if b <= 0:
        raise ValueError(f"padded_bins must be positive, got {b}")
    return (b + 31) // 32


def cat_bitset_bytes(padded_bins: int) -> int:
    """Exact bytes one categorical membership bitset occupies."""
    return cat_bitset_words(padded_bins) * 4


def partition_sel_bytes(padded_bins: int = 0, *,
                        cat: bool = False) -> int:
    """Exact bytes of the SMEM split descriptor one partition /
    fused-split launch carries: 8 i32 member slots, plus the
    membership bitset words when the split is a graduated
    cat-subset split (ISSUE 16)."""
    words = cat_bitset_words(padded_bins) if cat else 0
    return (8 + words) * 4


def hist_out_bytes(f_pad: int, padded_bins: int) -> int:
    """One histogram write: [f_pad, padded_bins, 2] f32."""
    return f_pad * padded_bins * HIST_CH * F32


def hist_build_bytes(cnt: int, *, f_pad: int, padded_bins: int,
                     itemsize: int = F32,
                     c_phys: int = LANE) -> int:
    """Exact HBM bytes one comb-direct histogram build over ``cnt``
    logical rows moves: each row read once + one histogram write."""
    lrb = logical_row_bytes(itemsize=itemsize, c_phys=c_phys)
    return cnt * lrb + hist_out_bytes(f_pad, padded_bins)


def fused_split_bytes(cnt: int, nleft: int, *, f_pad: int,
                      padded_bins: int,
                      itemsize: int = F32, c_phys: int = LANE,
                      rehist_rows: int = 0) -> int:
    """Exact HBM bytes one FUSED partition+histogram split moves: the
    partition traffic and the scan's ONE histogram write (the named
    child's rows are histogrammed from VMEM — no re-read); where the
    scan was told the larger child, also a comb-direct build of the
    smaller one's ``rehist_rows = min(nleft, cnt - nleft)`` rows (0:
    the scan was told the smaller child, nothing follows it)."""
    out = (partition_split_bytes(cnt, nleft,
                                 itemsize=itemsize, c_phys=c_phys)
           + hist_out_bytes(f_pad, padded_bins))
    if rehist_rows:
        out += hist_build_bytes(rehist_rows, f_pad=f_pad,
                                padded_bins=padded_bins,
                                itemsize=itemsize, c_phys=c_phys)
    return out


def unfused_split_bytes(cnt: int, nleft: int, *, f_pad: int,
                        padded_bins: int,
                        itemsize: int = F32, c_phys: int = LANE) -> int:
    """Unfused pipeline: partition, then re-read the SMALLER child for
    its histogram (subtraction trick), then one histogram write (the
    sibling comes from the subtraction, in registers)."""
    small = min(nleft, cnt - nleft)
    return (partition_split_bytes(cnt, nleft,
                                  itemsize=itemsize, c_phys=c_phys)
            + hist_build_bytes(small, f_pad=f_pad,
                               padded_bins=padded_bins,
                               itemsize=itemsize, c_phys=c_phys))


def stream_refresh_bytes(n_rows: int, *,
                         itemsize: int = F32, c_phys: int = LANE,
                         root_hist: bool = False, f_pad: int = 0,
                         padded_bins: int = 0) -> int:
    """Per-tree stream refresh: read + rewrite every comb line once;
    with the fused root carry, one extra root-histogram write."""
    lrb = logical_row_bytes(itemsize=itemsize, c_phys=c_phys)
    out = 2 * n_rows * lrb
    if root_hist:
        out += hist_out_bytes(f_pad, padded_bins)
    return out


def serving_traversal_bytes(rows: int, *, trees: int, levels: int,
                            features: int, value_bins: int = 256,
                            num_class: int = 1) -> int:
    """HBM bytes one bucketed serving dispatch moves (ISSUE 14,
    ``ops/predict.forest_scores``): the raw-row read plus the on-device
    quantize's ~log2(B) bound touches per (row, feature), then per
    traversal level one bin gather and 6 i32/bool node-field gathers
    per (row, tree) — split_feature, threshold, cat flag, two child
    pointers, and the PACKED per-node metadata word that since the
    ISSUE-15 satellite replaces the separate default_left gather plus
    the feature-indexed num_bins/has_nan re-reads — then the leaf
    gather and the donated score write.  The bench's serving block
    prices its bulk throughput against this (achieved vs predicted
    GB/s in ``obs report --roofline`` terms)."""
    import math
    quantize = rows * features * F32 * (
        1 + math.ceil(math.log2(max(value_bins, 2))))
    per_level = rows * trees * (6 * 4 + 4)
    tail = rows * trees * F32 + rows * num_class * F32
    return quantize + max(levels, 0) * per_level + tail


def serving_kernel_bytes(rows: int, *, trees: int, ni_pad: int,
                         nl_pad: int, cat_words_w: int = 0,
                         features: int, value_bins: int = 256,
                         num_class: int = 1,
                         leaf_itemsize: int = 4) -> int:
    """HBM bytes one bucketed serving dispatch moves on the
    VMEM-resident Pallas traversal path (ISSUE 18,
    ``ops/pallas/serve_kernel.py``): the raw-row read plus the
    on-device quantize's ~log2(B) bound touches per (row, feature) —
    unchanged from the gather path — then the FOREST ONCE (every node
    array DMAs HBM->VMEM a single time per dispatch,
    ``layout.serve_forest_vmem_bytes``, instead of re-streaming per
    level) and the ROW TILES ONCE (the quantized i32 bin block in,
    the donated score buffer in and the summed scores out).  Compare
    :func:`serving_traversal_bytes`: the gather walk pays
    ~28 B x rows x trees x LEVELS; this contract has no per-level
    term at all.  tests/test_serve_kernel.py equality-checks it
    against the traced kernel's actual operand/result bytes."""
    import math
    from ..ops.pallas.layout import serve_forest_vmem_bytes
    quantize = rows * features * F32 * (
        1 + math.ceil(math.log2(max(value_bins, 2))))
    forest_once = serve_forest_vmem_bytes(
        trees, ni_pad, nl_pad, cat_words_w=cat_words_w,
        leaf_itemsize=leaf_itemsize)
    rows_once = (rows * features * 4            # i32 bin block in
                 + 2 * rows * num_class * F32)  # donated buf in + out
    return quantize + forest_once + rows_once


# ---------------------------------------------------------------------
# FLOPs estimates (leading term; 2 flops per MAC)
# ---------------------------------------------------------------------
def hist_flops(cnt: int, *, f_pad: int, padded_bins: int) -> int:
    """One-hot contraction: per row, per feature, per channel a
    [1, padded_bins] MAC row."""
    return 2 * cnt * f_pad * padded_bins * HIST_CH


def partition_flops(cnt: int, *, scheme: str = "permute", R: int = 512,
                    c_phys: int = LANE) -> int:
    """Per-split compaction compute: the matmul scheme contracts a
    [R, R] one-hot per block (O(R)/row); the permute scheme pays one
    go-left matvec plus ~log2(R) select/roll rounds (O(log R)/row)."""
    lines = max(cnt, 1)
    if scheme == "matmul":
        return 2 * R * c_phys * lines
    rolls = max(int(R).bit_length() - 1, 1)
    return (2 + 2 * rolls) * c_phys * lines


def collective_bytes(kind: str, payload_bytes: int,
                     n_shards: int) -> int:
    """Per-shard ICI bytes one collective moves for a ``payload_bytes``
    buffer: ring all-reduce (psum) moves ~2(n-1)/n payloads per shard,
    reduce-scatter half that, an all-gather/pmax election (n-1)/n."""
    if n_shards <= 1:
        return 0
    frac = (n_shards - 1) / n_shards
    factor = {"psum": 2 * frac, "psum_scatter": frac,
              "pmax": frac, "all_gather": frac}.get(kind, 2 * frac)
    return int(payload_bytes * factor)


def learner_dispatch_bytes(kind: str, *, f_pad: int, padded_bins: int,
                           n_shards: int, num_leaves: int,
                           voting_top_k: int = 0) -> int:
    """Per-shard ICI bytes ONE mesh-learner grow dispatch moves — the
    analytical side of the ``obs collectives`` measured-vs-predicted
    join (ISSUE 8), recorded per dispatch by the learners' run-ledger
    rows (``parallel/data_parallel.py::_ledger_collective``).

    The dispatch runs at most ``num_leaves`` merges (root histogram +
    one per split).  The merged payload is the full [f_pad,
    padded_bins, 2] f32 histogram — except PV-tree voting, which
    bounds it to the ~2k elected features' slices plus one [f_pad]
    vote-count psum per merge.  The root grad/hess psum (3 scalars) is
    noise and deliberately excluded; a measured capture that includes
    it joins within one stat row, visibly, rather than being silently
    absorbed by a tolerance."""
    f_pad = max(int(f_pad), 1)
    if voting_top_k > 0:
        f_el = min(2 * int(voting_top_k), f_pad)
        payload = f_el * padded_bins * HIST_CH * F32 + f_pad * F32
    else:
        payload = hist_out_bytes(f_pad, padded_bins)
    return collective_bytes(kind, payload, n_shards) * int(num_leaves)


# ---------------------------------------------------------------------
# phase-level aggregation over a traced bench record
# ---------------------------------------------------------------------
class RecordModelError(ValueError):
    """A bench record lacks the fields the cost model needs (untraced,
    or pre-v3 without the ``shape`` block)."""


class RooflineNotMeasured(RecordModelError):
    """The record ran on the CPU: its walls are not device times, so
    there is no roofline share to report."""


def roofline_peak(rec: Dict[str, Any], which: str,
                  explicit: Optional[float] = None) -> float:
    """The roof a record's walls are judged against — ``which`` is
    ``"bw_gbps"`` or ``"tflops"``: the explicit value, else the env
    override, else the table entry of ``provenance.device_kind``."""
    prov = rec.get("provenance") or {}
    if "cpu" in (prov.get("backend"), rec.get("backend")):
        raise RooflineNotMeasured(
            "not measured (the record ran on the CPU; a roofline share "
            "comes only from a chip run)")
    env = {"bw_gbps": PEAK_BW_ENV, "tflops": PEAK_TFLOPS_ENV}[which]
    named = explicit or os.environ.get(env)
    if named and named != "auto":
        return float(named)
    kind = prov.get("device_kind")
    if kind not in DEVICE_KINDS:
        raise RecordModelError(
            f"no roofline peak for device_kind {kind!r} (known: "
            f"{sorted(DEVICE_KINDS)}); name the roof with "
            f"--peak-bw/--peak-tflops or {env}")
    return DEVICE_KINDS[kind][which]


def phase_model(rec: Dict[str, Any]) -> Dict[str, Dict[str, float]]:
    """Predicted per-phase bytes/FLOPs for a traced bench/v3 record.

    Needs ``rec["counters"]`` (device counters over the timed window)
    and ``rec["shape"]`` (f_pad / padded_bins / rows / trees — written
    by bench.py since bench/v3).

    Predictions are matched to what each measured span actually
    covers.  The tree grows inside ONE jitted loop, so the span the
    host can time is ``Tree::grow``, which covers every split of the
    tree: its row carries the whole-loop totals derived from the work
    counters (every split of every tree).  Per-kernel attribution of
    the loop is the device trace's (``kernel_model``); the root-scale
    sampled ``Split`` / ``ConstructHistogram`` probes that used to be
    priced here went with their producer (ISSUE 27).
    Partition copyback traffic is data-dependent (the right-segment
    size of every split), so partition rows carry ``bytes_lo`` /
    ``bytes_hi`` bounds (all-left / all-right) with ``bytes`` at the
    midpoint.
    """
    counters = rec.get("counters")
    shape = rec.get("shape")
    if not counters or not shape:
        raise RecordModelError(
            "cost model needs a TRACED bench/v3 record with 'counters' "
            "and 'shape' blocks (re-capture with LGBM_TPU_TRACE set; "
            f"got schema {rec.get('schema', '(unversioned)')!r})")
    f_pad = int(shape["f_pad"])
    padded_bins = int(shape["padded_bins"])
    scheme = str(rec.get("knobs", {}).get("partition", "permute"))
    fused = bool(rec.get("knobs", {}).get("fused", True))
    stream = bool(shape.get("stream", False))
    n_rows = int(shape.get("rows", rec.get("rows", 0)))
    trees = int(shape.get("trees", rec.get("iters", 0)))

    splits = int(counters.get("splits", 0))
    rows_part = int(counters.get("rows_partitioned", 0))
    rows_hist = int(counters.get("rows_histogrammed", 0))
    rows_rehist = int(counters.get("rows_rehistogrammed", 0))
    misses = int(counters.get("side_miss_splits", 0))
    lrb = logical_row_bytes()
    root_rows = n_rows * trees
    direct, rows_direct = _direct_builds(counters, root_rows)

    def _part_row(cnt: int) -> Dict[str, float]:
        # scan touches every partitioned row twice; copyback adds 0..2
        # more touches depending on the right-segment size
        return {
            "bytes_lo": 2 * cnt * lrb,
            "bytes_hi": 4 * cnt * lrb,
            "bytes": 3 * cnt * lrb,
            "flops": float(partition_flops(cnt, scheme=scheme)),
        }

    out: Dict[str, Dict[str, float]] = {}
    # whole-loop totals from the work counters — joined with the
    # Tree::grow wall, which is the span that covers every split.
    # Histogram traffic mirrors the per-split contracts above: fused
    # writes ONE child's histogram per split from the scan's
    # VMEM-resident blocks, and re-reads and writes again only the
    # smaller children of the side_miss_splits whose record named the
    # larger one (rows_rehistogrammed) and of the splits past the
    # hook's crossover (_direct_builds; root passes stay); unfused
    # re-reads the smaller child (rows_hist already counts it) and
    # writes ONE histogram per split (the sibling comes from the
    # subtraction, in registers) plus one per tree root.
    # These writes are deterministic, so they land in ALL of bytes /
    # bytes_lo / bytes_hi — only the partition copyback term varies.
    grow = _part_row(rows_part)
    # fused root passes cover at most the in-bag rows per tree
    # (bagging makes them fewer; rows_hist is the honest ceiling)
    hist_reads = (min(root_rows, rows_hist) + rows_rehist + rows_direct
                  if fused else rows_hist) * lrb
    hist_writes = (trees + splits + (misses + direct if fused else 0)) \
        * hist_out_bytes(f_pad, padded_bins)
    for key in ("bytes", "bytes_lo", "bytes_hi"):
        grow[key] += hist_reads + hist_writes
    grow["flops"] += hist_flops(rows_hist, f_pad=f_pad,
                                padded_bins=padded_bins)
    out["Tree::grow"] = grow
    if stream and n_rows and trees:
        out["Boosting"] = {
            "bytes": trees * stream_refresh_bytes(
                n_rows, root_hist=fused, f_pad=f_pad,
                padded_bins=padded_bins),
            "flops": 2.0 * trees * n_rows * 8,  # score+grad+hess math
        }
    return out


def _direct_builds(counters: Dict[str, Any], root_rows: int):
    """(splits, rows) of the comb-direct builds at the parents past the
    fused scan's hook crossover.  The splits are counted
    (``hook_splits``; a record from before the counter hooked them
    all); their children's rows are not counted apart: the smaller
    children's rows of the whole tree (``rows_histogrammed`` less the
    root passes) in the share of the row visits that were not hooked."""
    splits = int(counters.get("splits", 0))
    rows_part = int(counters.get("rows_partitioned", 0))
    rows_hist = int(counters.get("rows_histogrammed", 0))
    direct = splits - int(counters.get("hook_splits", splits))
    unhooked = rows_part - int(counters.get("rows_hooked", rows_part))
    child_rows = rows_hist - min(root_rows, rows_hist)
    return direct, (child_rows * unhooked // rows_part if rows_part else 0)


def kernel_model(rec: Dict[str, Any]) -> Dict[str, Dict[str, float]]:
    """Predicted HBM bytes per KERNEL CLASS (the ``obs attr``
    classifier's entries, ``xattr.KERNEL_CLASSES``) for a traced
    bench/v3 record — the device-time twin of ``phase_model``: where
    that joins predictions with measured HOST walls, this joins them
    with measured DEVICE time from an xplane capture, so achieved GB/s
    is judged on the time the kernels actually ran.

    Attribution follows the engaged path: with ``fused`` on, the scan,
    copyback and one child's histogram write execute inside the fused
    kernel, the builds of the ``side_miss_splits`` (their writes, the
    ``rows_rehistogrammed`` reads) and of the splits past the hook's
    crossover (``_direct_builds``) land on ``hist_build`` with the
    root passes (which ride ``stream_refresh`` instead when the fused
    root carry is on); unfused splits split the same traffic
    across partition_scan / partition_copyback / hist_build.  Copyback
    traffic is data-dependent, so classes that include it carry
    ``bytes_lo`` / ``bytes_hi`` bounds with ``bytes`` at the midpoint.
    Collective bytes come from the record's ledger collective rows
    (analytical ICI bytes) when present.
    """
    counters = rec.get("counters")
    shape = rec.get("shape")
    if not counters or not shape:
        raise RecordModelError(
            "cost model needs a TRACED bench/v3 record with 'counters' "
            "and 'shape' blocks (re-capture with LGBM_TPU_TRACE set; "
            f"got schema {rec.get('schema', '(unversioned)')!r})")
    f_pad = int(shape["f_pad"])
    padded_bins = int(shape["padded_bins"])
    fused = bool(rec.get("knobs", {}).get("fused", True))
    stream = bool(shape.get("stream", False))
    n_rows = int(shape.get("rows", rec.get("rows", 0)))
    trees = int(shape.get("trees", rec.get("iters", 0)))
    splits = int(counters.get("splits", 0))
    rows_part = int(counters.get("rows_partitioned", 0))
    rows_hist = int(counters.get("rows_histogrammed", 0))
    rows_rehist = int(counters.get("rows_rehistogrammed", 0))
    misses = int(counters.get("side_miss_splits", 0))
    lrb = logical_row_bytes()
    hw = hist_out_bytes(f_pad, padded_bins)
    root_rows = n_rows * trees
    direct, rows_direct = _direct_builds(counters, root_rows)

    def _exact(b: float) -> Dict[str, float]:
        return {"bytes": float(b), "bytes_lo": float(b),
                "bytes_hi": float(b)}

    out: Dict[str, Dict[str, float]] = {}
    if fused:
        # scan + copyback + ONE child's histogram write, one kernel
        out["fused_split"] = {
            "bytes_lo": 2.0 * rows_part * lrb + splits * hw,
            "bytes_hi": 4.0 * rows_part * lrb + splits * hw,
            "bytes": 3.0 * rows_part * lrb + splits * hw,
        }
        # the builds of the missed splits and of those past the
        # hook's crossover; the fused root carry builds root histograms
        # inside the refresh pass, else they are hist_build's too
        rehist = (rows_rehist + rows_direct) * lrb + (misses + direct) * hw
        out["hist_build"] = _exact(
            rehist if stream
            else rehist + min(root_rows, rows_hist) * lrb + trees * hw)
    else:
        out["partition_scan"] = _exact(2.0 * rows_part * lrb)
        out["partition_copyback"] = {
            "bytes_lo": 0.0, "bytes_hi": 2.0 * rows_part * lrb,
            "bytes": float(rows_part * lrb),
        }
        # root pass + smaller-child re-reads (rows_hist counts both),
        # one write per split (the sibling is a subtraction) + roots
        out["hist_build"] = _exact(rows_hist * lrb
                                   + (trees + splits) * hw)
    if stream and n_rows and trees:
        out["stream_refresh"] = _exact(trees * stream_refresh_bytes(
            n_rows, root_hist=fused, f_pad=f_pad,
            padded_bins=padded_bins))
    coll = sum(float(c.get("bytes_moved", 0.0))
               for c in (rec.get("ledger") or {}).get("collectives", []))
    if coll:
        out["collective"] = _exact(coll)
    return out


# ---------------------------------------------------------------------
# exact per-buffer HBM footprint model (ISSUE 9 tentpole)
#
# Prices every persistent training buffer of the physical-partition
# trained path as a closed-form function of (rows, features, bins,
# dtype, stream, n_shards) — the residency twin of the traffic
# contracts above.  The shapes here are EXACT: they reproduce the
# layout decisions ops/grow.py makes (PHYS_ROW_SLACK, comb_layout,
# stream_columns) from the same shared primitives, and
# tests/test_mem.py asserts equality against buffer sizes extracted
# from the real grow jaxprs across the planes x stream x mesh matrix.
# Per-phase live-sets make the PEAK a prediction, not a guess — the
# paged-comb refactor (ROADMAP item 5) is designed against this model
# off-chip instead of discovered on-chip by OOM.
# ---------------------------------------------------------------------
PEAK_HOST_BW_ENV = "LGBM_TPU_PEAK_HOST_BW_GBPS"
DEFAULT_PEAK_HOST_BW_GBPS = 32.0   # PCIe-class host<->HBM staging BW


def _phys_pad_and_slack():
    """(PHYS_ROW_PAD, PHYS_ROW_SLACK): the block a shard's rows pad to
    and the lines the comb carries past them - both sized for the
    largest block the scan can take, so neither depends on the one it
    takes or on the backend asked from (lazy: keeps this module
    import-light)."""
    from ..ops.grow import PHYS_ROW_PAD, PHYS_ROW_SLACK
    return int(PHYS_ROW_PAD), int(PHYS_ROW_SLACK)


def pad_rows(rows: int, n_shards: int = 1) -> int:
    """Global padded row count the physical layout allocates for
    ``rows`` real rows over ``n_shards`` row shards (to_device's
    row_pad_multiple = n_shards * grow.PHYS_ROW_PAD)."""
    r, _ = _phys_pad_and_slack()
    mult = max(int(n_shards), 1) * r
    return -(-int(rows) // mult) * mult


def _buf(shape, itemsize: int, scope: str, dtype: str,
         count: int = 1, donated: bool = False) -> Dict[str, Any]:
    return {"shape": tuple(int(d) for d in shape), "dtype": dtype,
            "count": int(count), "scope": scope, "donated": donated,
            "bytes": count * buffer_bytes(shape, itemsize)}


def comb_kernel_plan(*, features: int, max_bins: int,
                     stream_kind: Optional[str] = "binary",
                     scheme: str = "permute") -> Dict[str, Any]:
    """What each comb kernel of the physical route is built at for a
    dense table of ``features`` columns whose widest column has
    ``max_bins`` bins (``stream_kind`` None: the non-stream layout):
    the line, its planes, the tiles a comb histogram sweeps, and per
    kernel the rows a grid step moves, the scoped VMEM that block is
    priced at and the limit it is built under.  From shapes alone:
    nothing is placed on a device and nothing is compiled, so a caller
    can ask before it makes a full-size array.
    ``stageable`` is the ``comb_overwide`` routing fact's negation."""
    from ..ops.histogram import bins_per_feature_padded, feature_group_size
    from ..ops.pallas import hist_kernel2 as hk
    from ..ops.pallas import partition_kernel2 as pk
    from ..ops.pallas import stream_grad as sg
    from ..ops.pallas.layout import LANE, SCOPED_VMEM_LIMIT, comb_layout
    from ..ops.routing import NON_STREAM_EXTRA_COLS, comb_stageable
    b = bins_per_feature_padded(max_bins)
    g = feature_group_size(b)
    f_pad = -(-max(int(features), 1) // g) * g
    n_extra = (sg.stream_columns(stream_kind) if stream_kind
               else NON_STREAM_EXTRA_COLS)
    C = comb_layout(f_pad + n_extra)
    h_rows = hk.hist_block_rows(C)
    scan_r = pk.scan_block_rows(C, scheme=scheme)
    cb = pk.copyback_block_rows(C)
    kernels = {
        "scan": (scan_r, pk.scan_vmem_bytes(scan_r, C)),
        "copyback": (cb, pk.copyback_vmem_bytes(cb, C)),
        "hist": (h_rows, hk.hist_vmem_bytes(h_rows, min(C, 2 * LANE))),
    }
    if stream_kind:
        s_r = sg.stream_block_rows(C)
        kernels["stream"] = (s_r, sg.stream_vmem_bytes(s_r, C))
    return {
        "f_pad": f_pad, "padded_bins": b, "C": C,
        "comb_planes": C // LANE, "comb_line_bytes": C * F32,
        "hist_tiles": hk.hist_tiles(f_pad, C),
        "stageable": bool(comb_stageable(C, scheme)),
        "kernels": {k: {"rows": r, "vmem_bytes": int(p),
                        "vmem_limit": SCOPED_VMEM_LIMIT}
                    for k, (r, p) in kernels.items()},
    }


def grow_footprint(*, rows: int, f_pad: int, padded_bins: int,
                   num_leaves: int,
                   stream: bool = False, fused: bool = True,
                   stream_kind: str = "binary", n_shards: int = 1,
                   num_class: int = 1, itemsize: int = F32,
                   rows_padded: bool = False,
                   bins_cols: int = 0,
                   bins_itemsize: int = 1,
                   mc_batched: bool = False) -> Dict[str, Any]:
    """Exact per-buffer HBM footprint of the physical-partition trained
    path, PER SHARD (chip residency is per chip).

    ``rows`` is the real row count unless ``rows_padded`` (then it is
    the already-padded global n_pad).  ``f_pad`` / ``padded_bins`` are
    the widths the comb and histogram pool are ALLOCATED at
    (``DeviceDataset.phys_f_pad`` / ``phys_padded_bins``): under EFB the
    bundle columns where the bundles stay in the comb (ISSUE 36), the
    unbundled logical geometry where the ingest unbundles (ISSUE 12)
    — while ``bins_cols`` / ``bins_itemsize`` price the persistent
    device bin matrix itself, which stays BUNDLED (and possibly u16)
    on the EFB path; they default to f_pad at one byte, the
    no-bundling identity.  Buffer shapes reproduce
    ops/grow.py's layout decisions exactly:

    * comb/scratch are ``n_alloc`` lines of ``C`` lanes where
      ``n_alloc = n_local + PHYS_ROW_SLACK`` and ``C`` comes from
      ``layout.comb_layout`` over ``f_pad`` plus the value/rid
      extras (6, or ``stream_columns(kind)`` in stream mode);
    * the histogram arena is the grow loop's ``[L, f_pad, 4, B]`` pool
      (channel-second chan4 layout), live only during ``Tree::grow``;
    * stream+fused carries the ``[f_pad, B, 2]`` root histogram across
      grow calls (donated, like comb/scratch);
    * ``mc_batched`` prices the batched multiclass grow (ISSUE 19):
      the scan-over-K program STACKS its outputs — leaf_id becomes
      ``[K, n_local]`` and the tree arrays carry a leading ``[K]``
      axis — but the histogram arena stays the single
      ``[L, f_pad, 4, B]`` pool, because the scan body's arena is
      allocated once and reused across the K classes (one XLA buffer,
      not ``[K, L, F, 4, B]``; the footprint-vs-jaxpr equality test
      pins this against the traced program);
    * phase live-sets sum what is resident per phase; ``peak_bytes``
      is the max — the number ``obs mem`` joins against the measured
      allocator peak and the hbm-budget pass checks against the
      per-generation budget.
    """
    from ..ops.pallas.layout import comb_layout
    phys_r, slack = _phys_pad_and_slack()
    n_shards = max(int(n_shards), 1)
    n_pad = int(rows) if rows_padded else pad_rows(rows, n_shards)
    if n_pad % n_shards:
        raise ValueError(
            f"padded rows {n_pad} not divisible by n_shards={n_shards}")
    n_local = n_pad // n_shards
    if n_local % phys_r:
        raise ValueError(
            f"per-shard rows {n_local} not a multiple of the largest "
            f"partition block, {phys_r} rows (pass real rows, or pad to "
            f"the layout)")
    if stream:
        from ..ops.pallas.stream_grad import N_CONSTS, stream_columns
        n_extra = stream_columns(stream_kind)
        n_consts = N_CONSTS[stream_kind]
    else:
        n_extra, n_consts = 6, 0
    C = comb_layout(f_pad + n_extra)
    n_alloc = n_local + slack
    L = int(num_leaves)
    dt_name = "bfloat16" if itemsize == 2 else "float32"

    bufs: Dict[str, Dict[str, Any]] = {}
    bufs["comb"] = _buf((n_alloc, C), itemsize, "persistent",
                        dt_name, donated=True)
    bufs["scratch"] = _buf((n_alloc, C), itemsize, "persistent",
                           dt_name, donated=True)
    _bc = int(bins_cols) or int(f_pad)
    _bi = max(int(bins_itemsize), 1)
    bufs["bins"] = _buf((n_local, _bc), _bi, "persistent",
                        "uint16" if _bi == 2 else "uint8")
    bufs["score"] = _buf((n_local,), F32, "persistent", "float32",
                         count=num_class)
    bufs["label"] = _buf((n_local,), F32, "persistent", "float32")
    bufs["valid_rows"] = _buf((n_local,), F32, "persistent", "float32")
    if not stream:
        bufs["grad"] = _buf((n_local,), F32, "iteration", "float32",
                            count=num_class)
        bufs["hess"] = _buf((n_local,), F32, "iteration", "float32",
                            count=num_class)
        bufs["inbag"] = _buf((n_local,), F32, "iteration", "float32")
    if stream and fused:
        bufs["root_hist"] = _buf((f_pad, padded_bins, HIST_CH), F32,
                                 "persistent", "float32", donated=True)
    # grow-scoped (live inside the jitted tree-growth loop only)
    # mc_batched: hist_pool stays a SINGLE arena — the scan body
    # allocates it once and XLA reuses the buffer across the K classes
    bufs["hist_pool"] = _buf((L, f_pad, 4, padded_bins), F32, "grow",
                             "float32")
    k_stack = max(int(num_class), 1) if mc_batched else 1
    bufs["leaf_id"] = _buf((n_local,), 4, "grow", "int32",
                           count=k_stack)
    ni = max(L - 1, 1)
    tree_bytes = (ni * (7 * 4 + 2 * 1)   # 7 i32/f32 + 2 bool per node
                  + 3 * 4 * ni           # internal value/weight/count
                  + 3 * 4 * L            # leaf value/weight/count
                  + 4                    # num_leaves scalar
                  + 4)                   # cat_members [1, 1] (subset off)
    bufs["tree_arrays"] = {"shape": (L,), "dtype": "mixed",
                           "count": k_stack,
                           "scope": "grow", "donated": False,
                           "bytes": tree_bytes * k_stack}
    # init-scoped: building the comb allocates its output while the
    # zeros/bins inputs are alive (no donation on the one-time init)
    bufs["comb_init_tmp"] = _buf((n_alloc, C), itemsize, "init",
                                 dt_name)
    if stream:
        bufs["stream_aux"] = _buf((2 + n_consts, n_local), F32, "init",
                                  "float32")

    persistent = sum(b["bytes"] for b in bufs.values()
                     if b["scope"] in ("persistent", "iteration"))
    grow_extra = sum(b["bytes"] for b in bufs.values()
                     if b["scope"] == "grow")
    init_extra = sum(b["bytes"] for b in bufs.values()
                     if b["scope"] == "init")
    phase_live = {
        "Init": persistent + init_extra,
        "BeforeTrain": persistent,
        "Tree::grow": persistent + grow_extra,
        # UpdateScore: the async tail allocates the new score while the
        # old class slice is alive, with leaf_id/tree still held (the
        # full [K]-stacked outputs when mc_batched — the per-class
        # tails slice a device array the host still references)
        "UpdateScore": persistent + bufs["leaf_id"]["bytes"]
        + bufs["tree_arrays"]["bytes"]
        + bufs["score"]["bytes"] // max(num_class, 1),
    }
    peak_phase = max(phase_live, key=lambda k: phase_live[k])
    return {
        "geometry": {
            "rows": n_pad, "n_local": n_local, "n_alloc": n_alloc,
            "f_pad": int(f_pad), "padded_bins": int(padded_bins),
            "C": C, "n_extra": n_extra,
            "bins_cols": _bc, "bins_itemsize": _bi,
            "num_leaves": L, "stream": bool(stream),
            "fused": bool(fused), "n_shards": n_shards,
            "itemsize": int(itemsize),
            "num_class": max(int(num_class), 1),
            "mc_batched": bool(mc_batched),
        },
        "buffers": bufs,
        "phase_live": phase_live,
        "peak_phase": peak_phase,
        "peak_bytes": phase_live[peak_phase],
        "persistent_bytes": persistent,
    }


def page_schedule(*, rows: int, f_pad: int, padded_bins: int = 256,
                  num_leaves: int = 255,
                  stream: bool = True, fused: bool = True,
                  stream_kind: str = "binary",
                  n_shards: int = 1, num_class: int = 1,
                  itemsize: int = F32,
                  limit_bytes: Optional[int] = None,
                  rows_per_page: Optional[int] = None,
                  host_bw_gbps: Optional[float] = None,
                  force: bool = False,
                  ) -> Dict[str, Any]:
    """Page geometry for a larger-than-HBM training shape — the
    off-chip design artifact ROADMAP item 5 is written against.

    When the unpaged footprint fits the budget, returns
    ``{"paged": False, ...}`` — unless ``force`` (the
    ``LGBM_TPU_PAGED=1`` override: CI's tiny-budget forced-paged runs
    page a shape that fits, so the schedule must still be planned) or
    an explicit ``rows_per_page``.  Otherwise picks (or validates) a
    rows-per-page that fits THREE comb-line page buffers in the budget
    — the compute page's comb + its partition scratch + one inbound
    double-buffer page for the host->HBM prefetch — on top of the
    fixed overhead (histogram arena, tree state, carried root
    histogram), and prices the per-tree host<->HBM DMA: every page is
    read and written once per partition LEVEL (splits are
    level-synchronous over the resident page) plus once for the fused
    refresh+root pass, at ``LGBM_TPU_PEAK_HOST_BW_GBPS`` (PCIe-class
    staging, not the on-chip HBM roofline).
    """
    phys_r, slack = _phys_pad_and_slack()
    limit = int(limit_bytes or hbm_limit_bytes())
    host_bw = float(host_bw_gbps
                    or os.environ.get(PEAK_HOST_BW_ENV,
                                      DEFAULT_PEAK_HOST_BW_GBPS))
    # stream_kind matters: the streaming layouts carry per-objective
    # constant columns (binary 13 extras, l2 15), and near the lane
    # boundary that decides the comb line width C — a plan priced at
    # the wrong kind would fail the grower's geometry check
    # paged multiclass trains serial-K (the mc_batch_paged routing
    # rule), so the K classes multiply the per-class vectors but the
    # grow outputs are never [K]-stacked here: mc_batched=False
    full = grow_footprint(rows=rows, f_pad=f_pad,
                          padded_bins=padded_bins,
                          num_leaves=num_leaves,
                          stream=stream, fused=fused,
                          stream_kind=stream_kind,
                          n_shards=n_shards,
                          num_class=max(int(num_class), 1),
                          itemsize=itemsize)
    geo = full["geometry"]
    out: Dict[str, Any] = {
        "rows": int(rows), "n_local": geo["n_local"],
        "limit_bytes": limit, "unpaged_peak_bytes": full["peak_bytes"],
        "host_bw_gbps": host_bw,
    }
    if (full["peak_bytes"] <= limit and rows_per_page is None
            and not force):
        out.update({"paged": False, "fits": True})
        return out
    lrb = geo["C"] * itemsize
    # fixed overhead: everything in the full footprint that is NOT a
    # comb-scale buffer (pool, tree state, root carry, per-row vectors
    # shrink to page scale and are dominated by the page buffers)
    fixed = sum(b["bytes"] for name, b in full["buffers"].items()
                if name in ("hist_pool", "tree_arrays", "root_hist"))

    def _resident(rpp: int) -> int:
        page_alloc = rpp + slack
        page_bytes = page_alloc * lrb
        # compute page comb + partition scratch + inbound prefetch page
        return fixed + 3 * page_bytes

    if rows_per_page is None:
        budget_for_pages = limit - fixed
        if budget_for_pages <= 3 * slack * lrb:
            out.update({"paged": True, "fits": False,
                        "error": "fixed overhead alone exceeds the HBM "
                                 "budget — shrink num_leaves or bins"})
            return out
        rpp = (budget_for_pages // (3 * lrb)) - slack
        rpp = max((rpp // phys_r) * phys_r, phys_r)
    else:
        # the planner's own pages are whole blocks of the largest scan
        # step (above); a caller's need only be whole blocks of the
        # smallest - the grower, which knows the block its scan takes,
        # refuses what does not divide by that one (ops/grow.py)
        from ..ops.pallas.layout import SCAN_ROWS_MIN
        rpp = int(rows_per_page)
        if rpp % SCAN_ROWS_MIN:
            raise ValueError(
                f"rows_per_page must be a multiple of the partition "
                f"block, at least {SCAN_ROWS_MIN} rows")
    n_pages = -(-geo["n_local"] // rpp)
    levels = max(int(num_leaves - 1).bit_length(), 1)
    sweeps = levels + 1      # per-level partition passes + fused refresh
    dma_per_tree = sweeps * 2 * geo["n_local"] * lrb
    # fixed page-buffer size in comb LINES (the PageStore contract:
    # owned rows + the kernels' DMA-tail slack, clamped to the window)
    page_lines = min(rpp + slack, geo["n_alloc"])
    out.update({
        "paged": True,
        "rows_per_page": rpp,
        "n_pages": int(n_pages),
        "page_bytes": page_lines * geo["C"] * itemsize,
        "page_lines": int(page_lines),
        "C": geo["C"],
        "n_alloc": geo["n_alloc"],
        "resident_bytes": _resident(rpp),
        "fits": _resident(rpp) <= limit,
        "sweeps_per_tree": sweeps,
        "dma_bytes_per_tree": int(dma_per_tree),
        "overhead_s_per_tree": dma_per_tree / (host_bw * 1e9),
    })
    return out


def roofline_table(rec: Dict[str, Any], *,
                   peak_bw_gbps: Optional[float] = None,
                   peak_tflops: Optional[float] = None
                   ) -> List[Dict[str, Any]]:
    """Join predicted phase bytes/FLOPs with the record's measured
    phase walls into roofline-utilization rows (one per phase that has
    both a prediction and a measured wall)."""
    model = phase_model(rec)
    peak_bw = roofline_peak(rec, "bw_gbps", peak_bw_gbps)
    peak_tf = roofline_peak(rec, "tflops", peak_tflops)
    phases = rec.get("phases", {})
    rows: List[Dict[str, Any]] = []
    for name, pred in model.items():
        meas = phases.get(name)
        wall = float(meas.get("total_s", 0.0)) if isinstance(meas, dict) \
            else 0.0
        row: Dict[str, Any] = {
            "phase": name,
            "pred_gb": pred["bytes"] / 1e9,
            "pred_gflop": pred["flops"] / 1e9,
            "wall_s": wall,
        }
        if wall > 0:
            bw = pred["bytes"] / wall / 1e9
            tf = pred["flops"] / wall / 1e12
            row["gbps"] = bw
            row["bw_util"] = bw / peak_bw
            row["flops_util"] = tf / peak_tf
            row["bound"] = ("memory" if row["bw_util"] >= row[
                "flops_util"] else "compute")
        rows.append(row)
    return rows
