"""Block-partition logic: the paper's 4x4 register blocking adapted to TPU tiles.

The paper (S4.3.5) blocks GEMM into 4x4 register-resident tiles because the PE
has 64 FP registers (3*n^2 registers for an n-block => n=4).  On TPU the same
argument runs against VMEM and the MXU: tiles must be multiples of the
(8 sublane x 128 lane) vector registers, matmul tiles multiples of 128 on the
contracting/output dims to fill the 128x128 systolic array, and the working
set  bm*bk + bk*bn + bm*bn (+ f32 accumulator)  must fit the VMEM budget.

`choose_block_shape` is the AE4 analog ("bandwidth increase"): for a fixed
VMEM budget it picks the aspect ratio that maximises arithmetic intensity
(flops per HBM byte), exactly the paper's argument for widening the
FPS<->load-store path to the full block width.

`autotune_block_shape` goes one step further, the way the paper tunes its
blocking empirically per problem size (S5): rank the feasible candidates
analytically, then *measure* the top-K on the live backend and keep the
winner, persisted in a process + on-disk cache keyed by
(op, shape, dtype, backend).  Measurement is opt-in (REPRO_AUTOTUNE=1)
because it runs real kernels at first touch; without it the analytic
best — identical to `choose_block_shape` — is served from the same cache.
"""

from __future__ import annotations

import dataclasses
import functools
import json
import math
import os
import threading
from pathlib import Path
from typing import Callable, Optional, Sequence

import jax.numpy as jnp

# TPU v5e-class constants.
MXU_DIM = 128          # systolic array edge
SUBLANE = 8            # f32 sublane count; bf16 packs 16
# The ONE scoped-VMEM number: blocks are planned against it here, and every
# pallas_call passes it as `vmem_limit_bytes` (kernels._compat), so a planned
# block is one the compiler may place.  Half of the v5e's 128 MiB per core;
# a 2048^3 bf16 GEMM block (58.7 MB working set) compiles under it.
DEFAULT_VMEM_BUDGET = 64 * 1024 * 1024


def round_up(x: int, m: int) -> int:
    return ((x + m - 1) // m) * m


def cdiv(a: int, b: int) -> int:
    return (a + b - 1) // b


def pad_dim_to(x: jnp.ndarray, axis: int, multiple: int):
    """Zero-pad `axis` of x up to a multiple.  Returns (padded, original_size).

    This is the TPU replacement for the paper's DOT2/DOT3 RDP reconfiguration:
    instead of reconfiguring the datapath for residual (non multiple-of-4)
    fringes, we pad to the hardware tile and slice the result back.
    """
    size = x.shape[axis]
    target = round_up(size, multiple)
    if target == size:
        return x, size
    pad = [(0, 0)] * x.ndim
    pad[axis] = (0, target - size)
    return jnp.pad(x, pad), size


@dataclasses.dataclass(frozen=True)
class BlockShape:
    bm: int
    bn: int
    bk: int

    def vmem_bytes(self, dtype_bytes: int = 2) -> int:
        """Working-set bytes for one grid step: double-buffered A/B tiles +
        f32 accumulator + output tile, for `dtype_bytes`-wide operands.

        This is THE budget formula `choose_block_shape` enforces (it calls
        this method), so the selected block and the reported working set can
        never drift apart; tests/test_dag_tiling.py pins the equality.
        """
        return (
            2 * (self.bm * self.bk + self.bk * self.bn) * dtype_bytes
            + self.bm * self.bn * 4
            + self.bm * self.bn * dtype_bytes
        )

    def arithmetic_intensity(self) -> float:
        """flops per byte moved HBM->VMEM for one grid step (bf16 operands)."""
        flops = 2 * self.bm * self.bn * self.bk
        bytes_moved = (self.bm * self.bk + self.bk * self.bn) * 2
        return flops / bytes_moved


def epilogue_vmem_bytes(blk: BlockShape, dtype_bytes: int, *,
                        gate: bool = False, residual: bool = False) -> int:
    """Extra per-grid-step VMEM a fused epilogue claims on top of
    `BlockShape.vmem_bytes`: the dual-GEMM gate operand's double-buffered
    tile + its f32 accumulator, and the double-buffered residual tile
    (the bias row is negligible)."""
    extra = 0
    if gate:
        extra += 2 * blk.bk * blk.bn * dtype_bytes + blk.bm * blk.bn * 4
    if residual:
        extra += 2 * blk.bm * blk.bn * dtype_bytes
    return extra


#: width of a packed B tile once the kernel dequantizes it: to the f32
#: accumulator's dtype (`kernels.gemv.dequant_tile`)
DEQUANT_BYTES = 4


def dequant_vmem_bytes(blk: BlockShape, *, gate: bool = False) -> int:
    """VMEM a packed-weight (mixed-width) grid step claims besides its
    pipelined tiles: the dequantized B tile at f32, and the gate operand's
    beside it.  Mosaic places these on the kernel's stack; on a v5e the
    int8 dual-B SwiGLU at (bm, bn, bk) = (1024, 2048, 2048) asks 72.77 MiB
    in all, which this charge keeps out of a 64 MiB plan."""
    return (2 if gate else 1) * blk.bk * blk.bn * DEQUANT_BYTES


# --------------------------------------------------------------------------
# Decode bgemv: one weight tile a grid step against the whole batch
# --------------------------------------------------------------------------

#: weight bytes one `bgemv` grid step streams per operand: ~2.6 us of DMA at
#: the v5e's 819 GB/s, so the ~0.35 us a grid step costs stays small
BGEMV_TILE_BYTES = 2 * 1024 * 1024
#: widest contraction extent of one `bgemv` tile
BGEMV_MAX_BN = 2048
#: batch-block granularity when a batch is split (a bf16 tile's 16 rows)
BGEMV_BATCH_ALIGN = 16


def _tile_extent(dim: int, cap: int) -> int:
    """One tile's extent along `dim`: the whole dim when it fits in `cap`,
    else the largest multiple of 128 in [cap/2, cap] that divides dim (no
    ragged fringe), else cap rounded to 128 (a fringe the kernel masks on
    the contraction axis, or Pallas clips on the output axis)."""
    if dim <= cap:
        return dim
    cap = max(MXU_DIM, cap - cap % MXU_DIM)
    for t in range(cap, cap // 2 - 1, -MXU_DIM):
        if dim % t == 0:
            return t
    return cap


def bgemv_vmem_bytes(bb: int, bm: int, bn: int, a_dtype, x_dtype, *,
                     gate: bool = False, residual: bool = False) -> int:
    """VMEM one `bgemv` grid step claims for a weight of `a_dtype` against
    activations of `x_dtype`.  Products run at the wider float of the two;
    an integer (packed) weight converts to the activations' dtype.

    Per weight operand (two under the dual gate): its double-buffered
    (bm, bn) tile, plus the tile converted to the activations' width where
    the weight is stored narrower (packed int8 to bf16).  Then the
    double-buffered (bb, bn) x block, the (bb, bm) accumulator per operand,
    the double-buffered (bb, bm) output and residual blocks and the bias
    row.  Rows pad to whole sublanes."""
    a, x = jnp.dtype(a_dtype), jnp.dtype(x_dtype)
    act = jnp.promote_types(a, x) if jnp.issubdtype(a, jnp.floating) else x
    act_bytes, acc_bytes = act.itemsize, max(4, act.itemsize)
    rows = round_up(bb, SUBLANE)
    ops = 2 if gate else 1
    weight = 2 * bm * bn * a.itemsize + (bm * bn * act_bytes if act != a else 0)
    return (ops * weight
            + 2 * rows * bn * act_bytes
            + ops * rows * bm * acc_bytes
            + (4 if residual else 2) * rows * bm * act_bytes
            + 2 * SUBLANE * bm * act_bytes)


def bgemv_batch_cap(bm: int, bn: int, a_dtype, x_dtype, *,
                    gate: bool = False, residual: bool = False) -> int:
    """Most batch rows one block may hold beside (bm, bn) weight tiles under
    DEFAULT_VMEM_BUDGET: a multiple of BGEMV_BATCH_ALIGN, 0 when not even
    that many fit."""
    charge = functools.partial(bgemv_vmem_bytes, bm=bm, bn=bn,
                               a_dtype=a_dtype, x_dtype=x_dtype, gate=gate,
                               residual=residual)
    fixed = charge(0)
    per_block = charge(BGEMV_BATCH_ALIGN) - fixed
    return (max(0, (DEFAULT_VMEM_BUDGET - fixed) // per_block)
            * BGEMV_BATCH_ALIGN)


def plan_bgemv(batch: int, m: int, n: int, a_dtype, x_dtype, *,
               gate: bool = False, residual: bool = False,
               broadcast: bool = True, block_m: int = None,
               block_n: int = None) -> tuple[int, int, int]:
    """(bb, bm, bn) for a `bgemv` of `batch` vectors of `x_dtype` by an
    (m, n) weight of `a_dtype` (output extent m, contraction n, whichever
    way the weight is stored).

    The weight tile is about BGEMV_TILE_BYTES: bn at most BGEMV_MAX_BN along
    the contraction, bm the rest, each a whole extent, a divisor of it or a
    multiple of 128; a caller's `block_m`/`block_n` take their place.  A
    broadcast weight puts the whole batch in one block (bb = batch), so
    each tile is read once per call; a batch that does not fit beside the
    tiles under DEFAULT_VMEM_BUDGET splits into the fewest blocks that do,
    evened out.  A per-member weight (`broadcast` False) takes one member a
    block."""
    bn = min(block_n, n) if block_n else _tile_extent(n, BGEMV_MAX_BN)
    a_bytes = jnp.dtype(a_dtype).itemsize
    bm = (min(block_m, m) if block_m else _tile_extent(
        m, max(MXU_DIM, BGEMV_TILE_BYTES // (bn * a_bytes))))
    cap_kw = dict(gate=gate, residual=residual)
    cap = bgemv_batch_cap(bm, bn, a_dtype, x_dtype, **cap_kw)
    while not cap and bm > MXU_DIM and not block_m:
        bm = _tile_extent(m, bm // 2)
        cap = bgemv_batch_cap(bm, bn, a_dtype, x_dtype, **cap_kw)
    if not broadcast:
        return 1, bm, bn
    if batch <= cap:
        return batch, bm, bn
    if not cap:
        raise ValueError(f"no bgemv batch block fits {DEFAULT_VMEM_BUDGET} B "
                         f"of VMEM beside ({bm}, {bn}) weight tiles")
    blocks = cdiv(batch, cap)
    return round_up(cdiv(batch, blocks), BGEMV_BATCH_ALIGN), bm, bn


def rank_block_shapes(
    m: int,
    n: int,
    k: int,
    *,
    dtype_bytes: int = 2,
    vmem_budget: int = DEFAULT_VMEM_BUDGET,
    candidates: Sequence[int] = (128, 256, 512, 1024, 2048),
    top_k: Optional[int] = None,
    gate: bool = False,
    residual: bool = False,
    b_dtype_bytes: Optional[int] = None,
) -> list[BlockShape]:
    """All VMEM-feasible MXU-aligned block shapes, best analytic guess first.

    Ordering is by arithmetic intensity (the AE4 argument), tie-broken by
    larger bk (fewer k-steps => less accumulator traffic) then by iteration
    order (smaller bm, bn) — the exact preference `choose_block_shape` has
    always applied; rank[0] IS its answer.  `top_k` truncates the list (the
    autotuner's measurement shortlist).  `gate`/`residual` charge the fused
    epilogue's extra tiles (second operand double buffer + f32 accumulator,
    residual double buffer) against the same budget, so a fused dual-GEMM
    cannot be planned past the VMEM the plain GEMM was budgeted for.

    `b_dtype_bytes` plans a mixed-width op — f32/bf16 activations against a
    packed int8 weight stream (core.quant): the B tiles are budgeted and
    traffic-modelled at their true packed width, which makes bigger blocks
    feasible and raises the achievable flops/HBM-byte exactly as the
    quantization is supposed to.

    SKINNY M (m below one MXU tile — speculative verify windows run
    (k+1)-row GEMMs per slot, k+1 <= 8 typically): a full 128-row bm tile
    would pad >90% dead rows, so the SUBLANE-aligned extent round_up(m, 8)
    joins the bm candidates.  Ranking credits only the REAL rows as flops
    (eff_m = min(bm, round_up(m, 8))) while charging the full bm tile's
    bytes, so the skinny tile wins exactly when it should: same useful
    flops, 16x less A-tile traffic, and the freed VMEM buys wider bn/bk —
    which is where the intensity actually comes from when m is tiny.
    """
    b_bytes = dtype_bytes if b_dtype_bytes is None else b_dtype_bytes
    m_pad = round_up(m, SUBLANE)
    bm_cands = ([m_pad] if m_pad < MXU_DIM else []) + list(candidates)
    ranked: list[tuple[float, int, int, int, BlockShape]] = []
    for bm in bm_cands:
        if bm > round_up(m, MXU_DIM):
            continue
        eff_m = min(bm, m_pad)
        for bn in candidates:
            if bn > round_up(n, MXU_DIM):
                continue
            for bk in candidates:
                if bk > round_up(k, MXU_DIM):
                    continue
                cand = BlockShape(bm, bn, bk)
                if b_dtype_bytes is None:
                    used = cand.vmem_bytes(dtype_bytes)
                else:
                    used = (2 * (bm * bk * dtype_bytes + bk * bn * b_bytes)
                            + bm * bn * 4 + bm * bn * dtype_bytes
                            + dequant_vmem_bytes(cand, gate=gate))
                # the gate operand is a second B stream (packed width when
                # quantized); the residual tile is activation-width
                used += epilogue_vmem_bytes(cand, b_bytes, gate=gate)
                used += epilogue_vmem_bytes(cand, dtype_bytes,
                                            residual=residual)
                if used > vmem_budget:
                    continue
                ai = (2 * eff_m * bn * bk) / (
                    bm * bk * dtype_bytes + bk * bn * b_bytes
                )
                ranked.append((-ai, -bk, bm, bn, cand))
    ranked.sort(key=lambda t: t[:4])
    out = [t[4] for t in ranked]
    if not out:  # tiny problem: single MXU tile
        out = [BlockShape(MXU_DIM, MXU_DIM, MXU_DIM)]
    return out[:top_k] if top_k else out


def choose_block_shape(
    m: int,
    n: int,
    k: int,
    *,
    dtype_bytes: int = 2,
    vmem_budget: int = DEFAULT_VMEM_BUDGET,
    candidates: Sequence[int] = (128, 256, 512, 1024, 2048),
) -> BlockShape:
    """Pick an MXU-aligned block shape maximizing arithmetic intensity.

    Mirrors the paper's AE4 reasoning: bigger blocks amortise the per-block
    handshake (here: DMA issue) and raise flops/byte; the ceiling is local
    memory (here: VMEM, incl. the double buffer the Pallas pipeline inserts).
    This is the pure-analytic answer; `autotune_block_shape` layers empirical
    measurement on top of the same candidate ranking.
    """
    return rank_block_shapes(
        m, n, k, dtype_bytes=dtype_bytes, vmem_budget=vmem_budget,
        candidates=candidates,
    )[0]


# --------------------------------------------------------------------------
# Empirical block-shape autotuner (the paper's per-problem-size tuning, S5)
# --------------------------------------------------------------------------

AUTOTUNE_ENV = "REPRO_AUTOTUNE"              # "1" enables measurement
AUTOTUNE_CACHE_ENV = "REPRO_AUTOTUNE_CACHE"  # cache file path; "off" disables disk

_DEFAULT_CACHE = Path.home() / ".cache" / "repro" / "autotune.json"
_autotune_lock = threading.Lock()
_autotune_cache: dict[str, dict] = {}  # process cache, mirrors the disk file
_autotune_disk_loaded = False


def autotune_enabled() -> bool:
    return os.environ.get(AUTOTUNE_ENV, "0") not in ("0", "", "false", "off")


def _autotune_cache_path() -> Optional[Path]:
    raw = os.environ.get(AUTOTUNE_CACHE_ENV)
    if raw is not None:
        return None if raw in ("", "off", "none") else Path(raw)
    return _DEFAULT_CACHE


def _load_disk_cache() -> None:
    global _autotune_disk_loaded
    if _autotune_disk_loaded:
        return
    _autotune_disk_loaded = True
    path = _autotune_cache_path()
    if path is None or not path.exists():
        return
    try:
        data = json.loads(path.read_text())
    except (OSError, json.JSONDecodeError):
        return  # corrupt/unreadable cache: retune rather than crash
    for key, ent in data.items():
        # only measured winners are trusted from disk: analytic entries are
        # recomputed so heuristic improvements are never masked by the cache
        if (isinstance(ent, dict) and {"bm", "bn", "bk", "source"} <= set(ent)
                and ent["source"] == "measured"):
            _autotune_cache.setdefault(key, ent)


def _store_disk_cache() -> None:
    path = _autotune_cache_path()
    if path is None:
        return
    measured = {k: e for k, e in _autotune_cache.items()
                if e["source"] == "measured"}
    try:
        path.parent.mkdir(parents=True, exist_ok=True)
        path.write_text(json.dumps(measured, indent=1, sort_keys=True))
    except OSError:
        pass  # read-only FS: the process cache still works


def clear_autotune_cache(disk: bool = False) -> None:
    """Drop the process cache (tests; and after changing kernels).  With
    disk=True also removes the on-disk file."""
    global _autotune_disk_loaded
    with _autotune_lock:
        _autotune_cache.clear()
        _autotune_disk_loaded = False
        if disk:
            path = _autotune_cache_path()
            if path is not None and path.exists():
                path.unlink()


def autotune_cache_key(op: str, m: int, n: int, k: int, dtype_bytes: int,
                       backend: str, *, gate: bool = False,
                       residual: bool = False,
                       quantized: bool = False) -> str:
    suffix = f":g{int(gate)}r{int(residual)}" if (gate or residual) else ""
    if quantized:
        # packed-weight plans budget B tiles at 1 byte: a winner measured
        # quantized must never be served to the full-precision op (or vice
        # versa), so the flag keys its own cache entries
        suffix += ":q1"
    return f"{op}:m{m}:n{n}:k{k}:dt{dtype_bytes}:{backend}{suffix}"


def autotune_block_shape(
    op: str,
    m: int,
    n: int,
    k: int,
    *,
    dtype_bytes: int,
    backend: str,
    bench_fn: Optional[Callable[[BlockShape], float]] = None,
    top_k: int = 4,
    vmem_budget: int = DEFAULT_VMEM_BUDGET,
    gate: bool = False,
    residual: bool = False,
    quantized: bool = False,
) -> BlockShape:
    """Block shape for (op, m, n, k, dtype, backend), empirically tuned.

    The analytic ranking supplies the shortlist; when tuning is enabled
    (REPRO_AUTOTUNE=1) and a `bench_fn(block) -> seconds` is provided, the
    top-K candidates are measured once and the winner is persisted (process
    dict + JSON file at REPRO_AUTOTUNE_CACHE, default
    ~/.cache/repro/autotune.json).  Only MEASURED winners touch the disk:
    analytic picks are deterministic and recomputable, so persisting them
    would just freeze a heuristic that later versions may improve.  Cached
    analytic (process-local) entries are upgraded to measured ones the
    first time tuning runs; measured entries are final for the key.
    Without tuning this degrades to `choose_block_shape` behind the same
    cache, so callers route through one function either way.

    `gate`/`residual` describe the fused-epilogue variant being planned:
    they charge the extra VMEM (see `rank_block_shapes`) and key the cache
    separately, so a winner measured unfused is never served to a fused
    call with a different working set.
    """
    key = autotune_cache_key(op, m, n, k, dtype_bytes, backend,
                             gate=gate, residual=residual, quantized=quantized)
    want_measured = autotune_enabled() and bench_fn is not None
    with _autotune_lock:
        _load_disk_cache()
        ent = _autotune_cache.get(key)
        if ent is not None and (ent["source"] == "measured" or not want_measured):
            return BlockShape(ent["bm"], ent["bn"], ent["bk"])
    shortlist = rank_block_shapes(
        m, n, k, dtype_bytes=dtype_bytes, vmem_budget=vmem_budget, top_k=top_k,
        gate=gate, residual=residual,
        b_dtype_bytes=1 if quantized else None,
    )
    if want_measured:
        timed = [(bench_fn(blk), i) for i, blk in enumerate(shortlist)]
        best = shortlist[min(timed)[1]]
        ent = {"bm": best.bm, "bn": best.bn, "bk": best.bk, "source": "measured",
               "us": round(min(timed)[0] * 1e6, 3)}
    else:
        best = shortlist[0]
        ent = {"bm": best.bm, "bn": best.bn, "bk": best.bk, "source": "analytic"}
    with _autotune_lock:
        _autotune_cache[key] = ent
        if ent["source"] == "measured":
            _store_disk_cache()
    return best


@dataclasses.dataclass(frozen=True)
class GridPlan:
    """A fully-specified blocked-GEMM execution plan (paper's Algorithm 3)."""

    m: int
    n: int
    k: int
    block: BlockShape

    @property
    def grid(self) -> tuple[int, int, int]:
        return (cdiv(self.m, self.block.bm), cdiv(self.n, self.block.bn), cdiv(self.k, self.block.bk))

    @property
    def padded(self) -> tuple[int, int, int]:
        g = self.grid
        return (g[0] * self.block.bm, g[1] * self.block.bn, g[2] * self.block.bk)

    @property
    def num_block_matmuls(self) -> int:
        g = self.grid
        return g[0] * g[1] * g[2]

    def pad_waste_fraction(self) -> float:
        pm, pn, pk = self.padded
        return 1.0 - (self.m * self.n * self.k) / (pm * pn * pk)


def plan_gemm(m: int, n: int, k: int, **kw) -> GridPlan:
    return GridPlan(m, n, k, choose_block_shape(m, n, k, **kw))


@dataclasses.dataclass(frozen=True)
class BatchedGridPlan:
    """Execution plan for a fused batched GEMM: grid (m/bm, n/bn, batch, k/bk).

    The batch axis adds no per-step VMEM (one member's tiles are in flight at
    a time), so the per-member block shape is chosen by the same AE4 argument
    as the single GEMM.  What the batch changes is *reuse*: the kernel grid
    is (m/bm, n/bn, batch, k/bk) — batch inside the output-tile coords — so
    a broadcast B whose k extent is one tile (nk == 1) keeps a constant
    block index across consecutive batch steps and is fetched once per
    (i, j) for the whole batch.  The pipeline only elides DMAs between
    consecutive steps, so multi-k-tile weights are refetched per member.
    """

    batch: int
    m: int
    n: int
    k: int
    block: BlockShape
    broadcast_b: bool = False

    @property
    def grid(self) -> tuple[int, int, int, int]:
        # kernel order: batch inside the output-tile coords, k innermost
        return (
            cdiv(self.m, self.block.bm),
            cdiv(self.n, self.block.bn),
            self.batch,
            cdiv(self.k, self.block.bk),
        )

    @property
    def padded(self) -> tuple[int, int, int]:
        g = self.grid
        return (g[0] * self.block.bm, g[1] * self.block.bn, g[3] * self.block.bk)

    @property
    def num_block_matmuls(self) -> int:
        g = self.grid
        return g[0] * g[1] * g[2] * g[3]

    def b_tile_fetches(self) -> int:
        """HBM fetches of B tiles for the whole batch.

        Models the Pallas pipeline's consecutive-step DMA elision on the
        (i, j, batch, k) grid: a broadcast B is reused across the batch only
        when its k extent is a single tile (constant index while the batch
        advances); otherwise every member refetches its k sweep.
        """
        nm, nn, _, nk = self.grid
        if self.broadcast_b and nk == 1:
            return nm * nn
        return self.batch * nm * nn * nk


def plan_batched_gemm(
    batch: int, m: int, n: int, k: int, *, broadcast_b: bool = False, **kw
) -> BatchedGridPlan:
    return BatchedGridPlan(
        batch, m, n, k, choose_block_shape(m, n, k, **kw), broadcast_b
    )


# --------------------------------------------------------------------------
# Epilogue-fusion traffic model (what the fused flush buys, in HBM bytes)
# --------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class LayerTraffic:
    """Intermediate-tensor HBM traffic + launch count for one layer op chain.

    Counts the traffic fusion can remove — writes of intermediate
    activations and the immediate read-back by the next op — plus, when the
    caller asks (`weight_bytes_per_elem`), the weight stream itself: for the
    O(1)-reuse decode path the weight read IS the op, and block-scaled int8
    packing (core.quant) is the only lever that shrinks it.  With the
    default (weight accounting off) operand/weight streaming is identical
    fused and unfused and cancels out of the fusion comparison
    (bench_fused_epilogue reports both columns).
    """

    kernel_launches: int
    hbm_writes: int   # bytes written (intermediates + final output)
    hbm_reads: int    # bytes of intermediates read straight back
    weight_reads: int = 0  # bytes of weights streamed (0 = not modelled)

    @property
    def round_trips(self) -> int:
        return self.hbm_writes + self.hbm_reads

    @property
    def total_bytes(self) -> int:
        return self.round_trips + self.weight_reads


def mlp_traffic(
    m: int, d_model: int, d_ff: int, *, dtype_bytes: int = 2,
    fused: bool, kind: str = "swiglu",
    weight_bytes_per_elem: float = 0.0,
) -> LayerTraffic:
    """HBM traffic for one MLP forward over m tokens.

    Unfused SwiGLU is the paper's anti-pattern measured three times over:
    gate = x@Wg, up = x@Wu, mid = silu(gate)*up each write an (m, d_ff)
    tensor to HBM that the very next op reads straight back.  The fused
    dual-GEMM epilogue computes mid inside the flush (one write), and the
    down projection is one more GEMM — 2 launches and 2 output writes total
    against 4+ launches and 4 writes/3 read-backs.

    `weight_bytes_per_elem` > 0 also charges the weight stream (gate + up +
    down = 3 * d_model * d_ff elements): pass the full dtype width for the
    unquantized path and `quant.packed_weight_bytes(...)/elements` (~1.03
    for int8 + per-block f32 scales) for the packed path — the structural
    weight-byte reduction bench_quantized asserts.
    """
    mid = m * d_ff * dtype_bytes   # one (m, d_ff) intermediate
    out = m * d_model * dtype_bytes
    n_mats = 3 if kind in ("swiglu", "geglu") else 2
    w_reads = int(n_mats * d_model * d_ff * weight_bytes_per_elem)
    if kind in ("swiglu", "geglu"):
        if fused:
            # launch 1: dual-GEMM + gate epilogue -> mid; launch 2: down proj
            return LayerTraffic(kernel_launches=2, hbm_writes=mid + out,
                                hbm_reads=mid, weight_reads=w_reads)
        # gate GEMM, up GEMM, elementwise silu*mul, down GEMM
        return LayerTraffic(kernel_launches=4, hbm_writes=3 * mid + out,
                            hbm_reads=2 * mid + mid, weight_reads=w_reads)
    # two-matrix MLP (bias+gelu): fused = [up+bias+gelu] -> [down+bias]
    if fused:
        return LayerTraffic(kernel_launches=2, hbm_writes=mid + out,
                            hbm_reads=mid, weight_reads=w_reads)
    # up GEMM, bias+gelu elementwise, down GEMM, bias elementwise
    return LayerTraffic(kernel_launches=4, hbm_writes=2 * mid + 2 * out,
                        hbm_reads=mid + mid + out, weight_reads=w_reads)
