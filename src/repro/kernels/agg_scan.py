"""Fused sample-scan aggregation kernel (the paper's hot path, TPU-native).

BlinkDB's runtime cost is dominated by the sample scan: evaluate the
predicate, HT-weight each row, and segment-reduce seven sufficient statistics
per group (estimators.GroupedMoments). On a TPU this is an HBM-bandwidth
problem; the kernel streams each row-block HBM→VMEM exactly once and performs
the grouped reduction as a one-hot MXU matmul (the TPU-idiomatic replacement
for scatter-add — DESIGN.md §6):

    stats[8, B]   per-row quantities (mask, w, wx, wx², vfac, vfac·x, vfac·x², pad)
    onehot[B, GB] (code == group_id) for the current group block
    out[8, GB]   += stats @ onehot        (MXU)

Grid: (group_blocks, row_blocks) — row axis innermost so each output block
stays resident in VMEM while every row block streams past it.

Block shapes: B rows (multiple of 128 lanes), GB groups (multiple of 128).
VMEM footprint ≈ 4 input blocks (4·B·4B) + onehot (B·GB·4B) + out (8·GB·4B);
defaults (B=2048, GB=512) ≈ 4.3 MB — well under ~16 MB VMEM of TPU v5e.

Batched shared-scan execution
-----------------------------

`agg_scan_batched_pallas` amortizes ONE pass over the family prefix across Q
concurrent same-template queries. Each row block streams HBM→VMEM exactly
once; per-query state is tiny and lives in VMEM as a constant block
qconst[Qp, 128] (lane 0 = resolution cap k_q, lanes 1..n_atoms = the query's
predicate constants in flattened template order). The kernel evaluates the
DNF predicate, the prefix test entry_key < k_q, and the HT weights
rate = min(1, k_q/freq) for all Q queries on the resident block, then reduces
all Q×8 statistics with a single MXU matmul:

    stats[Q·8, B] @ onehot[B, GB]  →  out[Q·8, GB]   (stat-major rows)

so HBM traffic is ~1/Q of Q sequential scans while MXU work grows only
linearly. VMEM budget ≈ row blocks (≈6·B·4B) + atoms (A·B·4B) + per-query
intermediates (≈8·Qp·B·4B) + onehot (B·GB·4B) + out (8·Qp·GB·4B); at the
batched defaults (B=1024, GB=512, Qp=64) ≈ 8 MB — see docs/BATCHING.md for
the full budget math. Padding rows carry entry_key=+inf so every per-query
prefix test masks them; padded query slots get k=1 (freq≥1 keeps rates>0).

Fused memory-lean scan (`agg_scan_fused_pallas`)
------------------------------------------------

The batched kernel above still streams two DERIVED f32 arrays per row —
`freq = freq_table[strat]` and `entry_key = unit * freq` — plus full-width
f32/int32 copies of dictionary-encoded predicate/group columns. The fused
kernel streams the minimum bytes per row instead:

* **In-kernel HT derivation.** The stratum frequency table (padded to a
  multiple of 128 lanes, laid out as [D/128, 128]) rides along as a
  VMEM-resident constant block, exactly like qconst. Per row block the
  kernel derives freq with a two-level one-hot: `T[D/128, B] =
  ftab @ onehot(strat % 128)[128, B]`, then row `strat // 128` of each
  column of T (every one-hot column has exactly one 1.0, so the f32 dot
  and the select are bit-identical to the gather `freq_table[strat]`),
  then `entry_key = unit · freq` in VMEM. One matmul serves any table up
  to MAX_FUSED_STRATA (every int16 stratum id). Only `unit` (f32) and
  `strat` (narrow int) stream from HBM — ~8 fewer bytes/row than
  materialized freq/entry_key, and append/tombstone paths stop rebuilding
  derived arrays.
* **Packed narrow dtypes.** Dictionary-encoded atom/group columns and
  `strat` stream at their natural width (int8/int16 chosen from dictionary
  size by the executor) and are widened to f32/int32 in VMEM. `valid` rides
  along as a 1-byte bool so fault-shard masks compose with the prefix test.
* **Shared atom blocks.** `atom_slots` maps flattened template atoms to a
  deduplicated tuple of column arrays, so a template touching the same
  column twice streams it once.

Both fused kernels take a traced `row_spans` bound (scalar-prefetched):
the grid keeps its static shape, but only the row blocks that meet the
batch's key prefix (and an appended tail) are fetched and reduced; later
steps repeat the last block index, so no DMA starts, and skip their
compute (docs/BATCHING.md, "The row grid").

`quantile_scan_pallas` extends the fused kernel with a bins×groups
histogram output block (same one-hot MXU trick, `wbin[NB, B] @ onehot[B,
GB]`) so a QUANTILE answer — grouped moments AND the weighted value
histogram — costs ONE streaming pass instead of a second full-column read.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.types import cmp_fns

DEFAULT_BLOCK_ROWS = 2048
DEFAULT_BLOCK_ROWS_BATCHED = 1024
DEFAULT_BLOCK_GROUPS = 512
N_STATS = 8  # 7 used + 1 pad row for sublane alignment
CONST_LANES = 128  # qconst lane width: 1 (k) + up to 127 predicate atoms
FTAB_LANES = 128   # freq-table constant block is padded to this lane width
MAX_FUSED_STRATA = 2 ** 15  # every id an int16 stratum column holds
DEFAULT_QUANTILE_BINS = 256

_CMP = cmp_fns()
# Every fused-kernel matmul multiplies f32 operands at full precision: the
# one-hot products reproduce the f32 gather and sums only then. At Mosaic's
# default precision a TPU v5e returned moments 5e-2 to 4e-1 off (relative)
# for stratum frequencies of 1e5-1e7.
_EXACT = jax.lax.Precision.HIGHEST


def _agg_scan_kernel(values_ref, rates_ref, mask_ref, codes_ref, out_ref, *,
                     block_groups: int):
    gi = pl.program_id(0)   # group-block index (outer)
    ri = pl.program_id(1)   # row-block index (inner; accumulates into out)

    @pl.when(ri == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    v = values_ref[0, :].astype(jnp.float32)
    r = rates_ref[0, :].astype(jnp.float32)
    m = mask_ref[0, :].astype(jnp.float32)
    codes = codes_ref[0, :]

    w = m / r
    wx = w * v
    vfac = m * (1.0 - r) / (r * r)
    vx = vfac * v
    stats = jnp.stack([
        m, w, wx, wx * v, vfac, vx, vx * v,
        jnp.zeros_like(m),                      # pad to N_STATS sublanes
    ])                                          # [8, B]

    group_base = gi * block_groups
    gids = group_base + jax.lax.broadcasted_iota(jnp.int32, (1, block_groups), 1)
    onehot = (codes[:, None] == gids).astype(jnp.float32)   # [B, GB]

    out_ref[...] += jax.lax.dot_general(
        stats, onehot, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)     # [8, GB]


@functools.partial(jax.jit, static_argnames=("n_groups", "block_rows",
                                             "block_groups", "interpret"))
def agg_scan_pallas(values: jax.Array, rates: jax.Array, mask: jax.Array,
                    group_codes: jax.Array, n_groups: int,
                    block_rows: int = DEFAULT_BLOCK_ROWS,
                    block_groups: int = DEFAULT_BLOCK_GROUPS,
                    interpret: bool = False) -> jax.Array:
    """Returns f32[7, n_groups] (GroupedMoments field order)."""
    n = values.shape[0]
    bg = min(block_groups, max(128, -(-n_groups // 128) * 128))
    g_pad = -(-n_groups // bg) * bg
    n_pad = -(-max(n, 1) // block_rows) * block_rows

    def pad(x, fill):
        return jnp.pad(x, (0, n_pad - n), constant_values=fill)

    v = pad(values.astype(jnp.float32), 0).reshape(-1, block_rows)
    r = pad(rates.astype(jnp.float32), 1).reshape(-1, block_rows)
    m = pad(mask.astype(jnp.float32), 0).reshape(-1, block_rows)
    c = pad(group_codes.astype(jnp.int32), g_pad - 1).reshape(-1, block_rows)

    n_row_blocks = n_pad // block_rows
    n_group_blocks = g_pad // bg

    out = pl.pallas_call(
        functools.partial(_agg_scan_kernel, block_groups=bg),
        grid=(n_group_blocks, n_row_blocks),
        in_specs=[
            pl.BlockSpec((1, block_rows), lambda gi, ri: (ri, 0)),
            pl.BlockSpec((1, block_rows), lambda gi, ri: (ri, 0)),
            pl.BlockSpec((1, block_rows), lambda gi, ri: (ri, 0)),
            pl.BlockSpec((1, block_rows), lambda gi, ri: (ri, 0)),
        ],
        out_specs=pl.BlockSpec((N_STATS, bg), lambda gi, ri: (0, gi)),
        out_shape=jax.ShapeDtypeStruct((N_STATS, g_pad), jnp.float32),
        interpret=interpret,
    )(v, r, m, c)
    return out[:7, :n_groups]


def _agg_scan_batched_kernel(qconst_ref, values_ref, freq_ref, ek_ref,
                             atoms_ref, codes_ref, out_ref, *,
                             block_groups: int, ops_struct):
    gi = pl.program_id(0)   # group-block index (outer)
    ri = pl.program_id(1)   # row-block index (inner; accumulates into out)

    @pl.when(ri == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    v = values_ref[0, :].astype(jnp.float32)[None, :]     # [1, B]
    f = freq_ref[0, :].astype(jnp.float32)[None, :]
    ek = ek_ref[0, :].astype(jnp.float32)[None, :]
    codes = codes_ref[0, :]
    ks = qconst_ref[:, 0:1]                               # [Qp, 1]

    prefix = ek < ks                                      # [Qp, B]
    if ops_struct:
        disj = jnp.zeros(prefix.shape, dtype=bool)
        ai = 0
        for conj in ops_struct:
            m = jnp.ones(prefix.shape, dtype=bool)
            for op in conj:
                col = atoms_ref[ai, 0, :].astype(jnp.float32)[None, :]
                m = m & _CMP[op](col, qconst_ref[:, ai + 1:ai + 2])
                ai += 1
            disj = disj | m
        mf = (prefix & disj).astype(jnp.float32)
    else:
        mf = prefix.astype(jnp.float32)

    r = jnp.minimum(1.0, ks / f)                          # [Qp, B]
    w = mf / r
    wx = w * v
    vfac = mf * (1.0 - r) / (r * r)
    vx = vfac * v
    # Stat-major stacking: row s*Qp + q holds statistic s of query q.
    stats = jnp.concatenate([
        mf, w, wx, wx * v, vfac, vx, vx * v,
        jnp.zeros_like(mf),                   # pad to N_STATS sublane groups
    ], axis=0)                                            # [8·Qp, B]

    group_base = gi * block_groups
    gids = group_base + jax.lax.broadcasted_iota(jnp.int32, (1, block_groups), 1)
    onehot = (codes[:, None] == gids).astype(jnp.float32)  # [B, GB]

    out_ref[...] += jax.lax.dot_general(
        stats, onehot, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)               # [8·Qp, GB]


@functools.partial(jax.jit, static_argnames=("ops_struct", "n_groups",
                                             "block_rows", "block_groups",
                                             "interpret"))
def agg_scan_batched_pallas(values: jax.Array, freq: jax.Array,
                            entry_key: jax.Array, atom_cols: jax.Array,
                            group_codes: jax.Array, ks: jax.Array,
                            pred_consts: jax.Array, *, ops_struct,
                            n_groups: int,
                            block_rows: int = DEFAULT_BLOCK_ROWS_BATCHED,
                            block_groups: int = DEFAULT_BLOCK_GROUPS,
                            interpret: bool = False) -> jax.Array:
    """Q-query shared scan: returns f32[Q, 7, n_groups].

    `ops_struct` is the static predicate template (tuple of conjunctions of
    CmpOps); atom i in flattened template order reads atom_cols[i] and
    compares it against pred_consts[q, i]. Semantics match
    ref.agg_scan_batched_ref.
    """
    n = values.shape[0]
    q = ks.shape[0]
    n_atoms = sum(len(c) for c in ops_struct)
    if n_atoms + 1 > CONST_LANES:
        raise ValueError(f"predicate has {n_atoms} atoms; max {CONST_LANES - 1}")

    q_pad = max(8, -(-q // 8) * 8)
    bg = min(block_groups, max(128, -(-n_groups // 128) * 128))
    g_pad = -(-n_groups // bg) * bg
    n_pad = -(-max(n, 1) // block_rows) * block_rows

    def pad(x, fill):
        return jnp.pad(x, (0, n_pad - n), constant_values=fill)

    v = pad(values.astype(jnp.float32), 0).reshape(-1, block_rows)
    f = pad(freq.astype(jnp.float32), 1).reshape(-1, block_rows)
    ek = pad(entry_key.astype(jnp.float32), jnp.inf).reshape(-1, block_rows)
    c = pad(group_codes.astype(jnp.int32), g_pad - 1).reshape(-1, block_rows)

    na = max(n_atoms, 1)
    a = atom_cols.astype(jnp.float32)
    if a.shape[0] == 0:
        a = jnp.zeros((1, n), jnp.float32)
    a = jnp.pad(a, ((0, na - a.shape[0]), (0, n_pad - n)))
    a = a.reshape(na, -1, block_rows)

    # qconst[Qp, 128]: lane 0 = k, lanes 1..n_atoms = predicate constants.
    # Padded query slots use k=1 (freq ≥ 1 keeps rates > 0; results dropped).
    qconst = jnp.ones((q_pad, CONST_LANES), jnp.float32)
    qconst = qconst.at[:q, 0].set(ks.astype(jnp.float32))
    if n_atoms:
        qconst = qconst.at[:q, 1:1 + n_atoms].set(
            pred_consts.astype(jnp.float32))

    n_row_blocks = n_pad // block_rows
    n_group_blocks = g_pad // bg

    out = pl.pallas_call(
        functools.partial(_agg_scan_batched_kernel, block_groups=bg,
                          ops_struct=ops_struct),
        grid=(n_group_blocks, n_row_blocks),
        in_specs=[
            pl.BlockSpec((q_pad, CONST_LANES), lambda gi, ri: (0, 0)),
            pl.BlockSpec((1, block_rows), lambda gi, ri: (ri, 0)),
            pl.BlockSpec((1, block_rows), lambda gi, ri: (ri, 0)),
            pl.BlockSpec((1, block_rows), lambda gi, ri: (ri, 0)),
            pl.BlockSpec((na, 1, block_rows), lambda gi, ri: (0, ri, 0)),
            pl.BlockSpec((1, block_rows), lambda gi, ri: (ri, 0)),
        ],
        out_specs=pl.BlockSpec((N_STATS * q_pad, bg), lambda gi, ri: (0, gi)),
        out_shape=jax.ShapeDtypeStruct((N_STATS * q_pad, g_pad), jnp.float32),
        interpret=interpret,
    )(qconst, v, f, ek, a, c)
    # stat-major rows → [Q, 7, n_groups]
    out = out.reshape(N_STATS, q_pad, g_pad)
    return out[:7, :q, :n_groups].transpose(1, 0, 2)


def _derive_freq(ftab_ref, strat_ref):
    """freq[1, B] from the VMEM-resident frequency table ftab[NH, 128]
    (stratum s at row s // 128, lane s % 128).

    Two-level one-hot: T[NH, B] = ftab @ (s % 128 == lane)[128, B] holds
    in column b every row's entry at lane s_b % 128; row s_b // 128 is then
    selected on the VPU. Each one-hot column has exactly one 1.0, so at
    full precision every entry of T is a table value plus exact zeros and
    the select adds only zeros: bit-identical to the f32 gather
    `freq_table[strat]`. A one-row table (at most 128 strata) is T itself.
    """
    s = strat_ref[...].astype(jnp.int32)[None, :]             # [1, B]
    nh = ftab_ref.shape[0]
    lane = s if nh == 1 else s & (FTAB_LANES - 1)
    ids = jax.lax.broadcasted_iota(jnp.int32, (FTAB_LANES, 1), 0)
    onehot = (lane == ids).astype(jnp.float32)                # [128, B]
    t = jax.lax.dot_general(                                  # [NH, B]
        ftab_ref[...], onehot, (((1,), (0,)), ((), ())),
        precision=_EXACT, preferred_element_type=jnp.float32)
    if nh == 1:
        return t
    rows = jax.lax.broadcasted_iota(jnp.int32, (nh, 1), 0)
    row = s >> (FTAB_LANES.bit_length() - 1)                 # s // 128
    return jnp.sum(jnp.where(row == rows, t, 0.0), axis=0, keepdims=True)


def _eval_dnf(qconst_ref, atom_refs, prefix, *, ops_struct, atom_slots,
              lane_base):
    """prefix & DNF(template) as f32 mask [Qp, B] (or [1, B] single-query).

    atom_refs holds DEDUPLICATED narrow-dtype column blocks; flattened atom
    i reads atom_refs[atom_slots[i]], widened to f32 in VMEM. The query's
    constant for atom i sits at qconst lane `lane_base + i`.
    """
    if not ops_struct:
        return prefix.astype(jnp.float32)
    disj = jnp.zeros(prefix.shape, dtype=bool)
    ai = 0
    for conj in ops_struct:
        m = jnp.ones(prefix.shape, dtype=bool)
        for op in conj:
            col = atom_refs[atom_slots[ai]][...].astype(jnp.float32)[None, :]
            m = m & _CMP[op](col, qconst_ref[:, lane_base + ai:
                                             lane_base + ai + 1])
            ai += 1
        disj = disj | m
    return (prefix & disj).astype(jnp.float32)


def _fused_scan_kernel(plan_ref, qconst_ref, ftab_ref, values_ref,
                       unit_ref, strat_ref, valid_ref, codes_ref, *rest,
                       block_groups: int, ops_struct, atom_slots):
    atom_refs, out_ref = rest[:-1], rest[-1]
    gi = pl.program_id(0)   # group-block index (outer)
    ri = pl.program_id(1)   # row-block index (inner; accumulates into out)

    @pl.when(ri == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    @pl.when(ri < plan_ref[2])   # steps past the visited blocks do nothing
    def _accumulate():
        v = values_ref[...].astype(jnp.float32)[None, :]      # [1, B]
        f = _derive_freq(ftab_ref, strat_ref)                 # [1, B]
        ek = unit_ref[...].astype(jnp.float32)[None, :] * f   # [1, B]
        va = valid_ref[...][None, :]                          # [1, B] bool
        codes = codes_ref[...].astype(jnp.int32)
        ks = qconst_ref[:, 0:1]                               # [Qp, 1]

        prefix = (ek < ks) & va                               # [Qp, B]
        mf = _eval_dnf(qconst_ref, atom_refs, prefix, ops_struct=ops_struct,
                       atom_slots=atom_slots, lane_base=1)

        r = jnp.minimum(1.0, ks / f)                          # [Qp, B]
        w = mf / r
        wx = w * v
        vfac = mf * (1.0 - r) / (r * r)
        vx = vfac * v
        # Stat-major stacking: row s*Qp + q holds statistic s of query q.
        stats = jnp.concatenate([
            mf, w, wx, wx * v, vfac, vx, vx * v,
            jnp.zeros_like(mf),               # pad to N_STATS sublane groups
        ], axis=0)                                            # [8·Qp, B]

        group_base = gi * block_groups
        gids = group_base + jax.lax.broadcasted_iota(
            jnp.int32, (1, block_groups), 1)
        onehot = (codes[:, None] == gids).astype(jnp.float32)  # [B, GB]

        out_ref[...] += jax.lax.dot_general(                  # [8·Qp, GB]
            stats, onehot, (((1,), (0,)), ((), ())),
            precision=_EXACT, preferred_element_type=jnp.float32)


def _pad_ftab(freq_table: jax.Array) -> jax.Array:
    """[D_pad / 128, 128] f32 constant block, D_pad a multiple of
    FTAB_LANES ≥ 128: stratum s at row s // 128, lane s % 128.

    Pad entries are 1.0 (never selected: strat < D), keeping rates finite."""
    d = freq_table.shape[0]
    if d > MAX_FUSED_STRATA:
        raise ValueError(f"freq table has {d} strata; max "
                         f"{MAX_FUSED_STRATA} for in-kernel derivation")
    d_pad = max(FTAB_LANES, -(-d // FTAB_LANES) * FTAB_LANES)
    ft = jnp.pad(freq_table.astype(jnp.float32), (0, d_pad - d),
                 constant_values=1.0)
    return ft.reshape(-1, FTAB_LANES)


def _normalize_atoms(atom_cols, ops_struct, atom_slots, n_rows):
    """Validate/default the dedup mapping; always ≥ 1 column block."""
    n_atoms = sum(len(c) for c in ops_struct)
    if atom_slots is None:
        atom_slots = tuple(range(n_atoms))
    if len(atom_slots) != n_atoms:
        raise ValueError(f"atom_slots has {len(atom_slots)} entries; "
                         f"template has {n_atoms} atoms")
    if n_atoms and max(atom_slots, default=-1) >= len(atom_cols):
        raise ValueError("atom_slots references a missing atom column")
    if not atom_cols:
        atom_cols = (jnp.zeros((n_rows,), jnp.int8),)
    return tuple(atom_cols), atom_slots


def visit_plan(row_spans, n_row_blocks: int, block_rows: int, xp=jnp):
    """int32[3] (head blocks, first tail block, grid steps that visit a
    block) for `row_spans` = (head_end, tail_start, tail_end) in rows: the
    row blocks that meet [0, head_end) ∪ [tail_start, tail_end). Traced
    (`xp=jnp`) inside the kernels' wrappers; `xp=np` gives the same plan on
    the host, for counting the rows a grid visits."""
    sp = xp.asarray(row_spans, xp.int32)
    head = xp.minimum(-(-sp[0] // block_rows), n_row_blocks)
    t0 = xp.clip(sp[1] // block_rows, head, n_row_blocks)
    t1 = xp.clip(-(-sp[2] // block_rows), t0, n_row_blocks)
    return xp.stack([head, t0, head + t1 - t0])


def _bounded_grid(n_group_blocks: int, n_row_blocks: int, block_rows: int,
                  const_shapes, n_streamed: int, out_blocks):
    """Grid (group blocks, row blocks) whose row index maps follow the
    scalar-prefetched `visit_plan`: VMEM-resident constant blocks first,
    then `n_streamed` row-blocked columns; one [rows, group block] output
    block per entry of `out_blocks`. Row blocks are 1-D over the flat padded
    columns: Mosaic requires a block's last two dims to tile (8, 128) or
    span the array, which a (1, B) block over a [n_blocks, B] reshape does
    not."""
    row_spec = pl.BlockSpec(
        (block_rows,),
        lambda gi, ri, plan: (_row_block(ri, plan, n_row_blocks),))
    return pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(n_group_blocks, n_row_blocks),
        in_specs=[pl.BlockSpec(sh, lambda gi, ri, plan: (0, 0))
                  for sh in const_shapes] + [row_spec] * n_streamed,
        out_specs=[pl.BlockSpec(sh, lambda gi, ri, plan: (0, gi))
                   for sh in out_blocks])


def _row_block(ri, plan_ref, n_row_blocks: int):
    """Row block of grid step `ri` under a `visit_plan`: the head blocks,
    then the tail's; a step past the last visited block repeats it, so its
    block index does not change and no DMA starts for it."""
    head, t0, steps = plan_ref[0], plan_ref[1], plan_ref[2]
    r = jnp.minimum(ri, jnp.maximum(steps - 1, 0))
    return jnp.clip(jnp.where(r < head, r, r - head + t0), 0,
                    n_row_blocks - 1)


@functools.partial(jax.jit, static_argnames=("ops_struct", "atom_slots",
                                             "n_groups", "block_rows",
                                             "block_groups", "interpret"))
def agg_scan_fused_pallas(values: jax.Array, unit: jax.Array,
                          strat: jax.Array, freq_table: jax.Array,
                          valid: jax.Array, atom_cols, group_codes: jax.Array,
                          ks: jax.Array, pred_consts: jax.Array, *,
                          ops_struct, atom_slots=None, n_groups: int,
                          block_rows: int = DEFAULT_BLOCK_ROWS_BATCHED,
                          block_groups: int = DEFAULT_BLOCK_GROUPS,
                          interpret: bool = False,
                          row_spans: jax.Array | None = None) -> jax.Array:
    """Memory-lean Q-query shared scan: returns f32[Q, 7, n_groups].

    Streams only the primitive layout — values (f32), unit (f32), strat
    (narrow int), valid (bool), group codes + atom columns at their stored
    narrow dtype — and derives freq/entry_key in VMEM from the resident
    freq_table. Semantics (bit-identical): freq = freq_table[strat],
    entry_key = unit·freq, prefix = (entry_key < k) & valid, then the
    batched 7-statistic reduction of ref.agg_scan_batched_ref.

    `atom_cols` is a tuple of 1-D arrays (deduplicated column blocks);
    static `atom_slots[i]` names the block read by flattened template atom
    i (default: identity). Padding rows are masked by unit=+inf ⇒
    entry_key=+inf failing every prefix test, so narrow-dtype pad fills
    never contribute.

    `row_spans` (traced int32[3], scalar-prefetched) bounds the row grid:
    only the row blocks that meet [0, head_end) ∪ [tail_start, tail_end)
    are fetched and reduced. The caller guarantees that no row outside
    those spans passes the prefix test of any query, so the result is
    bit-identical to the full grid (None), which a skipped block would
    only have added exact zeros to.
    """
    n = values.shape[0]
    q = ks.shape[0]
    n_atoms = sum(len(c) for c in ops_struct)
    if n_atoms + 1 > CONST_LANES:
        raise ValueError(f"predicate has {n_atoms} atoms; max {CONST_LANES - 1}")
    atom_cols, atom_slots = _normalize_atoms(atom_cols, ops_struct, atom_slots, n)

    q_pad = max(8, -(-q // 8) * 8)
    bg = min(block_groups, max(128, -(-n_groups // 128) * 128))
    g_pad = -(-n_groups // bg) * bg
    n_pad = -(-max(n, 1) // block_rows) * block_rows

    def pad(x, fill):
        return jnp.pad(x, (0, n_pad - n), constant_values=fill)

    v = pad(values.astype(jnp.float32), 0)
    u = pad(unit.astype(jnp.float32), jnp.inf)   # pad rows fail every prefix
    s = pad(strat, 0)                            # narrow dtype preserved
    va = pad(valid.astype(bool), False)
    # Pad fill 0 is safe for every code dtype: pad rows carry entry_key=+inf
    # so their (zeroed) stats never land in any group.
    c = pad(group_codes, 0)
    acols = [pad(a, 0) for a in atom_cols]

    ftab = _pad_ftab(freq_table)

    # qconst[Qp, 128]: lane 0 = k, lanes 1..n_atoms = predicate constants.
    # Padded query slots use k=1 (freq ≥ 1 keeps rates > 0; results dropped).
    qconst = jnp.ones((q_pad, CONST_LANES), jnp.float32)
    qconst = qconst.at[:q, 0].set(ks.astype(jnp.float32))
    if n_atoms:
        qconst = qconst.at[:q, 1:1 + n_atoms].set(
            pred_consts.astype(jnp.float32))

    n_row_blocks = n_pad // block_rows
    plan = (jnp.full((3,), n_row_blocks, jnp.int32) if row_spans is None
            else visit_plan(row_spans, n_row_blocks, block_rows))
    (out,) = pl.pallas_call(
        functools.partial(_fused_scan_kernel, block_groups=bg,
                          ops_struct=ops_struct, atom_slots=atom_slots),
        grid_spec=_bounded_grid(g_pad // bg, n_row_blocks, block_rows,
                                [qconst.shape, ftab.shape], 5 + len(acols),
                                [(N_STATS * q_pad, bg)]),
        out_shape=[jax.ShapeDtypeStruct((N_STATS * q_pad, g_pad),
                                        jnp.float32)],
        interpret=interpret,
    )(plan, qconst, ftab, v, u, s, va, c, *acols)
    # stat-major rows → [Q, 7, n_groups]
    out = out.reshape(N_STATS, q_pad, g_pad)
    return out[:7, :q, :n_groups].transpose(1, 0, 2)


def _fused_quantile_kernel(plan_ref, qconst_ref, ftab_ref, values_ref,
                           unit_ref, strat_ref, valid_ref, codes_ref, *rest,
                           block_groups: int, ops_struct, atom_slots,
                           n_bins: int):
    atom_refs, mom_ref, hist_ref = rest[:-2], rest[-2], rest[-1]
    gi = pl.program_id(0)
    ri = pl.program_id(1)

    @pl.when(ri == 0)
    def _init():
        mom_ref[...] = jnp.zeros_like(mom_ref)
        hist_ref[...] = jnp.zeros_like(hist_ref)

    @pl.when(ri < plan_ref[2])   # steps past the visited blocks do nothing
    def _accumulate():
        k = qconst_ref[0, 0]
        lo = qconst_ref[0, 1]
        hi = qconst_ref[0, 2]

        v = values_ref[...].astype(jnp.float32)[None, :]      # [1, B]
        f = _derive_freq(ftab_ref, strat_ref)                 # [1, B]
        ek = unit_ref[...].astype(jnp.float32)[None, :] * f
        va = valid_ref[...][None, :]
        codes = codes_ref[...].astype(jnp.int32)

        prefix = (ek < k) & va                                # [1, B]
        mf = _eval_dnf(qconst_ref[0:1], atom_refs, prefix,
                       ops_struct=ops_struct, atom_slots=atom_slots,
                       lane_base=3)

        r = jnp.minimum(1.0, k / f)
        w = mf / r
        wx = w * v
        vfac = mf * (1.0 - r) / (r * r)
        vx = vfac * v
        stats = jnp.concatenate([
            mf, w, wx, wx * v, vfac, vx, vx * v,
            jnp.zeros_like(mf),
        ], axis=0)                                            # [8, B]

        group_base = gi * block_groups
        gids = group_base + jax.lax.broadcasted_iota(
            jnp.int32, (1, block_groups), 1)
        onehot = (codes[:, None] == gids).astype(jnp.float32)  # [B, GB]

        mom_ref[...] += jax.lax.dot_general(                  # [8, GB]
            stats, onehot, (((1,), (0,)), ((), ())),
            precision=_EXACT, preferred_element_type=jnp.float32)

        # Weighted value histogram over the family-global [lo, hi] range,
        # reduced by the SAME resident onehot: wbin[NB, B] @ onehot[B, GB].
        span = jnp.maximum(hi - lo, 1e-12)
        # Clip in f32 BEFORE the int cast: out-of-range values (padding
        # rows) would otherwise overflow the cast.
        bins = jnp.clip((v - lo) / span * n_bins,
                        0.0, n_bins - 1).astype(jnp.int32)    # [1, B]
        bids = jax.lax.broadcasted_iota(jnp.int32, (n_bins, 1), 0)
        wbin = (bins == bids).astype(jnp.float32) * w         # [NB, B]
        hist_ref[...] += jax.lax.dot_general(                 # [NB, GB]
            wbin, onehot, (((1,), (0,)), ((), ())),
            precision=_EXACT, preferred_element_type=jnp.float32)


@functools.partial(jax.jit, static_argnames=("ops_struct", "atom_slots",
                                             "n_groups", "n_bins",
                                             "block_rows", "block_groups",
                                             "interpret"))
def quantile_scan_pallas(values: jax.Array, unit: jax.Array, strat: jax.Array,
                         freq_table: jax.Array, valid: jax.Array, atom_cols,
                         group_codes: jax.Array, k: jax.Array, lo: jax.Array,
                         hi: jax.Array, pred_consts: jax.Array, *,
                         ops_struct, atom_slots=None, n_groups: int,
                         n_bins: int = DEFAULT_QUANTILE_BINS,
                         block_rows: int = DEFAULT_BLOCK_ROWS_BATCHED,
                         block_groups: int = DEFAULT_BLOCK_GROUPS,
                         interpret: bool = False,
                         row_spans: jax.Array | None = None
                         ) -> tuple[jax.Array, jax.Array]:
    """One-pass QUANTILE scan: (moments f32[7, G], hist f32[n_bins, G]).

    Same memory-lean streaming layout as agg_scan_fused_pallas, single
    query, with a second bins×groups output block: the HT-weighted value
    histogram over the fixed [lo, hi] range (pre-computed family-global
    bounds), bucketed as floor((v-lo)/span·n_bins) clipped to [0, n_bins).
    qconst lanes: 0 = k, 1 = lo, 2 = hi, 3..2+n_atoms = predicate consts.
    `row_spans` bounds the row grid as in agg_scan_fused_pallas.
    """
    n = values.shape[0]
    n_atoms = sum(len(c) for c in ops_struct)
    if n_atoms + 3 > CONST_LANES:
        raise ValueError(f"predicate has {n_atoms} atoms; max {CONST_LANES - 3}")
    if n_bins % 128 != 0:
        raise ValueError(f"n_bins must be a multiple of 128, got {n_bins}")
    atom_cols, atom_slots = _normalize_atoms(atom_cols, ops_struct, atom_slots, n)

    bg = min(block_groups, max(128, -(-n_groups // 128) * 128))
    g_pad = -(-n_groups // bg) * bg
    n_pad = -(-max(n, 1) // block_rows) * block_rows

    def pad(x, fill):
        return jnp.pad(x, (0, n_pad - n), constant_values=fill)

    v = pad(values.astype(jnp.float32), 0)
    u = pad(unit.astype(jnp.float32), jnp.inf)
    s = pad(strat, 0)
    va = pad(valid.astype(bool), False)
    c = pad(group_codes, 0)
    acols = [pad(a, 0) for a in atom_cols]
    ftab = _pad_ftab(freq_table)

    qconst = jnp.ones((8, CONST_LANES), jnp.float32)
    qconst = qconst.at[0, 0].set(jnp.asarray(k, jnp.float32))
    qconst = qconst.at[0, 1].set(jnp.asarray(lo, jnp.float32))
    qconst = qconst.at[0, 2].set(jnp.asarray(hi, jnp.float32))
    if n_atoms:
        qconst = qconst.at[0, 3:3 + n_atoms].set(
            pred_consts.astype(jnp.float32).reshape(-1))

    n_row_blocks = n_pad // block_rows
    plan = (jnp.full((3,), n_row_blocks, jnp.int32) if row_spans is None
            else visit_plan(row_spans, n_row_blocks, block_rows))
    mom, hist = pl.pallas_call(
        functools.partial(_fused_quantile_kernel, block_groups=bg,
                          ops_struct=ops_struct, atom_slots=atom_slots,
                          n_bins=n_bins),
        grid_spec=_bounded_grid(g_pad // bg, n_row_blocks, block_rows,
                                [qconst.shape, ftab.shape], 5 + len(acols),
                                [(N_STATS, bg), (n_bins, bg)]),
        out_shape=[
            jax.ShapeDtypeStruct((N_STATS, g_pad), jnp.float32),
            jax.ShapeDtypeStruct((n_bins, g_pad), jnp.float32),
        ],
        interpret=interpret,
    )(plan, qconst, ftab, v, u, s, va, c, *acols)
    return mom[:7, :n_groups], hist[:, :n_groups]
