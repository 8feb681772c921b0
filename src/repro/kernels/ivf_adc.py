"""Bucket-resident fused IVF-ADC + top-k Pallas kernel.

``pq_adc`` streams ALL N codes per query batch — IVF's candidate-set
reduction (probe nprobe buckets, score only their codes) buys nothing on
that path, and l2's per-(query, probe) residual LUT geometry cannot flatten
into it at all. This kernel executes the probe natively, so kernel-path
work scales with the probed candidate count instead of N.

Layout: inverted lists are BLOCK-ALIGNED (built by
``repro.core.ivf.build_block_lists``): cluster c owns ``ceil(count_c/blk)``
contiguous rows of a (B+1, blk) slot table (``bucket_ids`` global row ids,
``bucket_codes`` their PQ codes), the last row of a cluster padded with -1
ids, and row B is a shared all-pad block. Pad slack is <= blk-1 per cluster
instead of the (max - count) of a fixed-capacity bucket table — the layout
that keeps compressed-index bytes honest. Probing expands OUTSIDE the
kernel into a ``visit`` table: (Q, T) block ids with T = nprobe *
steps_per_probe, step t serving probe p = t // steps_per_probe (clusters
shorter than steps_per_probe blocks point their tail steps at the shared
pad block).

The gather is driven by scalar prefetch (``pltpu.PrefetchScalarGridSpec``):
``visit`` is available before the kernel body runs, and the code/id
``index_map``s read ``visit[q, t]`` to pick which block the program's DMA
fetches — the classic gather-via-prefetch pattern, no vector gather needed.

Per program: the block's (blk, m) codes expand to a one-hot selector and
contract against that query's LUT row on the MXU (exactly the pq_adc
trick), plus a per-(query, probe) scalar ``coarse`` term that carries the
metric geometry:

  dot: one shared (m, ksub) LUT per query; coarse[q, p] = q . centroid_p
       (residual codes score q.residual, the centroid term is additive).
  l2:  per-(query, probe) LUTs on t = q - centroid_p (4-D luts input);
       coarse[q, p] = 0.

``coarse`` doubles as a probe knockout: callers mask a whole probe by
adding NEG_INF to its coarse term; pad slots (id -1) knock out in-kernel.
The -1 sentinel is also how PREDICATE FILTERS reach this kernel
(invariant 6): ``ops.ivf_adc_topk(allowed=...)`` rewrites ``bucket_ids``
so filtered-out slots read as -1 — the kernel itself never learns about
filters, and an all-true bitmap leaves its inputs (hence outputs)
bit-identical.

Results fold into a per-query (1, k) VMEM scoreboard across the T grid
steps (same unrolled knockout top-k as topk_distance), written out at the
last step. Returned ids are the GLOBAL row ids stored in ``bucket_ids``.

LUT precision (``lut_dtype``): f32, bf16 (2x MXU rate, documented
m * 2^-8 * max|lut| score bound), or int8 with per-(query, subspace) absmax
scales — the table is stored and contracted as int8 (int8 x int8 one-hot ->
int32 partials on the MXU, exact), then the m partials are scaled and summed
in f32: score = sum_j scale[q, j] * lut_i8[q, j, codes[n, j]]. vs bf16 that
is another 2x off the resident table bytes; the quantization error per
subspace is <= scale/2 = max|lut_j| / 254.

Three grid modes share the scoring math:

  * per-query (``ivf_adc``) — grid (Q, T), one (query, probe-step) per
    program: a block probed by s queries is DMA'd s times and each
    contraction is a (1, m*ksub) matvec (MXU at 1/8-1/128 utilization).
  * blocked (``ivf_adc_blocked``) — grid (G,) over the SEGMENTED schedule
    built by ``repro.core.ivf.build_block_schedule``: program g DMAs block
    ``sched_block[g]`` ONCE and contracts it against that group's
    pre-gathered (qblk, m*ksub) LUT panel — a genuine MXU matmul — then
    folds each slot's (1, blk) scores into its query's row of a
    (Q + 1, k) VMEM scoreboard (row Q is the trash row that knockout-
    sentinel slots land in). Panel HBM traffic matches the per-query
    grid's LUT traffic (each pair still reads one LUT row); the win is
    the shared code-block DMA, the dropped pad-block pairs, and the
    matmul-shaped contraction.
  * run-resident (``ivf_adc_run_resident``) — grid (R,) over the
    schedule's per-block RUNS (``stats["runs"]``): a block shared by s
    queries still costs the blocked grid ceil(s/qblk) DMAs (one per
    group); here program r DMAs block ``run_block[r]`` once for the WHOLE
    batch, expands its one-hot selector once, and an inner
    ``jax.lax.fori_loop`` walks the run's ``run_len[r]`` groups — each
    group's LUT panel is manually DMA'd into a double-buffered VMEM
    scratch so the NEXT panel's fetch overlaps the current contraction,
    while the grid pipeline overlaps the next RUN's block DMA the same
    way. Code-block HBM traffic drops from G to R fetches; panel traffic
    is unchanged (each pair still reads one LUT row).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.pq_adc import adc_block_scores, quantize_lut_int8
from repro.kernels.topk_distance import NEG_INF, _select_topk


def _probe_coarse(crow, p):
    """(1, nprobe) coarse row -> (1, 1) entry p, selected by a lane mask
    (exact: one nonzero term)."""
    lane = jax.lax.broadcasted_iota(jnp.int32, crow.shape, 1)
    return jnp.sum(jnp.where(lane == p, crow, 0.0), axis=1, keepdims=True)


def _ivf_adc_kernel(visit_ref, c_ref, id_ref, l_ref, coarse_ref, *refs,
                    n_steps: int, spp: int, k: int, ksub: int, int8: bool):
    if int8:
        sc_ref, s_out, i_out, bs_ref, bi_ref = refs
    else:
        sc_ref = None
        s_out, i_out, bs_ref, bi_ref = refs
    t = pl.program_id(1)

    @pl.when(t == 0)
    def _init():
        bs_ref[...] = jnp.full_like(bs_ref, NEG_INF)
        bi_ref[...] = jnp.full_like(bi_ref, -1)

    ids = id_ref[0]        # (1, blk) int32 global row ids, -1 = pad slot
    s = adc_block_scores(l_ref[0], c_ref[0], ksub,
                         None if sc_ref is None else sc_ref[0])  # (1, blk)
    # coarse carries the metric's centroid term AND the caller's probe
    # knockout (NEG_INF for masked probes); pad slots knock out on id
    s = s + _probe_coarse(coarse_ref[0], t // spp)
    s = jnp.where(ids >= 0, s, NEG_INF)

    comb_s = jnp.concatenate([bs_ref[...], s], axis=1)
    comb_i = jnp.concatenate([bi_ref[...], ids], axis=1)
    bs_ref[...], bi_ref[...] = _select_topk(comb_s, comb_i, k)

    @pl.when(t == n_steps - 1)
    def _finalize():
        s_out[0] = bs_ref[...]
        i_out[0] = bi_ref[...]


def _prep_luts(luts, lut_dtype: str):
    """Cast / quantize the tables once per call -> (luts, scales or None)."""
    if lut_dtype == "int8":
        return quantize_lut_int8(luts)
    if jnp.dtype(lut_dtype) != jnp.float32:
        return luts.astype(jnp.dtype(lut_dtype)), None
    return luts, None


# scalar memory the per-query grid's prefetched visit table may take: the
# chip has 1 MiB of SMEM per core, shared with the compiler's own scalars;
# larger batches run as a loop over query chunks that each fit
SMEM_VISIT_BYTES = 512 << 10


@functools.partial(jax.jit,
                   static_argnames=("k", "steps_per_probe", "interpret",
                                    "lut_dtype"))
def ivf_adc(bucket_codes, bucket_ids, visit, luts, coarse, *, k: int,
            steps_per_probe: int = 1, interpret: bool = False,
            lut_dtype: str = "float32"):
    """bucket_codes: (B, blk, m) int32; bucket_ids: (B, blk) int32 (-1
    pad); visit: (Q, T) int32 block ids, T = nprobe * steps_per_probe;
    luts: (Q, m, ksub) f32 (shared, dot) or (Q, nprobe, m, ksub) f32
    (per-probe, l2); coarse: (Q, nprobe) f32
    -> (scores (Q, k) f32, ids (Q, k) int32).

    Grid step (q, t) scores block visit[q, t] for probe
    p = t // steps_per_probe:
      score[q, n in block] = sum_j luts[q(, p), j, codes[n, j]] + coarse[q, p]
    with pad slots (id -1) and anything the caller NEG_INF'd in ``coarse``
    knocked to NEG_INF. Unfilled scoreboard slots come back NEG_INF / -1
    (the ops.py dispatcher normalizes them to -inf / -1).
    """
    B, blk, m = bucket_codes.shape
    Q, T = visit.shape
    spp = steps_per_probe
    assert T % spp == 0, (T, spp)
    nprobe = T // spp
    per_probe = luts.ndim == 4
    ksub = luts.shape[-1]
    luts, scales = _prep_luts(luts, lut_dtype)
    # per-row operands gain a unit axis so every block's last two dims
    # equal the array's (the TPU lowering's rule for non-(8, 128) blocks)
    P = nprobe if per_probe else 1
    codes = bucket_codes.astype(jnp.int32)
    ids = bucket_ids.astype(jnp.int32).reshape(B, 1, blk)
    per_q = [visit.astype(jnp.int32), luts.reshape(Q, P, 1, m * ksub),
             coarse.astype(jnp.float32).reshape(Q, 1, nprobe)]
    if scales is not None:
        per_q.append(scales.reshape(Q, P, 1, m))

    def run(visit_c, luts_c, coarse_c, *scales_c):
        qc = visit_c.shape[0]
        if per_probe:
            def row(q, t):
                return q * P + t // spp
        else:
            def row(q, t):
                return q
        # every index_map sees the prefetched (flattened) visit table last
        in_specs = [
            pl.BlockSpec((1, blk, m), lambda q, t, v: (v[q * T + t], 0, 0)),
            pl.BlockSpec((1, 1, blk), lambda q, t, v: (v[q * T + t], 0, 0)),
            pl.BlockSpec((1, 1, m * ksub),
                         lambda q, t, v: (row(q, t), 0, 0)),
            pl.BlockSpec((1, 1, nprobe), lambda q, t, v: (q, 0, 0)),
        ]
        args = [codes, ids, luts_c.reshape(qc * P, 1, m * ksub), coarse_c]
        if scales_c:
            in_specs.append(
                pl.BlockSpec((1, 1, m), lambda q, t, v: (row(q, t), 0, 0)))
            args.append(scales_c[0].reshape(qc * P, 1, m))
        kernel = functools.partial(_ivf_adc_kernel, n_steps=T, spp=spp, k=k,
                                   ksub=ksub, int8=bool(scales_c))
        grid_spec = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(qc, T),
            in_specs=in_specs,
            out_specs=[
                pl.BlockSpec((1, 1, k), lambda q, t, v: (q, 0, 0)),
                pl.BlockSpec((1, 1, k), lambda q, t, v: (q, 0, 0)),
            ],
            scratch_shapes=[
                pltpu.VMEM((1, k), jnp.float32),
                pltpu.VMEM((1, k), jnp.int32),
            ],
        )
        return pl.pallas_call(
            kernel,
            grid_spec=grid_spec,
            out_shape=[
                jax.ShapeDtypeStruct((qc, 1, k), jnp.float32),
                jax.ShapeDtypeStruct((qc, 1, k), jnp.int32),
            ],
            interpret=interpret,
        )(visit_c.reshape(qc * T), *args)

    qc = max(1, min(Q, SMEM_VISIT_BYTES // (4 * T)))
    if qc == Q:
        s, i = run(*per_q)
    else:
        # query chunks padded to a whole number (pad rows are sliced off)
        n_c = -(-Q // qc)
        per_q = [jnp.pad(x, ((0, n_c * qc - Q),) + ((0, 0),) * (x.ndim - 1)
                         ).reshape((n_c, qc) + x.shape[1:]) for x in per_q]
        s, i = jax.lax.map(lambda xs: run(*xs), per_q)
    return s.reshape(-1, k)[:Q], i.reshape(-1, k)[:Q]


def _merge_slots(s, ids, qrow_ref, prow_ref, base, coarse_ref, bs_ref,
                 bi_ref, k: int):
    """Fold each slot's (1, blk) scores into its query's scoreboard row.
    Slot i scores pair (qrow[base + i], probe prow[base + i]); its coarse
    term comes from that pair's row of the (Q + 1, nprobe) coarse table,
    whose last row is NEG_INF — the trash row sentinel slots land in."""
    for slot in range(s.shape[0]):  # static unroll: qblk dynamic-row RMWs
        row = qrow_ref[base + slot]
        ss = s[slot:slot + 1, :] + _probe_coarse(
            coarse_ref[pl.ds(row, 1), :], prow_ref[base + slot])
        ss = jnp.where(ids >= 0, ss, NEG_INF)
        comb_s = jnp.concatenate([bs_ref[pl.ds(row, 1), :], ss], axis=1)
        comb_i = jnp.concatenate([bi_ref[pl.ds(row, 1), :], ids], axis=1)
        ns, ni = _select_topk(comb_s, comb_i, k)
        bs_ref[pl.ds(row, 1), :] = ns
        bi_ref[pl.ds(row, 1), :] = ni


def _ivf_adc_blocked_kernel(sb_ref, qrow_ref, prow_ref, c_ref, id_ref,
                            panel_ref, coarse_ref, *refs, n_groups: int,
                            n_q: int, k: int, ksub: int, qblk: int,
                            int8: bool):
    if int8:
        scp_ref, s_out, i_out, bs_ref, bi_ref = refs
    else:
        scp_ref = None
        s_out, i_out, bs_ref, bi_ref = refs
    g = pl.program_id(0)

    @pl.when(g == 0)
    def _init():
        bs_ref[...] = jnp.full_like(bs_ref, NEG_INF)
        bi_ref[...] = jnp.full_like(bi_ref, -1)

    ids = id_ref[0]         # (1, blk) int32 global row ids, -1 = pad slot
    # the group's SHARED code block against its (qblk, m*ksub) LUT rows
    s = adc_block_scores(panel_ref[0], c_ref[0], ksub,
                         None if scp_ref is None else scp_ref[0])
    _merge_slots(s, ids, qrow_ref, prow_ref, g * qblk, coarse_ref, bs_ref,
                 bi_ref, k)

    @pl.when(g == n_groups - 1)
    def _finalize():
        s_out[...] = bs_ref[0:n_q, :]
        i_out[...] = bi_ref[0:n_q, :]


def _group_panels(luts, coarse, sched_q, sched_t, spp: int):
    """Pre-gather the grouped grids' operands: one LUT row per (q, probe)
    pair — the same per-pair LUT traffic the per-query grid pays, laid out
    so the contraction is a matmul. Sentinel slots read row 0 and score
    against the NEG_INF trash row of the padded coarse table.
    -> (panel (G, qblk, m*ksub), rows (G, qblk) LUT row per pair,
    qrow (G*qblk,) scoreboard row per pair (Q for sentinels),
    prow (G*qblk,) probe per pair, coarse (Q + 1, nprobe) f32 with the
    NEG_INF trash row appended)."""
    G, qblk = sched_q.shape
    Q, nprobe = coarse.shape
    per_probe = luts.ndim == 4
    m, ksub = luts.shape[-2:]
    qs = jnp.clip(sched_q, 0)
    p_of = sched_t // spp
    n_rows = Q * nprobe if per_probe else Q
    rows = qs * nprobe + p_of if per_probe else qs
    panel = jnp.take(luts.reshape(n_rows, m * ksub), rows.reshape(-1),
                     axis=0).reshape(G, qblk, m * ksub)
    qrow = jnp.where(sched_q >= 0, sched_q, Q).astype(jnp.int32)
    coarse = jnp.concatenate([coarse.astype(jnp.float32),
                              jnp.full((1, nprobe), NEG_INF, jnp.float32)])
    return (panel, rows, qrow.reshape(G * qblk),
            p_of.astype(jnp.int32).reshape(G * qblk), coarse)


def _group_scales(scales, rows, n_rows: int, m: int):
    G, qblk = rows.shape
    return jnp.take(scales.reshape(n_rows, m), rows.reshape(-1),
                    axis=0).reshape(G, qblk, m)


@functools.partial(jax.jit,
                   static_argnames=("k", "steps_per_probe", "interpret",
                                    "lut_dtype"))
def ivf_adc_blocked(bucket_codes, bucket_ids, sched_block, sched_q, sched_t,
                    luts, coarse, *, k: int, steps_per_probe: int = 1,
                    interpret: bool = False, lut_dtype: str = "float32"):
    """Blocked-mode twin of ``ivf_adc`` over a segmented schedule.

    sched_block: (G,) int32 block ids; sched_q/sched_t: (G, qblk) int32
    (query, visit-step) pairs, -1 in sched_q = knockout sentinel (see
    ``repro.core.ivf.build_block_schedule``). luts/coarse as in
    ``ivf_adc``. Program g fetches block sched_block[g] once, contracts it
    against the group's (qblk, m*ksub) LUT panel (pre-gathered in-graph —
    uniform across shared and per-probe LUT geometry), and merges each
    slot's scores into a per-query (1, k) scoreboard row.

    Scores are bit-identical to the per-query grid: both fold the same
    exact per-subspace partials in the same j order (``adc_block_scores``).
    -> (scores (Q, k) f32, ids (Q, k) int32), NEG_INF/-1 sentinels as in
    ``ivf_adc`` (the ops.py dispatcher normalizes).
    """
    B, blk, m = bucket_codes.shape
    G, qblk = sched_q.shape
    Q, nprobe = coarse.shape
    ksub = luts.shape[-1]
    luts, scales = _prep_luts(luts, lut_dtype)
    panel, rows, qrow, prow, coarse_t = _group_panels(
        luts, coarse, sched_q, sched_t, steps_per_probe)

    in_specs = [
        pl.BlockSpec((1, blk, m), lambda g, sb, qr, pr: (sb[g], 0, 0)),
        pl.BlockSpec((1, 1, blk), lambda g, sb, qr, pr: (sb[g], 0, 0)),
        pl.BlockSpec((1, qblk, m * ksub), lambda g, sb, qr, pr: (g, 0, 0)),
        pl.BlockSpec((Q + 1, nprobe), lambda g, sb, qr, pr: (0, 0)),
    ]
    args = [bucket_codes.astype(jnp.int32),
            bucket_ids.astype(jnp.int32).reshape(B, 1, blk), panel, coarse_t]
    if scales is not None:
        n_rows = Q * nprobe if luts.ndim == 4 else Q
        in_specs.append(
            pl.BlockSpec((1, qblk, m), lambda g, sb, qr, pr: (g, 0, 0)))
        args.append(_group_scales(scales, rows, n_rows, m))

    kernel = functools.partial(_ivf_adc_blocked_kernel, n_groups=G, n_q=Q,
                               k=k, ksub=ksub, qblk=qblk,
                               int8=scales is not None)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(G,),
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((Q, k), lambda g, sb, qr, pr: (0, 0)),
            pl.BlockSpec((Q, k), lambda g, sb, qr, pr: (0, 0)),
        ],
        scratch_shapes=[
            pltpu.VMEM((Q + 1, k), jnp.float32),  # row Q = sentinel trash
            pltpu.VMEM((Q + 1, k), jnp.int32),
        ],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((Q, k), jnp.float32),
            jax.ShapeDtypeStruct((Q, k), jnp.int32),
        ],
        interpret=interpret,
    )(sched_block.astype(jnp.int32), qrow, prow, *args)


def _ivf_adc_run_resident_kernel(rb_ref, rs_ref, rl_ref, qrow_ref, prow_ref,
                                 c_ref, id_ref, panel_hbm, coarse_ref, *refs,
                                 n_runs: int, n_q: int, k: int, ksub: int,
                                 qblk: int, int8: bool):
    if int8:
        scp_hbm, s_out, i_out, bs_ref, bi_ref, pbuf, sem, sbuf = refs
    else:
        scp_hbm = sbuf = None
        s_out, i_out, bs_ref, bi_ref, pbuf, sem = refs
    r = pl.program_id(0)

    @pl.when(r == 0)
    def _init():
        bs_ref[...] = jnp.full_like(bs_ref, NEG_INF)
        bi_ref[...] = jnp.full_like(bi_ref, -1)

    codes = c_ref[0]        # (blk, m) int32 — THE run's code block
    ids = id_ref[0]         # (1, blk) int32 global row ids, -1 = pad slot
    g0 = rs_ref[r]
    L = rl_ref[r]           # groups in this run (0 for pad runs)

    def copies(slot, g):
        """The group's LUT panel (and int8 scales) DMAs."""
        out = [pltpu.make_async_copy(panel_hbm.at[pl.ds(g, 1)],
                                     pbuf.at[slot], sem.at[0, slot])]
        if int8:
            out.append(pltpu.make_async_copy(scp_hbm.at[pl.ds(g, 1)],
                                             sbuf.at[slot], sem.at[1, slot]))
        return out

    @pl.when(L > 0)
    def _warm():                      # first panel in flight before the loop
        for c in copies(0, g0):
            c.start()

    def body(j, carry):
        slot = jax.lax.rem(j, 2)
        g = g0 + j

        @pl.when(j + 1 < L)
        def _prefetch():              # next panel races the contraction
            for c in copies(1 - slot, g + 1):
                c.start()

        for c in copies(slot, g):
            c.wait()
        s = adc_block_scores(pbuf[slot, 0], codes, ksub,
                             None if sbuf is None else sbuf[slot, 0])
        _merge_slots(s, ids, qrow_ref, prow_ref, g * qblk, coarse_ref,
                     bs_ref, bi_ref, k)
        return carry

    jax.lax.fori_loop(0, L, body, 0)

    @pl.when(r == n_runs - 1)
    def _finalize():
        s_out[...] = bs_ref[0:n_q, :]
        i_out[...] = bi_ref[0:n_q, :]


@functools.partial(jax.jit,
                   static_argnames=("k", "steps_per_probe", "interpret",
                                    "lut_dtype"))
def ivf_adc_run_resident(bucket_codes, bucket_ids, run_block, run_start,
                         run_len, sched_q, sched_t, luts, coarse, *, k: int,
                         steps_per_probe: int = 1, interpret: bool = False,
                         lut_dtype: str = "float32"):
    """Block-RESIDENT run-length twin of ``ivf_adc_blocked``.

    run_block/run_start/run_len: (R,) int32 — the per-block runs from
    ``build_block_schedule``'s ``stats["runs"]`` (run r covers schedule
    groups [run_start[r], run_start[r] + run_len[r]), all on block
    ``run_block[r]``; pad runs have run_len 0). sched_q/sched_t: the
    (G, qblk) group tables the runs index into. luts/coarse as in
    ``ivf_adc``.

    Program r fetches block run_block[r] ONCE for the whole batch (the
    grid pipeline double-buffers the next run's block against the current
    run's work), then loops the run's groups with an inner fori_loop,
    manually double-buffering each group's (qblk, m*ksub) LUT panel DMA
    against the previous group's contraction +
    scoreboard merge. Scores are bit-identical to the per-query and
    blocked grids (``adc_block_scores``).
    -> (scores (Q, k) f32, ids (Q, k) int32), NEG_INF/-1 sentinels as in
    ``ivf_adc`` (the ops.py dispatcher normalizes).
    """
    B, blk, m = bucket_codes.shape
    G, qblk = sched_q.shape
    R = run_block.shape[0]
    Q, nprobe = coarse.shape
    ksub = luts.shape[-1]
    luts, scales = _prep_luts(luts, lut_dtype)
    # same pre-gathered geometry as the blocked grid; the panels stay in
    # HBM and the kernel streams them per group
    panel, rows, qrow, prow, coarse_t = _group_panels(
        luts, coarse, sched_q, sched_t, steps_per_probe)

    in_specs = [
        pl.BlockSpec((1, blk, m),
                     lambda r, rb, rs, rl, qr, pr: (rb[r], 0, 0)),
        pl.BlockSpec((1, 1, blk),
                     lambda r, rb, rs, rl, qr, pr: (rb[r], 0, 0)),
        pl.BlockSpec(memory_space=pl.ANY),         # panel: streamed
        pl.BlockSpec((Q + 1, nprobe), lambda r, rb, rs, rl, qr, pr: (0, 0)),
    ]
    args = [bucket_codes.astype(jnp.int32),
            bucket_ids.astype(jnp.int32).reshape(B, 1, blk), panel, coarse_t]
    scratch = [
        pltpu.VMEM((Q + 1, k), jnp.float32),  # row Q = sentinel trash
        pltpu.VMEM((Q + 1, k), jnp.int32),
        pltpu.VMEM((2, 1, qblk, m * ksub), panel.dtype),
        pltpu.SemaphoreType.DMA((2 if scales is not None else 1, 2)),
    ]
    if scales is not None:
        n_rows = Q * nprobe if luts.ndim == 4 else Q
        # lanes pad to the 128 tiling so each group's slice is a whole-tile
        # DMA; the kernel reads only the first m columns
        mp = -(-m // 128) * 128
        in_specs.append(pl.BlockSpec(memory_space=pl.ANY))
        args.append(jnp.pad(_group_scales(scales, rows, n_rows, m),
                            ((0, 0), (0, 0), (0, mp - m))))
        scratch.append(pltpu.VMEM((2, 1, qblk, mp), jnp.float32))

    kernel = functools.partial(_ivf_adc_run_resident_kernel, n_runs=R,
                               n_q=Q, k=k, ksub=ksub, qblk=qblk,
                               int8=scales is not None)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=5,
        grid=(R,),
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((Q, k), lambda r, rb, rs, rl, qr, pr: (0, 0)),
            pl.BlockSpec((Q, k), lambda r, rb, rs, rl, qr, pr: (0, 0)),
        ],
        scratch_shapes=scratch,
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((Q, k), jnp.float32),
            jax.ShapeDtypeStruct((Q, k), jnp.int32),
        ],
        interpret=interpret,
    )(run_block.astype(jnp.int32), run_start.astype(jnp.int32),
      run_len.astype(jnp.int32), qrow, prow, *args)
