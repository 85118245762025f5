"""Fused Pallas TPU kernel for the t-digest flush-time compress.

The XLA expression of the compress (``tdigest._compress_presorted``) pays
HBM round trips between its stages — sort, prefix sum, k-binning,
segmented reduce — and the sort alone re-reads the [S, M] row set ~40
times. This kernel runs the whole pipeline per series-block in VMEM:

    1. bitonic MERGE (not sort): both inputs are row-ascending, so
       log2(L) compare-exchange stages suffice; implemented as static
       shift + select passes (Mosaic-friendly, no reshapes),
    2. log-step prefix sum for cumulative weights,
    3. k-scale binning with an Abramowitz-Stegun asin approximation
       (|err| <= 6.8e-5 rad => bin-edge shift < 0.003 of a bin, well
       inside the digest's accuracy envelope),
    4. chunked one-hot segmented reduce into the output bins.

One HBM read of the four input planes and one write of the two output
planes per row — everything else stays on-chip. The op it re-expresses
is the reference's mergeAllTemps scan (merging_digest.go:135-219).

The public entry ``compress_presorted`` falls back to the XLA path off
TPU (tests run on the CPU mesh) and for batch ranks other than 2.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl

from veneur_tpu.core.bucketing import bucketed

_ROWS = 128          # series rows per kernel block
_KCHUNK = 16         # output bins reduced per inner step
# Mosaic addresses kernel operands with 32-bit byte offsets, so any single
# pallas_call operand must stay under 2 GiB. Rows beyond this bound are
# processed in row-slabs (the padded [slab, 256] f32 plane at 1M rows is
# 1 GiB); the slab loop unrolls into a handful of kernel launches that XLA
# schedules back-to-back over the same HBM planes.
_MAX_SLAB_ROWS = 1 << 20
# Rows one trip of the live-row-bounded flush works on
# (tdigest.drain_and_quantile with a row count): 16 kernel blocks. A
# batch of at most this many rows is the straight-line program.
# Swept on a v5e at 2^20 reserved rows (PERF.md, PR 30), 320 / 205,280
# live rows: 512 rows 1.2 / 30.3 ms, 1,024 1.3 / 29.0, 2,048 1.4-1.6 /
# 28.4, 4,096 2.0 / 28.7, 8,192 2.7 / 31.6, 32,768 5.9 / 33.4; the
# straight-line program 155.6 ms whatever is live.
_FLUSH_SLAB_ROWS = 2048


def _row_slabs(total: int):
    """Yield (start, size) row spans each small enough for one kernel call."""
    start = 0
    while start < total:
        size = min(_MAX_SLAB_ROWS, total - start)
        yield start, size
        start += size


@bucketed("pow2")
def _next_pow2(n: int) -> int:
    return 1 << (n - 1).bit_length()


def _shift_left(x: jax.Array, d: int, fill: float) -> jax.Array:
    """out[:, i] = x[:, i+d]; right-pads with fill."""
    pad = jnp.full((x.shape[0], d), fill, x.dtype)
    return jnp.concatenate([x[:, d:], pad], axis=1)


def _shift_right(x: jax.Array, d: int, fill: float) -> jax.Array:
    """out[:, i] = x[:, i-d]; left-pads with fill."""
    pad = jnp.full((x.shape[0], d), fill, x.dtype)
    return jnp.concatenate([pad, x[:, :-d]], axis=1)


def _bitonic_merge(key: jax.Array, w: jax.Array):
    """Merge a row-bitonic sequence ascending. Static log2(L) stages of
    shift + compare + select; lead positions of each 2d-block pair with
    i+d, trail positions with i-d."""
    l = key.shape[1]
    d = l // 2
    while d >= 1:
        lead = (jax.lax.broadcasted_iota(jnp.int32, key.shape, 1) // d) % 2 == 0
        k_up = _shift_left(key, d, jnp.inf)
        k_dn = _shift_right(key, d, -jnp.inf)
        w_up = _shift_left(w, d, 0.0)
        w_dn = _shift_right(w, d, 0.0)
        swap_lead = key > k_up          # lead keeps the min
        swap_trail = k_dn > key         # trail keeps the max
        new_key = jnp.where(lead,
                            jnp.where(swap_lead, k_up, key),
                            jnp.where(swap_trail, k_dn, key))
        new_w = jnp.where(lead,
                          jnp.where(swap_lead, w_up, w),
                          jnp.where(swap_trail, w_dn, w))
        key, w = new_key, new_w
        d //= 2
    return key, w


def _bitonic_sort_desc(key: jax.Array, w: jax.Array):
    """Full bitonic sort DESCENDING along axis 1 (length must be a power
    of two). Empty slots carry key=+inf and therefore sort to the FRONT —
    exactly the layout the merge stage expects for the b half (the
    pre-reversed ascending list). Replaces the callers' XLA lax.sort,
    which round-trips HBM on every one of its ~log^2 passes; here the
    whole network runs on the block in VMEM."""
    l = key.shape[1]
    iota = jax.lax.broadcasted_iota(jnp.int32, key.shape, 1)
    k = 2
    while k <= l:
        # bitonic direction per k-block, inverted for a descending result
        # (at k == l the sign is uniform: one final descending pass).
        # Encoded as a per-position key sign flip — "keep min of the
        # signed key" — because Mosaic cannot select between i1 vectors.
        sk_sign = jnp.where((iota & k) == 0, -1.0, 1.0)
        j = k // 2
        while j >= 1:
            lead = (iota & j) == 0
            sk = sk_sign * key
            sk_up = _shift_left(sk, j, jnp.inf)
            sk_dn = _shift_right(sk, j, -jnp.inf)
            k_up = _shift_left(key, j, jnp.inf)
            k_dn = _shift_right(key, j, -jnp.inf)
            w_up = _shift_left(w, j, 0.0)
            w_dn = _shift_right(w, j, 0.0)
            swap_lead = sk > sk_up          # lead keeps the signed min
            swap_trail = sk_dn > sk         # trail keeps the signed max
            new_key = jnp.where(lead,
                                jnp.where(swap_lead, k_up, key),
                                jnp.where(swap_trail, k_dn, key))
            new_w = jnp.where(lead,
                              jnp.where(swap_lead, w_up, w),
                              jnp.where(swap_trail, w_dn, w))
            key, w = new_key, new_w
            j //= 2
        k *= 2
    return key, w


def _prefix_sum(x: jax.Array) -> jax.Array:
    """Inclusive prefix sum along axis 1 via log-step shifts."""
    d = 1
    n = x.shape[1]
    while d < n:
        x = x + _shift_right(x, d, 0.0)
        d *= 2
    return x


def _asin_poly(x: jax.Array) -> jax.Array:
    """Abramowitz & Stegun 4.4.45 asin approximation, |err| <= 6.8e-5.
    Monotone on [-1, 1]; Mosaic has no native asin."""
    s = jnp.sign(x)
    a = jnp.abs(x)
    p = 1.5707288 + a * (-0.2121144 + a * (0.0742610 + a * -0.0187293))
    return s * (0.5 * jnp.pi - jnp.sqrt(jnp.maximum(1.0 - a, 0.0)) * p)


def _compress_kernel(ma_ref, wa_ref, mb_ref, wb_ref, om_ref, ow_ref, *,
                     compression: float, half: int, kout: int, m: int,
                     sort_b: bool):
    nm, sw = _merge_bin_reduce(ma_ref[...], wa_ref[...], mb_ref[...],
                               wb_ref[...], compression, half, kout, m,
                               sort_b)
    om_ref[...] = nm
    ow_ref[...] = sw


def _merge_bin_reduce(ma, wa, mb, wb, compression: float, half: int,
                      kout: int, m: int, sort_b: bool = False):
    """Shared kernel body: bitonic-merge the two halves (b pre-reversed —
    or, with sort_b, sorted descending right here in VMEM), assign
    k-scale bins, and segment-reduce into kout output bins.
    Returns (nm, sw) with dead bins carrying mean == -inf."""
    rows = ma.shape[0]

    def pad_to(x, width, fill):
        if x.shape[1] == width:
            return x
        return jnp.concatenate(
            [x, jnp.full((rows, width - x.shape[1]), fill, x.dtype)], axis=1)

    if sort_b:
        # unsorted b half (empties = +inf): descending in-kernel sort
        # lands +inf pads in front — the same layout the pre-reversed
        # path produces, at VMEM cost instead of ~log^2 HBM sort passes
        mb, wb = _bitonic_sort_desc(mb, wb)
    key = jnp.concatenate([pad_to(ma, half, jnp.inf), mb], axis=1)
    w = jnp.concatenate([pad_to(wa, half, 0.0), wb], axis=1)
    key, w = _bitonic_merge(key, w)
    key, w = key[:, :m], w[:, :m]   # +inf pads sort to the back

    live = w > 0
    m0 = jnp.where(live, key, 0.0)
    incl = _prefix_sum(w)
    total = jnp.max(incl, axis=1, keepdims=True)
    q_mid = (incl - 0.5 * w) / jnp.maximum(total, 1e-30)
    kq = compression * (_asin_poly(jnp.clip(2.0 * q_mid - 1.0, -1.0, 1.0))
                        / jnp.pi + 0.5)
    cluster = jnp.clip(jnp.floor(kq), 0.0, float(kout - 1))
    wm = w * m0

    sw_parts, swm_parts = [], []
    for c0 in range(0, kout, _KCHUNK):
        targets = (jax.lax.broadcasted_iota(jnp.int32, (_KCHUNK, 1), 0)
                   .astype(jnp.float32) + float(c0))
        hit = cluster[:, None, :] == targets[None, :, :]      # [R, KC, M]
        sw_parts.append(jnp.sum(jnp.where(hit, w[:, None, :], 0.0), axis=2))
        swm_parts.append(jnp.sum(jnp.where(hit, wm[:, None, :], 0.0), axis=2))
    # kout need not be a multiple of _KCHUNK; trim the overshoot (those
    # bins can never be hit — cluster ids are clipped to kout-1)
    sw = jnp.concatenate(sw_parts, axis=1)[:, :kout]          # [R, K]
    swm = jnp.concatenate(swm_parts, axis=1)[:, :kout]
    live_o = sw > 0
    nm = jnp.where(live_o, swm / jnp.where(live_o, sw, 1.0), -jnp.inf)
    return nm, sw


def _suffix_min(x: jax.Array) -> jax.Array:
    """Right-to-left running min along axis 1 (log-step)."""
    d, n = 1, x.shape[1]
    while d < n:
        x = jnp.minimum(x, _shift_left(x, d, jnp.inf))
        d *= 2
    return x


def _cummax(x: jax.Array) -> jax.Array:
    d, n = 1, x.shape[1]
    while d < n:
        x = jnp.maximum(x, _shift_right(x, d, -jnp.inf))
        d *= 2
    return x


def _kernel_quantiles(nm, sw, mn, mx, qs, kout: int, nq: int):
    """In-kernel batched inverse-CDF over the freshly reduced bins,
    mirroring tdigest.quantile/_upper_bounds exactly (the in-VMEM rows
    make the per-q one-hot gathers ~3% of the segmented-reduce cost)."""
    live = sw > 0
    masked = jnp.where(live, nm, jnp.inf)
    suffix = _suffix_min(masked)
    next_m = _shift_left(suffix, 1, jnp.inf)
    live_ub = jnp.where(jnp.isfinite(next_m), 0.5 * (nm + next_m), mx)
    ub = _cummax(jnp.where(live, live_ub, -jnp.inf))
    ub_prev = _shift_right(ub, 1, 0.0)
    incl = _prefix_sum(sw)
    total = jnp.max(incl, axis=1, keepdims=True)
    excl = incl - sw
    pos = (jax.lax.broadcasted_iota(jnp.int32, (1, kout), 1)
           .astype(jnp.float32))
    outs = []
    for p in range(nq):
        target = qs[0, p] * total                       # [R, 1]
        idx = jnp.sum((incl < target).astype(jnp.float32), axis=1,
                      keepdims=True)                    # [R, 1]
        idx = jnp.minimum(idx, float(kout - 1))
        hit = pos == idx                                # [R, K]
        gather = lambda a: jnp.sum(jnp.where(hit, a, 0.0), axis=1,
                                   keepdims=True)
        ub_i, prev_ub, w_i, excl_i = (gather(ub), gather(ub_prev),
                                      gather(sw), gather(excl))
        # leading gap bins carry ub == -inf; fall back to min
        lb = jnp.where(idx == 0, mn, jnp.maximum(prev_ub, mn))
        prop = (target - excl_i) / jnp.where(w_i > 0, w_i, 1.0)
        out = lb + prop * (ub_i - lb)
        outs.append(jnp.where(total > 0, out, jnp.nan))
    return jnp.concatenate(outs, axis=1)                # [R, P]


def _drain_kernel(ma_ref, wa_ref, mb_ref, wb_ref, mn_ref, mx_ref, qs_ref,
                  om_ref, ow_ref, pct_ref, *, compression: float, half: int,
                  kout: int, m: int, nq: int, sort_b: bool):
    """compress + quantile fused: one VMEM round for the whole flush."""
    nm, sw = _merge_bin_reduce(ma_ref[...], wa_ref[...], mb_ref[...],
                               wb_ref[...], compression, half, kout, m,
                               sort_b)
    om_ref[...] = nm
    ow_ref[...] = sw
    pct_ref[...] = _kernel_quantiles(nm, sw, mn_ref[...], mx_ref[...],
                                     qs_ref[...], kout, nq)


# kernel -> times its body was traced for the backend (not interpret
# mode). These programs are only ever called from inside other jitted
# programs, where their own executable cache stays empty; a trace is
# the evidence that the Mosaic kernel went into a compiled program
# (obs/kernels.py reports it next to the compiled-variant counts).
TRACED = {"_drain_quantile_pallas": 0, "_compress_presorted_pallas": 0}


@functools.partial(jax.jit,
                   static_argnames=("compression", "out_size", "interpret",
                                    "sort_b"))
def _drain_quantile_pallas(mean_a, weight_a, mean_b, weight_b, mn, mx, qs,
                           compression: float, out_size: int,
                           interpret: bool = False, sort_b: bool = False):
    """Fused drain + percentile program. mean_b/weight_b must be
    row-ascending — or arbitrary-order with sort_b=True (empties = +inf),
    in which case the kernel sorts them in VMEM. mn/mx are the final
    per-row extrema [S]; qs is [P]. Rows are processed in <= 1M-row slabs
    to respect Mosaic's 32-bit operand addressing."""
    TRACED["_drain_quantile_pallas"] += not interpret
    s = mean_a.shape[0]
    if s > _MAX_SLAB_ROWS:
        outs = [
            _drain_quantile_slab(
                mean_a[st:st + sz], weight_a[st:st + sz],
                mean_b[st:st + sz], weight_b[st:st + sz],
                mn[st:st + sz], mx[st:st + sz], qs, compression, out_size,
                interpret, sort_b)
            for st, sz in _row_slabs(s)]
        return tuple(jnp.concatenate(parts, axis=0) for parts in zip(*outs))
    return _drain_quantile_slab(mean_a, weight_a, mean_b, weight_b, mn, mx,
                                qs, compression, out_size, interpret, sort_b)


def _drain_quantile_slab(mean_a, weight_a, mean_b, weight_b, mn, mx, qs,
                         compression: float, out_size: int,
                         interpret: bool = False, sort_b: bool = False):
    s, ka = mean_a.shape
    kb = mean_b.shape[1]
    nq = qs.shape[0]
    half = _next_pow2(max(ka, kb))
    rows = _ROWS
    pad_rows = (-s) % rows
    if pad_rows:
        zf = lambda x, fill: jnp.concatenate(
            [x, jnp.full((pad_rows,) + x.shape[1:], fill, x.dtype)], axis=0)
        mean_a, weight_a = zf(mean_a, jnp.inf), zf(weight_a, 0.0)
        mean_b, weight_b = zf(mean_b, jnp.inf), zf(weight_b, 0.0)
        mn, mx = zf(mn, jnp.inf), zf(mx, -jnp.inf)
    sp = s + pad_rows
    kb_real = kb
    mean_b = jnp.pad(mean_b, ((0, 0), (0, half - kb)),
                     constant_values=jnp.inf)
    weight_b = jnp.pad(weight_b, ((0, 0), (0, half - kb)))
    if not sort_b:
        # pre-reversed ascending list: +inf pads land in front
        mean_b = jnp.flip(mean_b, axis=1)
        weight_b = jnp.flip(weight_b, axis=1)

    kernel = functools.partial(_drain_kernel, compression=compression,
                               half=half, kout=out_size, m=ka + kb_real,
                               nq=nq, sort_b=sort_b)
    out_mean, out_w, pcts = pl.pallas_call(
        kernel,
        grid=(sp // rows,),
        in_specs=[pl.BlockSpec((rows, ka), lambda i: (i, 0)),
                  pl.BlockSpec((rows, ka), lambda i: (i, 0)),
                  pl.BlockSpec((rows, half), lambda i: (i, 0)),
                  pl.BlockSpec((rows, half), lambda i: (i, 0)),
                  pl.BlockSpec((rows, 1), lambda i: (i, 0)),
                  pl.BlockSpec((rows, 1), lambda i: (i, 0)),
                  pl.BlockSpec((1, nq), lambda i: (0, 0))],
        out_specs=[pl.BlockSpec((rows, out_size), lambda i: (i, 0)),
                   pl.BlockSpec((rows, out_size), lambda i: (i, 0)),
                   pl.BlockSpec((rows, nq), lambda i: (i, 0))],
        out_shape=[jax.ShapeDtypeStruct((sp, out_size), jnp.float32),
                   jax.ShapeDtypeStruct((sp, out_size), jnp.float32),
                   jax.ShapeDtypeStruct((sp, nq), jnp.float32)],
        interpret=interpret,
    )(mean_a, weight_a, mean_b, weight_b, mn[:, None], mx[:, None],
      qs[None, :])
    if pad_rows:
        out_mean, out_w, pcts = out_mean[:s], out_w[:s], pcts[:s]
    out_mean = lax.cummax(out_mean, axis=out_mean.ndim - 1)
    return out_mean, out_w, pcts


def drain_quantile(mean_a, weight_a, mean_b, weight_b, mn, mx,
                   qs, compression: float, out_size: int,
                   interpret: bool = False, sort_b: bool = False):
    """Public fused drain+quantile; the a half must be row-ascending and
    mn/mx the final extrema. The b half must be row-ascending too unless
    sort_b=True (then any order, empties carrying mean=+inf, sorted on
    the block in VMEM — cheaper than a caller-side lax.sort)."""
    return _drain_quantile_pallas(mean_a, weight_a, mean_b,
                                  weight_b, mn, mx, qs, compression,
                                  out_size, interpret=interpret,
                                  sort_b=sort_b)


@functools.partial(jax.jit,
                   static_argnames=("compression", "out_size", "interpret",
                                    "sort_b"))
def _compress_presorted_pallas(mean_a, weight_a, mean_b, weight_b,
                               compression: float, out_size: int,
                               interpret: bool = False,
                               sort_b: bool = False):
    TRACED["_compress_presorted_pallas"] += not interpret
    s = mean_a.shape[0]
    if s > _MAX_SLAB_ROWS:
        outs = [
            _compress_presorted_slab(
                mean_a[st:st + sz], weight_a[st:st + sz],
                mean_b[st:st + sz], weight_b[st:st + sz],
                compression, out_size, interpret, sort_b)
            for st, sz in _row_slabs(s)]
        return tuple(jnp.concatenate(parts, axis=0) for parts in zip(*outs))
    return _compress_presorted_slab(mean_a, weight_a, mean_b, weight_b,
                                    compression, out_size, interpret, sort_b)


def _compress_presorted_slab(mean_a, weight_a, mean_b, weight_b,
                             compression: float, out_size: int,
                             interpret: bool = False, sort_b: bool = False):
    s, ka = mean_a.shape
    kb = mean_b.shape[1]
    half = _next_pow2(max(ka, kb))
    rows = _ROWS
    pad_rows = (-s) % rows
    if pad_rows:
        zf = lambda x, fill: jnp.concatenate(
            [x, jnp.full((pad_rows, x.shape[1]), fill, x.dtype)], axis=0)
        mean_a, weight_a = zf(mean_a, jnp.inf), zf(weight_a, 0.0)
        mean_b, weight_b = zf(mean_b, jnp.inf), zf(weight_b, 0.0)
    sp = s + pad_rows
    kb_real = kb
    mean_b = jnp.pad(mean_b, ((0, 0), (0, half - kb)),
                     constant_values=jnp.inf)
    weight_b = jnp.pad(weight_b, ((0, 0), (0, half - kb)))
    if not sort_b:
        # pre-reverse the (already ascending) half outside the kernel
        mean_b = jnp.flip(mean_b, axis=1)
        weight_b = jnp.flip(weight_b, axis=1)
    kb = half

    kernel = functools.partial(_compress_kernel, compression=compression,
                               half=half, kout=out_size, m=ka + kb_real,
                               sort_b=sort_b)
    out_mean, out_w = pl.pallas_call(
        kernel,
        grid=(sp // rows,),
        in_specs=[pl.BlockSpec((rows, ka), lambda i: (i, 0)),
                  pl.BlockSpec((rows, ka), lambda i: (i, 0)),
                  pl.BlockSpec((rows, kb), lambda i: (i, 0)),
                  pl.BlockSpec((rows, kb), lambda i: (i, 0))],
        out_specs=[pl.BlockSpec((rows, out_size), lambda i: (i, 0)),
                   pl.BlockSpec((rows, out_size), lambda i: (i, 0))],
        out_shape=[jax.ShapeDtypeStruct((sp, out_size), jnp.float32),
                   jax.ShapeDtypeStruct((sp, out_size), jnp.float32)],
        interpret=interpret,
    )(mean_a, weight_a, mean_b, weight_b)
    if pad_rows:
        out_mean, out_w = out_mean[:s], out_w[:s]
    # gap-fill empty bins with the running max mean so rows stay ascending
    out_mean = lax.cummax(out_mean, axis=out_mean.ndim - 1)
    return out_mean, out_w


def pallas_ok(mean_a: jax.Array) -> bool:
    """The kernel applies to [S, K] f32 batches on a real TPU backend.
    A backend that cannot initialise raises here: that is an error, not
    a reason to take the XLA path quietly."""
    on_tpu = jax.default_backend() == "tpu" or any(
        d.platform == "tpu" for d in jax.devices())
    return (on_tpu and mean_a.ndim == 2
            and mean_a.dtype == jnp.float32)


def compress_presorted(mean_a, weight_a, mean_b, weight_b,
                       compression: float, out_size: int,
                       interpret: bool = False, sort_b: bool = False):
    """Fused compress of a row-ascending list with a second list that is
    either row-ascending or (sort_b=True) arbitrary-order with empties at
    mean=+inf; falls back to the sort-based XLA compress off-TPU / for
    unsupported shapes (which sorts everything itself, so sort_b only
    matters on the kernel path)."""
    if interpret or pallas_ok(mean_a):
        return _compress_presorted_pallas(
            mean_a, weight_a, mean_b, weight_b, compression, out_size,
            interpret=interpret, sort_b=sort_b)
    from veneur_tpu.ops import tdigest as td

    return td._compress(jnp.concatenate([mean_a, mean_b], axis=-1),
                        jnp.concatenate([weight_a, weight_b], axis=-1),
                        compression, out_size)
