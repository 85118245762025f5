"""The accuracy-sweep harness (analysis/tdigest_sweep.py — the
reference's ``tdigest/analysis`` role) and the shift-guarded ingest it
motivated: ordered/shifting arrival previously aliased values across
temp bins (0.44 rank error measured pre-fix); the quantile-anchored
binning + cond-drain guard holds every swept regime inside the
reference's eps=0.02 envelope (``tdigest/histo_test.go:11-25``)."""

import jax.numpy as jnp
import numpy as np
import pytest

from veneur_tpu.analysis.tdigest_sweep import (INGESTS, run_config,
                                               sample_ingest)
from veneur_tpu.ops import tdigest as td


class TestShiftGuard:
    def test_pred_fires_on_disjoint_shift_only(self):
        rows = 8
        temp = td.init_temp(rows)
        flat = np.tile(np.arange(rows, dtype=np.int32), 64)
        low = np.random.default_rng(0).uniform(0, 10, flat.size)
        temp = td.ingest_chunk(temp, jnp.asarray(flat),
                               jnp.asarray(low.astype(np.float32)),
                               jnp.ones(flat.size, jnp.float32))
        # same range again: no shift
        assert not bool(td.shift_pred(
            *temp.anchors(), jnp.asarray(flat),
            jnp.asarray(low.astype(np.float32)),
            jnp.ones(flat.size, jnp.float32), rows))
        # disjoint range: shift
        assert bool(td.shift_pred(
            *temp.anchors(), jnp.asarray(flat),
            jnp.asarray((low + 1000).astype(np.float32)),
            jnp.ones(flat.size, jnp.float32), rows))
        # empty accumulator never triggers
        fresh = td.init_temp(rows)
        assert not bool(td.shift_pred(
            *fresh.anchors(), jnp.asarray(flat),
            jnp.asarray(low.astype(np.float32)),
            jnp.ones(flat.size, jnp.float32), rows))
        # nor do rows below the minimum accumulated mass (1-2 samples
        # make a point-range summary; any value would read disjoint —
        # the spurious-drain 4x ingest regression, round-5)
        tiny = td.init_temp(rows)
        tiny = td.ingest_chunk(tiny, jnp.asarray(flat[:rows]),
                               jnp.asarray(low[:rows].astype(np.float32)),
                               jnp.ones(rows, jnp.float32))
        assert not bool(td.shift_pred(
            *tiny.anchors(), jnp.asarray(flat),
            jnp.asarray((low + 1000).astype(np.float32)),
            jnp.ones(flat.size, jnp.float32), rows))

    def test_single_sample_chunks_never_vote(self):
        """A chunk bringing one sample per row cannot trip the guard:
        a lone stationary sample lands outside the segment-mean
        envelope ~20% of the time at small n, which would re-open the
        drain-churn regression for the realistic fleet shape
        (round-5 review finding)."""
        rows = 8
        temp = td.init_temp(rows)
        flat = np.tile(np.arange(rows, dtype=np.int32), 64)
        vals = np.random.default_rng(3).uniform(0, 10, flat.size)
        temp = td.ingest_chunk(temp, jnp.asarray(flat),
                               jnp.asarray(vals.astype(np.float32)),
                               jnp.ones(flat.size, jnp.float32))
        one = np.arange(rows, dtype=np.int32)
        # even a fully DISJOINT 1-sample-per-row chunk stays quiet...
        assert not bool(td.shift_pred(
            *temp.anchors(), jnp.asarray(one),
            jnp.full(rows, 1e6, jnp.float32),
            jnp.ones(rows, jnp.float32), rows))
        # ...while a >=4-sample disjoint chunk still fires
        four = np.repeat(np.arange(rows, dtype=np.int32), 4)
        assert bool(td.shift_pred(
            *temp.anchors(), jnp.asarray(four),
            jnp.full(four.size, 1e6, jnp.float32),
            jnp.ones(four.size, jnp.float32), rows))

    @pytest.mark.parametrize("ingest", INGESTS)
    def test_guarded_ingest_drains_into_digest(self, ingest):
        """A hard step change moves the accumulated bins into the digest
        (weight appears there) and the final quantiles stay accurate:
        by the shift guard alone (what the slab store runs), by the row
        drain alone (the rows are under ``ROW_DRAIN_MAX_ARRIVALS``) and
        by the dense store's sample path, which has both."""
        rows = 4
        n = 512
        rng = np.random.default_rng(1)
        vals = np.sort(rng.normal(100, 20, (rows, n)).astype(np.float32),
                       axis=1)
        digest = td.init((rows,))
        temp = td.init_temp(rows)
        guarded = sample_ingest(ingest, td.DEFAULT_COMPRESSION)
        chunks = 8
        per = n // chunks
        flat = np.repeat(np.arange(rows, dtype=np.int32), per)
        for c in range(chunks):
            part = vals[:, c * per:(c + 1) * per].reshape(-1)
            digest, temp = guarded(digest, temp, jnp.asarray(flat),
                                   jnp.asarray(part),
                                   jnp.ones(part.size, jnp.float32))
        # sorted arrival trips the guard: mass reached the digest
        # before the final drain
        assert float(jnp.sum(digest.weight)) > 0
        # interval stats survived the mid-interval guard drains
        np.testing.assert_allclose(np.asarray(temp.count),
                                   np.full(rows, n), rtol=1e-6)
        drained = td.drain_temp(digest, temp)
        pcts = np.asarray(td.quantile(
            drained, jnp.asarray([0.1, 0.5, 0.9], jnp.float32)))
        for r in range(rows):
            t_sorted = np.sort(vals[r])
            for qi, q in enumerate((0.1, 0.5, 0.9)):
                lo = np.searchsorted(t_sorted, pcts[r, qi], "left") / n
                hi = np.searchsorted(t_sorted, pcts[r, qi], "right") / n
                assert max(0.0, lo - q, q - hi) <= 0.02, (r, q)


class TestSweepEnvelope:
    """Small sweep cells asserting the documented envelope; the full
    sweep (python -m veneur_tpu.analysis.tdigest_sweep) regenerates
    docs/tdigest_accuracy.*."""

    def test_rows_past_the_row_drain_still_need_the_shift_guard(self):
        """Ordered arrival at 256 samples a row a chunk: from the sixth
        chunk on a row is past ``ROW_DRAIN_MAX_ARRIVALS`` and bins
        against its anchors; only the shift guard keeps the step out of
        them (PERF.md, PR 40: 0.149 without it)."""
        cells = {i: run_config("sorted_asc", 100.0, "binned16", "float32",
                               rows=4, n=4096, golden_rows=0, ingest=i)
                 for i in ("rowdrained", "rowdrained_unguarded")}
        assert cells["rowdrained"]["max_rank_err"] <= 0.02, cells
        assert cells["rowdrained_unguarded"]["max_rank_err"] > 0.05, cells

    def test_ordered_arrival_binned_within_envelope(self):
        cell = run_config("sorted_asc", 100.0, "binned16", "float32",
                          rows=4, n=1024, golden_rows=1)
        assert cell["max_rank_err"] <= 0.02, cell

    def test_stationary_binned_within_envelope(self):
        cell = run_config("lognormal", 100.0, "binned16", "bfloat16",
                          rows=4, n=1024, golden_rows=1)
        assert cell["max_rank_err"] <= 0.02, cell

    def test_fanin_within_envelope(self):
        cell = run_config("pareto", 100.0, "fanin8", "float32",
                          rows=4, n=1024, golden_rows=1)
        assert cell["max_rank_err"] <= 0.02, cell

    def test_low_compression_binned_within_envelope(self):
        """The lowest accepted compression (k=24 bins mapping onto the
        8 anchor segments) must stay inside a sane envelope — the
        regime where a round-5 review found an anchor-index underflow
        in an earlier (recomputed-summary) implementation."""
        cell = run_config("normal", 20.0, "binned16", "float32",
                          rows=4, n=1024, golden_rows=1)
        assert cell["max_rank_err"] <= 0.06, cell  # c=20 is coarse
