"""Count-min + top-k heavy hitters (BASELINE config #5).

Golden-tested against an exact python Counter: count-min estimates are
upward-biased only, and with table width far above distinct-key count the
top-k must match the exact top-k identically.
"""

import collections

import jax.numpy as jnp
import numpy as np
import pytest

from veneur_tpu.core.store import MetricStore
from veneur_tpu.ops import countmin as cm
from veneur_tpu.ops import hll as hll_ops
from veneur_tpu.samplers import parser as p
from veneur_tpu.samplers.intermetric import HistogramAggregates

AGG = HistogramAggregates.from_names(["count"])


def _split(keys):
    keys = np.asarray(keys, np.uint64)
    return (jnp.asarray((keys >> np.uint64(32)).astype(np.uint32)),
            jnp.asarray((keys & np.uint64(0xFFFFFFFF)).astype(np.uint32)))


class TestCountMinKernel:
    def test_estimates_upper_bound_exact(self):
        rng = np.random.default_rng(0)
        ids = rng.integers(1, 1 << 62, 500, dtype=np.uint64)
        reps = rng.integers(1, 50, 500)
        stream = np.repeat(ids, reps)
        rng.shuffle(stream)
        sk = cm.init(1, depth=4, width=1 << 14, k=32)
        rows = jnp.zeros(len(stream), jnp.int32)
        hi, lo = _split(stream)
        sk = cm.update(sk, rows, rows.astype(jnp.uint32), hi, lo,
                       jnp.ones(len(stream), jnp.float32))
        qhi, qlo = _split(ids)
        est = np.asarray(cm.estimate(sk, jnp.zeros(500, jnp.int32), qhi, qlo))
        exact = collections.Counter(stream.tolist())
        want = np.array([exact[int(i)] for i in ids], np.float32)
        assert (est >= want - 1e-3).all()          # never underestimates
        assert (est <= want + len(stream) / (1 << 14) * 4 + 1).all()

    def test_topk_matches_exact_counter(self):
        rng = np.random.default_rng(1)
        # heavy hitters with clearly separated counts + background noise
        heavy = rng.integers(1, 1 << 62, 16, dtype=np.uint64)
        stream = []
        for i, h in enumerate(heavy):
            stream.extend([int(h)] * (1000 - 50 * i))
        noise = rng.integers(1, 1 << 62, 3000, dtype=np.uint64)
        stream.extend(noise.tolist())
        stream = np.array(stream, np.uint64)
        rng.shuffle(stream)
        sk = cm.init(1, depth=4, width=1 << 15, k=16)
        # several drains, as the store produces
        for part in np.array_split(stream, 7):
            hi, lo = _split(part)
            zr = jnp.zeros(len(part), jnp.int32)
            sk = cm.update(sk, zr, zr.astype(jnp.uint32), hi, lo,
                           jnp.ones(len(part), jnp.float32))
        got_ids = {(int(h) << 32) | int(l)
                   for h, l, c in zip(np.asarray(sk.topk_hi[0]),
                                      np.asarray(sk.topk_lo[0]),
                                      np.asarray(sk.topk_counts[0]))
                   if c > 0}
        assert got_ids == {int(h) for h in heavy}
        # counts within the count-min slack of exact
        exact = collections.Counter(stream.tolist())
        by_id = {(int(h) << 32) | int(l): float(c)
                 for h, l, c in zip(np.asarray(sk.topk_hi[0]),
                                    np.asarray(sk.topk_lo[0]),
                                    np.asarray(sk.topk_counts[0]))}
        slack = len(stream) / (1 << 15) * 4 + 1
        for hid, c in by_id.items():
            assert exact[hid] <= c <= exact[hid] + slack

    def test_per_series_isolation(self):
        """The shared table is salted by series row: two series counting
        the same keys keep independent top-k lists."""
        sk = cm.init(2, depth=4, width=1 << 14, k=8)
        keys = np.arange(1, 9, dtype=np.uint64) * 12345
        hi, lo = _split(np.tile(keys, 10))
        rows0 = jnp.zeros(80, jnp.int32)
        rows1 = jnp.ones(80, jnp.int32)
        sk = cm.update(sk, rows0, rows0.astype(jnp.uint32), hi, lo,
                       jnp.ones(80, jnp.float32))
        sk = cm.update(sk, rows1, rows1.astype(jnp.uint32), hi, lo,
                       jnp.full(80, 3.0, jnp.float32))
        c0 = np.sort(np.asarray(sk.topk_counts[0]))[-8:]
        c1 = np.sort(np.asarray(sk.topk_counts[1]))[-8:]
        assert np.allclose(c0, 10.0)
        assert np.allclose(c1, 30.0)


def _zipf_streams(seed, streams=4, lines=800, drains=2, keys=100_000,
                  zipf_s=0.99):
    """``drains`` batches of ``streams`` x ``lines`` key ids, Zipf over
    ``keys`` keys (float64 NumPy, nothing of veneur_tpu): a list of
    (rows, ids) and the exact frequency of every (stream, id)."""
    rng = np.random.default_rng(seed)
    law = np.arange(1, keys + 1, dtype=np.float64) ** -zipf_s
    law /= law.sum()
    names = rng.integers(1, 1 << 62, (streams, keys), dtype=np.uint64)
    batches, exact = [], collections.Counter()
    for _ in range(drains):
        rows = np.repeat(np.arange(streams), lines)
        ids = names[rows, rng.choice(keys, streams * lines, p=law)]
        order = rng.permutation(len(rows))
        batches.append((rows[order], ids[order]))
        exact.update(zip(rows.tolist(), ids.tolist()))
    return batches, exact


class TestTopkKeptByEstimate:
    """A drain brings a stream some 790 distinct candidates for K = 32
    places: the list is kept by estimate, so no key is left out whose
    exact frequency exceeds the list's last count, and no count is
    under the exact frequency (the parent scattered candidates into a
    4K ring by a hash, last writer wins, and lost keys of frequency
    10-101 beside rows of frequency 1)."""

    @pytest.mark.parametrize("seed", [0, 1, 2, 3])
    def test_no_heavy_hitter_left_out_no_undercount(self, seed):
        streams, k = 4, 32
        batches, exact = _zipf_streams(seed, streams)
        sk = cm.init(streams, depth=4, width=1 << 16, k=k)
        pad = 1024  # a chunk's padding: out-of-range rows, count 0
        for rows, ids in batches:
            hi, lo = _split(np.concatenate([ids, np.zeros(pad, np.uint64)]))
            rows = np.concatenate([rows, np.full(pad, streams)])
            sk = cm.update(
                sk, jnp.asarray(rows, jnp.int32),
                jnp.asarray(rows + 7, jnp.uint32), hi, lo,
                jnp.asarray(rows < streams, jnp.float32))
        got_ids = (np.asarray(sk.topk_hi).astype(np.uint64) << np.uint64(32)
                   | np.asarray(sk.topk_lo).astype(np.uint64))
        got_ct = np.asarray(sk.topk_counts)
        for r in range(streams):
            assert (got_ct[r] > 0).all()        # 1,000+ keys for 32 places
            listed = dict(zip(got_ids[r].tolist(), got_ct[r].tolist()))
            assert len(listed) == k             # no key twice
            under = [i for i, c in listed.items() if c < exact[(r, i)]]
            assert not under
            last = min(listed.values())
            missed = [(i, f) for (row, i), f in exact.items()
                      if row == r and i not in listed and f > last]
            assert not missed, (r, last, sorted(missed)[:5])

    def test_a_key_repeated_in_a_batch_is_one_candidate(self):
        """1,000 lines of 8 keys: every key once in the list, at its
        exact frequency."""
        rng = np.random.default_rng(5)
        ids = rng.integers(1, 1 << 62, 8, dtype=np.uint64)
        stream = np.repeat(ids, 125)
        rng.shuffle(stream)
        sk = cm.init(2, depth=4, width=1 << 14, k=4)
        hi, lo = _split(stream)
        rows = jnp.ones(len(stream), jnp.int32)
        sk = cm.update(sk, rows, rows.astype(jnp.uint32), hi, lo,
                       jnp.ones(len(stream), jnp.float32))
        assert (np.asarray(sk.topk_counts)[0] == 0).all()
        np.testing.assert_array_equal(np.asarray(sk.topk_counts)[1],
                                      [125.0] * 4)
        got = (np.asarray(sk.topk_hi)[1].astype(np.uint64) << np.uint64(32)
               | np.asarray(sk.topk_lo)[1].astype(np.uint64))
        assert len(set(got.tolist())) == 4 and set(got.tolist()) <= set(
            ids.tolist())


class TestHeavyHitterStore:
    def test_end_to_end_topk_emission(self):
        store = MetricStore(initial_capacity=16, chunk=256)
        rng = np.random.default_rng(4)
        exact = collections.Counter()
        users = [f"user{i}" for i in range(40)]
        weights = np.linspace(60, 2, 40)
        draws = rng.choice(40, 5000, p=weights / weights.sum())
        for d in draws:
            exact[users[d]] += 1
            store.process_metric(p.parse_metric(
                f"api.by_user:{users[d]}|s|#veneurtopk,env:prod".encode()))
        final, _, _ = store.flush([], AGG, is_local=True, now=7,
                                  forward=False)
        topk = {m.tags[-1].split(":", 1)[1]: m.value for m in final
                if m.name == "api.by_user.topk"}
        assert 0 < len(topk) <= 32
        # the exact heaviest keys must all be present with close counts
        for user, cnt in exact.most_common(10):
            assert user in topk
            assert topk[user] >= cnt
            assert topk[user] <= cnt + 5000 / (1 << 16) * 4 + 1
        # plain sets are unaffected
        store.process_metric(p.parse_metric(b"plain.set:m1|s"))
        final2, _, _ = store.flush([], AGG, is_local=False, now=8)
        by = {m.name: m.value for m in final2}
        assert by["plain.set"] == pytest.approx(1.0, rel=0.01)

    def test_native_batch_routing(self):
        native = pytest.importorskip("veneur_tpu.native")
        if not native.available():
            pytest.skip("no g++")
        store = MetricStore(initial_capacity=16, chunk=256)
        lines = []
        for i in range(300):
            lines.append(f"hh.keys:k{i % 5}|s|#veneurtopk")
            lines.append(f"hh.card:k{i}|s")
        batch = native.parse_lines("\n".join(lines).encode())
        store.process_batch(batch)
        final, _, _ = store.flush([], AGG, is_local=False, now=9)
        topk = {m.tags[-1].split(":", 1)[1]: m.value for m in final
                if m.name == "hh.keys.topk"}
        assert set(topk) == {f"k{i}" for i in range(5)}
        for v in topk.values():
            assert v >= 60.0
        by = {m.name: m.value for m in final}
        assert abs(by["hh.card"] - 300) / 300 < 0.05  # HLL estimate

    def test_topk_tag_does_not_clobber_other_types_scope(self):
        """veneurtopk only reroutes SETS; a global counter carrying the
        tag must stay global on the native path (round-2 review
        regression)."""
        native = pytest.importorskip("veneur_tpu.native")
        if not native.available():
            pytest.skip("no g++")
        b = native.parse_lines(b"c.x:1|c|#veneurglobalonly,veneurtopk")
        assert b.count == 1
        assert int(b.scope[0]) == p.GLOBAL_ONLY
        store = MetricStore(initial_capacity=8, chunk=32)
        store.process_batch(b)
        assert len(store.global_counters) == 1
        assert len(store.heavy_hitters) == 0

    def test_member_memo_bound_falls_back_to_hex(self):
        store = MetricStore(initial_capacity=8, chunk=64)
        g = store.heavy_hitters
        g.MEMO_LIMIT = 3  # tiny bound for the test
        for i in range(10):
            for _ in range(10 - i):
                store.process_metric(p.parse_metric(
                    f"m.k:member{i}|s|#veneurtopk".encode()))
        final, _, _ = store.flush([], AGG, is_local=True, now=1,
                                  forward=False)
        names = [m.tags[-1] for m in final if m.name == "m.k.topk"]
        assert len(names) == 10
        hexed = [t for t in names if t.startswith("key:0x")]
        memoed = [t for t in names if not t.startswith("key:0x")]
        assert len(memoed) == 3 and len(hexed) == 7

    def test_growth(self):
        store = MetricStore(initial_capacity=2, chunk=32)
        for i in range(20):
            store.process_metric(p.parse_metric(
                f"grow.h{i}:k|s|#veneurtopk".encode()))
        final, _, _ = store.flush([], AGG, is_local=True, now=1,
                                  forward=False)
        topk = [m for m in final if m.name.endswith(".topk")]
        assert len(topk) == 20
        for m in topk:
            assert m.value == 1.0


class TestHeavyHitterMerge:
    """Satellite: heavy-hitter state MOVES on a handoff/replication
    merge — ``restore_state`` adds the count-min tables element-wise
    and re-enters each series' top-k candidates, so a resized peer or
    a promoted standby keeps serving fleet top-k. Estimates stay
    upward-biased only, with the merged overcount bounded by
    ``e/w · ΣN`` (docs/tiered.md "Merging count-min tables")."""

    def test_merge_matches_merged_oracle_within_cm_bound(self):
        import math

        rng = np.random.default_rng(11)
        exact = collections.Counter()
        stores = []
        users = [f"u{i}" for i in range(30)]
        weights = np.linspace(50, 2, 30)
        for _ in range(2):
            store = MetricStore(initial_capacity=16, chunk=256)
            draws = rng.choice(30, 3000, p=weights / weights.sum())
            for d in draws:
                exact[users[d]] += 1
                store.process_metric(p.parse_metric(
                    f"api.hh:{users[d]}|s|#veneurtopk".encode()))
            stores.append(store)
        a, b = stores
        # the exact group snapshot the handoff wire / the standby's
        # replication stream carries
        groups = {"heavy_hitters": a.heavy_hitters.snapshot_state()}
        from veneur_tpu.fleet.standby import PROMOTABLE_GROUPS
        assert "heavy_hitters" in PROMOTABLE_GROUPS
        assert b.restore_state(groups) > 0
        final, _, _ = b.flush([], AGG, is_local=True, now=1,
                              forward=False)
        topk = {m.tags[-1].split(":", 1)[1]: m.value for m in final
                if m.name == "api.hh.topk"}
        width = np.asarray(groups["heavy_hitters"]["table"]).shape[-1]
        total = sum(exact.values())
        slack = math.e / width * total + 1.0
        for user, cnt in exact.most_common(10):
            assert user in topk
            # upward-biased only, within the merged-table CM bound
            assert cnt <= topk[user] <= cnt + slack


class TestTopkForwarding:
    """Fleet aggregation of heavy hitters: two locals forward their
    sketches (count-min table + top-k candidates) through the JSON wire;
    the global's fleet top-k counts are the SUMS of per-host counts —
    the merge path the store docstring used to disclaim."""

    def _local_with(self, counts: dict):
        store = MetricStore(initial_capacity=16, chunk=256)
        for member, n in counts.items():
            for _ in range(n):
                store.process_metric(p.parse_metric(
                    f"api.callers:{member}|s|#veneurtopk".encode()))
        return store

    def test_fleet_topk_sums_across_hosts(self):
        from veneur_tpu.forward.convert import (apply_json_metric,
                                                json_metrics_from_state)

        # host A and host B see overlapping key sets
        a = self._local_with({"alice": 30, "bob": 10, "carol": 2})
        b = self._local_with({"alice": 5, "bob": 25, "dave": 7})
        gstore = MetricStore(initial_capacity=16, chunk=256)
        for local in (a, b):
            _, fwd, _ = local.flush([], AGG, is_local=True, now=0,
                                    forward=True)
            assert fwd.topk is not None
            # through the real JSON wire format (serialize + parse)
            import json as _json

            payload = _json.loads(_json.dumps(
                json_metrics_from_state(fwd)))
            for d in payload:
                apply_json_metric(gstore, d)

        final, _, _ = gstore.flush([], AGG, is_local=False, now=1,
                                   forward=False)
        got = {m.tags[-1].split(":", 1)[1]: m.value
               for m in final if m.name == "api.callers.topk"}
        # count-min estimates are upward-biased only; at this load the
        # tables are collision-free, so sums are exact
        assert got["alice"] == 35.0
        assert got["bob"] == 35.0
        assert got["carol"] == 2.0
        assert got["dave"] == 7.0

    def test_fleet_topk_over_grpc(self):
        """The sketch also rides gRPC, as the MetricList.topk extension
        (skipped by a reference global), through the real transport +
        the native import lane."""
        from veneur_tpu.forward import GRPCForwarder, ImportServer

        a = self._local_with({"alice": 30, "bob": 10})
        b = self._local_with({"alice": 5, "bob": 25, "dave": 7})
        gstore = MetricStore(initial_capacity=16, chunk=256)
        srv = ImportServer(gstore)
        port = srv.start("127.0.0.1:0")
        try:
            client = GRPCForwarder(f"127.0.0.1:{port}")
            assert client.supports_topk
            for local in (a, b):
                _, fwd, _ = local.flush([], AGG, is_local=True, now=0,
                                        forward=True)
                assert fwd.topk is not None
                client.forward(fwd)
            assert client.errors == 0
            final, _, _ = gstore.flush([], AGG, is_local=False, now=1,
                                       forward=False)
            got = {m.tags[-1].split(":", 1)[1]: m.value
                   for m in final if m.name == "api.callers.topk"}
            assert got["alice"] == 35.0
            assert got["bob"] == 35.0
            assert got["dave"] == 7.0
        finally:
            srv.stop()

    def test_reference_compat_suppresses_topk_field(self):
        from veneur_tpu.forward import GRPCForwarder
        from veneur_tpu.forward.convert import metric_list_from_state

        a = self._local_with({"alice": 3})
        _, fwd, _ = a.flush([], AGG, is_local=True, now=0, forward=True)
        assert fwd.topk is not None
        assert metric_list_from_state(fwd).HasField("topk")
        assert not metric_list_from_state(
            fwd, reference_compat=True).HasField("topk")
        compat = GRPCForwarder("127.0.0.1:1", reference_compat=True)
        assert not compat.supports_topk

    def test_fleet_topk_survives_different_intern_orders(self):
        """Regression: table columns are salted with the STABLE series
        id, not the local row index — host A interning m1 then m2 and
        host B interning only m2 (row 0) must still sum m2's counts."""
        from veneur_tpu.forward.convert import (apply_json_metric,
                                                json_metrics_from_state)

        a = MetricStore(initial_capacity=16, chunk=256)
        for _ in range(3):
            a.process_metric(p.parse_metric(b"m1:x|s|#veneurtopk"))
        for _ in range(10):
            a.process_metric(p.parse_metric(b"m2:bob|s|#veneurtopk"))
        b = MetricStore(initial_capacity=16, chunk=256)
        for _ in range(25):
            b.process_metric(p.parse_metric(b"m2:bob|s|#veneurtopk"))

        gstore = MetricStore(initial_capacity=16, chunk=256)
        # interleave so the global also interns m2 at a different row
        # than host A did
        gstore.process_metric(p.parse_metric(b"zzz:pad|s|#veneurtopk"))
        for local in (a, b):
            _, fwd, _ = local.flush([], AGG, is_local=True, now=0,
                                    forward=True)
            for d in json_metrics_from_state(fwd):
                apply_json_metric(gstore, d)
        final, _, _ = gstore.flush([], AGG, is_local=False, now=1,
                                   forward=False)
        got = {(m.name, m.tags[-1]): m.value for m in final
               if m.name.endswith(".topk")}
        assert got[("m2.topk", "key:bob")] == 35.0
        assert got[("m1.topk", "key:x")] == 3.0

    def test_import_rejects_mismatched_shape(self):
        gstore = MetricStore(initial_capacity=16, chunk=256)
        with pytest.raises(ValueError, match="shape"):
            gstore.import_topk(np.zeros((2, 128), np.float32), [])

    def test_forward_disabled_keeps_topk_local(self):
        a = self._local_with({"x": 3})
        _, fwd, _ = a.flush([], AGG, is_local=True, now=0, forward=False)
        assert fwd.topk is None
