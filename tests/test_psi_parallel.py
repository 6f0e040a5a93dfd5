"""The scalable PSI engine: chunked/parallel rounds must be bit-identical
to the serial path, degrade gracefully without gmpy2 or fork, and keep
the in-flight working set bounded (ISSUE 4 tentpole)."""
import importlib.util
import sys

import numpy as np
from repro.testing.hypo import given, settings, strategies as st

from repro.core import modexp
from repro.core.bloom import BloomFilter, ShardedBloom
from repro.core.modexp import ModexpPool, pack_ints, unpack_ints
from repro.core.psi import PSIClient, PSIServer, psi_intersect, psi_round

GROUP = "modp512"  # fast test group; protocol identical to modp2048


def _reset(client, server):
    """Drop per-session caches so a re-run recomputes every leg with the
    SAME secrets — what bit-identity must survive."""
    client.reset_session()
    server.reset_session()


# ---------------------------------------------------------------------------
# Serial == chunked == parallel (bit-identical)
# ---------------------------------------------------------------------------


@given(st.lists(st.text(min_size=1, max_size=10), min_size=0, max_size=60),
       st.lists(st.text(min_size=1, max_size=10), min_size=0, max_size=60),
       st.integers(1, 17))
@settings(max_examples=12)
def test_chunked_round_bit_identical_to_serial(xs, ys, chunk):
    """Random uneven sets (duplicates allowed): every chunk size yields
    the exact same intersection list — same elements, same order, same
    duplicate multiplicity — as the one-chunk serial round."""
    client = PSIClient(xs, GROUP)
    server = PSIServer(ys, group=GROUP)
    ref, _ = psi_round(client, server, chunk_size=max(len(xs), 1))
    _reset(client, server)
    got, stats = psi_round(client, server, chunk_size=chunk)
    assert got == ref
    assert sorted(set(got)) == sorted(set(xs) & set(ys))
    assert stats["n_chunks"] == max(1, -(-len(xs) // chunk))


def test_parallel_round_bit_identical_to_serial():
    xs = [f"id-{i}" for i in range(400)] + ["dup"] * 3
    ys = [f"id-{i + 150}" for i in range(400)] + ["dup"]
    client = PSIClient(xs, GROUP)
    server = PSIServer(ys, group=GROUP)
    ref, _ = psi_round(client, server, chunk_size=64)
    _reset(client, server)
    with ModexpPool(2) as pool:
        got, stats = psi_round(client, server, pool=pool, chunk_size=64)
    assert got == ref
    assert got.count("dup") == 3                 # client-side multiplicity
    if stats["parallelism"]:                     # host allowed fork
        assert stats["parallelism"] == 2


def test_empty_intersection_and_empty_sets():
    for xs, ys in ([["a", "b"], ["c", "d"]], [[], ["a"]], [["a"], []],
                   [[], []]):
        for par in (0, 2):
            inter, _ = psi_intersect(xs, ys, group=GROUP, chunk_size=1,
                                     parallelism=par)
            assert inter == []


def test_memoized_blind_survives_engine_switch():
    """The packed blinded set computed by the serial engine is reused
    verbatim by the parallel engine (one session, many owners)."""
    client = PSIClient([f"id-{i}" for i in range(50)], GROUP)
    s1 = PSIServer([f"id-{i + 10}" for i in range(50)], group=GROUP)
    i1, st1 = psi_round(client, s1, chunk_size=16)
    blob = client._blinded_packed
    with ModexpPool(2) as pool:
        s2 = PSIServer([f"id-{i + 20}" for i in range(50)], group=GROUP)
        i2, st2 = psi_round(client, s2, pool=pool, chunk_size=16)
    assert client._blinded_packed is blob        # never recomputed
    assert not st1["blind_cached"] and st2["blind_cached"]
    assert i2 == [f"id-{i}" for i in range(20, 50)]


# ---------------------------------------------------------------------------
# Protocol variants
# ---------------------------------------------------------------------------


@given(st.lists(st.text(min_size=1, max_size=8), min_size=0, max_size=40),
       st.lists(st.text(min_size=1, max_size=8), min_size=0, max_size=40))
@settings(max_examples=10)
def test_noinv_and_bloom_modes_agree(xs, ys):
    """Both protocol variants (inverse-free double-blinded comparison vs
    Bloom-compressed unblinding) recover the same intersection, with the
    same client-order + duplicate semantics."""
    noinv, s1 = psi_intersect(xs, ys, group=GROUP, mode="noinv",
                              chunk_size=7)
    bloom, s2 = psi_intersect(xs, ys, group=GROUP, mode="bloom",
                              chunk_size=7)
    assert noinv == bloom
    assert s1["mode"] == "noinv" and s2["mode"] == "bloom"


def test_noinv_trades_wire_for_compute():
    """The variant table's claim: bloom mode compresses the server set
    ~12x; noinv ships it raw but runs no full-width exponent."""
    xs = [f"a{i}" for i in range(300)]
    ys = [f"a{i + 100}" for i in range(300)]
    _, sn = psi_intersect(xs, ys, group=GROUP, mode="noinv")
    _, sb = psi_intersect(xs, ys, group=GROUP, mode="bloom")
    assert sn["server_set_bytes"] == sn["uncompressed_server_set_bytes"]
    assert sb["bloom_bytes"] * 8 < sb["uncompressed_server_set_bytes"]
    assert sn["server_response_bytes"] > sb["server_response_bytes"]


def test_noinv_client_through_bloom_compat_surface():
    """A noinv-mode client driven through the legacy blind/respond/
    intersect API lazily inverts its exponent and still succeeds."""
    client = PSIClient(["a", "b", "c"], GROUP)          # default: noinv
    server = PSIServer(["b", "c", "d"], group=GROUP)
    double, bf = server.respond(client.blind())
    assert client.intersect(double, bf) == ["b", "c"]


# ---------------------------------------------------------------------------
# Graceful degradation
# ---------------------------------------------------------------------------


def test_gmpy2_absent_fallback(monkeypatch):
    """With gmpy2 unimportable, the backend is the builtin pow and the
    whole protocol still computes the same integers."""
    monkeypatch.setitem(sys.modules, "gmpy2", None)  # import -> ImportError
    spec = importlib.util.find_spec("repro.core.modexp")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    assert mod.HAVE_GMPY2 is False
    assert mod.powmod(12345, 678, 1009) == pow(12345, 678, 1009)
    out = mod.pow_chunk((pack_ints([7, 11], 8), 3, 1000003, 8))
    assert unpack_ints(out, 8) == [pow(7, 3, 1000003),
                                   pow(11, 3, 1000003)]


def test_backend_matches_builtin_pow():
    """Whatever backend is live (gmpy2 or builtin), it agrees with pow."""
    p = 2 ** 127 - 1
    for base, exp in [(3, 65537), (p - 2, p - 2), (1, 0)]:
        assert modexp.powmod(base, exp, p) == pow(base, exp, p)


def test_pool_fork_failure_degrades_to_serial(monkeypatch):
    import concurrent.futures as cf

    def boom(*a, **k):
        raise OSError("no fork for you")

    monkeypatch.setattr(cf, "ProcessPoolExecutor", boom)
    pool = ModexpPool(4)
    assert not pool.is_parallel
    assert "no fork for you" in pool.fallback_reason
    inter, stats = psi_intersect(["a", "b", "c"], ["b", "c", "d"],
                                 group=GROUP, pool=pool)
    assert inter == ["b", "c"] and stats["parallelism"] == 0


def test_imap_bounded_lookahead():
    """The pool never pulls more than ``inflight`` tasks ahead of the
    consumer — the property that bounds peak memory for 1e6-ID streams."""
    pool = ModexpPool(0)                         # serial: lookahead 1
    pulled, consumed = [], []

    def tasks():
        for i in range(20):
            pulled.append(i)
            yield (pack_ints([i + 2], 8), 3, 1000003, 8)

    for out in pool.imap(modexp.pow_chunk, tasks()):
        consumed.append(out)
        assert len(pulled) - len(consumed) <= max(pool.inflight, 1)
    assert len(consumed) == 20


def test_round_reports_bounded_inflight():
    xs = [f"x{i}" for i in range(1000)]
    client = PSIClient(xs, GROUP)
    server = PSIServer(xs[::2], group=GROUP)
    _, stats = psi_round(client, server, chunk_size=128)
    assert stats["peak_inflight_elements"] <= 128 * ModexpPool(0).inflight
    assert stats["peak_inflight_elements"] < len(xs)


# ---------------------------------------------------------------------------
# Sharded bloom
# ---------------------------------------------------------------------------


@given(st.sets(st.binary(min_size=1, max_size=24), min_size=1, max_size=300),
       st.integers(1, 5))
@settings(max_examples=15)
def test_sharded_bloom_no_false_negatives(items, shards):
    items = sorted(items)
    bf = ShardedBloom.for_capacity(len(items), 1e-6, n_shards=shards)
    bf.add_batch(items)
    assert bf.query_batch(items).all()
    for it in items[:10]:
        assert it in bf                          # scalar path agrees


def test_sharded_bloom_parallel_build_merge_equals_serial():
    items = [f"m{i}".encode() for i in range(500)]
    whole = ShardedBloom.for_capacity(500, 1e-6, n_shards=4)
    whole.add_batch(items)
    a = ShardedBloom.for_capacity(500, 1e-6, n_shards=4)
    b = ShardedBloom.for_capacity(500, 1e-6, n_shards=4)
    a.add_batch(items[:250])
    b.add_batch(items[250:])
    merged = a.merge(b)
    for s1, s2 in zip(whole.shards, merged.shards):
        np.testing.assert_array_equal(s1.bits, s2.bits)


def test_sharded_bloom_frames_bound_message_size():
    bf = ShardedBloom.for_capacity(200_000, 1e-9)
    frames = bf.shard_frames()
    assert len(frames) == bf.n_shards > 1
    assert sum(len(f) for f in frames) == bf.nbytes()
    assert max(len(f) for f in frames) < 300 * 1024   # streamable frames


def test_bloom_scalar_and_batch_paths_agree():
    bf = BloomFilter.for_capacity(64, 1e-6)
    items = [f"i{i}".encode() for i in range(64)]
    bf.add_batch(items[:32])
    for it in items[32:]:
        bf.add(it)
    batch = bf.query_batch(items)
    assert batch.all()
    assert all(it in bf for it in items)


# ---------------------------------------------------------------------------
# resolve() surfaces
# ---------------------------------------------------------------------------


def test_resolution_parallel_matches_serial():
    from repro.core.resolution import VerticalDataset, resolve
    rng = np.random.default_rng(0)
    ids = [f"s{i}" for i in range(120)]
    sci = VerticalDataset(ids, rng.integers(0, 9, 120))
    owners = {f"o{k}": VerticalDataset(
        [ids[i] for i in rng.permutation(120)[:90]],
        rng.normal(size=(90, 3)).astype(np.float32)) for k in range(3)}
    ser = resolve(sci, owners, group=GROUP)
    par = resolve(sci, owners, group=GROUP, parallelism=2, chunk_size=17)
    assert ser[0].ids == par[0].ids
    assert ser[2]["global_intersection"] == par[2]["global_intersection"]
    for name in owners:
        assert ser[1][name].ids == par[1][name].ids


def test_session_resolve_parallel_matches_serial():
    from repro.data import make_vertical_mnist_parties
    from repro.federation import VerticalSession, feature_parties

    def build():
        sci, owners = make_vertical_mnist_parties(240, seed=3,
                                                  keep_frac=0.8)
        return VerticalSession(*feature_parties(sci, owners))

    s_ser, s_par = build(), build()
    st_ser = s_ser.resolve(group=GROUP)
    st_par = s_par.resolve(group=GROUP, parallelism=2, chunk_size=37)
    assert s_ser.scientist.ids == s_par.scientist.ids
    assert (st_ser["global_intersection"]
            == st_par["global_intersection"])
    kinds = {m["kind"] for m in s_par.transcript}
    assert {"psi_blind_chunk", "psi_double_chunk",
            "psi_server_set_chunk"} <= kinds     # default mode: noinv


def test_session_resolve_reuses_blind_across_owners():
    from repro.data import make_vertical_mnist_parties
    from repro.federation import VerticalSession, feature_parties
    sci, owners = make_vertical_mnist_parties(150, seed=1, n_owners=2)
    session = VerticalSession(*feature_parties(sci, owners))
    stats = session.resolve(group=GROUP, chunk_size=32)
    cached = [r["blind_cached"] for r in stats["rounds"]]
    assert cached == [False, True]               # paid once, reused after
