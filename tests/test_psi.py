"""PSI + Bloom filter: unit and property tests (claim C1)."""
import hashlib

import numpy as np
import pytest
from repro.testing.hypo import given, settings, strategies as st

from repro.core.bloom import BloomFilter
from repro.core.psi import (GROUPS, PSIClient, PSIServer, hash_to_group,
                            psi_intersect)

GROUP = "modp512"  # fast test group; protocol identical to modp2048


# ---------------------------------------------------------------------------
# Bloom filter
# ---------------------------------------------------------------------------


@given(st.sets(st.binary(min_size=1, max_size=32), min_size=1, max_size=200))
@settings(max_examples=50)
def test_bloom_no_false_negatives(items):
    bf = BloomFilter.for_capacity(len(items), 1e-6)
    bf.add_all(items)
    for it in items:
        assert it in bf


@given(st.integers(min_value=1, max_value=500))
@settings(max_examples=20)
def test_bloom_false_positive_rate(n):
    bf = BloomFilter.for_capacity(n, 1e-4)
    members = [f"member-{i}".encode() for i in range(n)]
    bf.add_all(members)
    trials = 2000
    fp = sum(f"non-member-{i}".encode() in bf for i in range(trials))
    assert fp / trials < 1e-2  # orders of magnitude slack over target 1e-4


def test_bloom_serialization_roundtrip():
    bf = BloomFilter(1024, 5)
    bf.add(b"x")
    bf2 = BloomFilter.from_bytes(bf.to_bytes(), 1024, 5)
    assert b"x" in bf2 and b"y" not in bf2


def test_bloom_rejects_bad_params():
    with pytest.raises(ValueError):
        BloomFilter(0, 3)


# ---------------------------------------------------------------------------
# DDH group
# ---------------------------------------------------------------------------


def test_hash_to_group_is_quadratic_residue():
    for g in ("modp512", "modp2048"):
        p, q, nb = GROUPS[g]
        h = hash_to_group(b"subject-1", p, nb)
        # elements of QR_p have order dividing q: h^q == 1
        assert pow(h, q, p) == 1


def test_blinding_commutes():
    p, q, nb = GROUPS[GROUP]
    h = hash_to_group(b"abc", p, nb)
    a, b = 12345, 67891
    assert pow(pow(h, a, p), b, p) == pow(pow(h, b, p), a, p)


# ---------------------------------------------------------------------------
# PSI protocol
# ---------------------------------------------------------------------------


@given(st.sets(st.text(min_size=1, max_size=12), min_size=0, max_size=40),
       st.sets(st.text(min_size=1, max_size=12), min_size=0, max_size=40))
@settings(max_examples=20)
def test_psi_equals_set_intersection(xs, ys):
    xs, ys = sorted(xs), sorted(ys)
    inter, _ = psi_intersect(xs, ys, group=GROUP)
    assert sorted(inter) == sorted(set(xs) & set(ys))


def test_psi_server_learns_only_cardinality():
    """The server's view is blinded group elements — distinct from the raw
    hashes, and the client's exponent never leaves the client."""
    client = PSIClient(["a", "b"], GROUP)
    blinded = client.blind()
    p, q, nb = GROUPS[GROUP]
    raw = [hash_to_group(x.encode(), p, nb) for x in ["a", "b"]]
    assert all(b != r for b, r in zip(blinded, raw))


def test_psi_bloom_compression_smaller_than_raw():
    server_items = [f"y{i}" for i in range(500)]
    _, stats = psi_intersect(["y1", "zz"], server_items, group=GROUP,
                             mode="bloom")
    assert stats["bloom_bytes"] < stats["uncompressed_server_set_bytes"]


def test_psi_2048_group_roundtrip():
    inter, _ = psi_intersect(["a", "b", "c"], ["b", "c", "d"])
    assert sorted(inter) == ["b", "c"]


def test_short_and_full_exponents_agree():
    """Short-exponent DH (the hot-loop lever) computes the same
    intersection as full-width exponents."""
    from repro.core.psi import psi_intersect
    xs = [f"id-{i}" for i in range(40)]
    ys = [f"id-{i + 20}" for i in range(40)]
    short, _ = psi_intersect(xs, ys, group="modp512")
    full, _ = psi_intersect(xs, ys, group="modp512", exp_bits=None)
    assert short == full == [f"id-{i + 20}" for i in range(20)]


def test_client_blind_is_memoized_and_reusable_across_owners():
    """One client -> many owners: the blinded upload is computed once
    and every owner round still yields the right intersection."""
    from repro.core.psi import PSIClient, PSIServer
    xs = [f"id-{i}" for i in range(30)]
    client = PSIClient(xs, "modp512")
    b1 = client.blind()
    assert client.blind() is b1              # memoized, not re-blinded
    for shift in (5, 10):
        ys = [f"id-{i + shift}" for i in range(30)]
        server = PSIServer(ys, group="modp512")
        inter = client.intersect(*server.respond(b1))
        assert inter == [f"id-{i}" for i in range(shift, 30)]


def test_server_bloom_cached_across_rounds():
    from repro.core.psi import PSIClient, PSIServer
    ys = [f"id-{i}" for i in range(25)]
    server = PSIServer(ys, group="modp512")
    c1 = PSIClient([f"id-{i}" for i in range(10)], "modp512")
    _, bf1 = server.respond(c1.blind())
    _, bf2 = server.respond(c1.blind())
    assert bf1 is bf2                        # built once per session
