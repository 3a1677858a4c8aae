"""Property tests for the paper's schedule generators (§2)."""

import math

import pytest

from hypothesis import given, settings, strategies as st

from repro.core import schedule as S
from repro.core.topology import Topology, log_radix

ps = st.integers(min_value=2, max_value=40)
ks = st.integers(min_value=1, max_value=6)
cs = st.integers(min_value=1, max_value=1000)
Ns = st.integers(min_value=2, max_value=8)
ns = st.integers(min_value=2, max_value=8)


@settings(max_examples=60, deadline=None)
@given(p=ps, k=ks, c=cs, root=st.integers(0, 1000))
def test_kported_broadcast(p, k, c, root):
    root = root % p
    sch = S.kported_broadcast(p, k, c, root=root)
    S.verify_broadcast(sch, root=root)
    assert sch.num_rounds == log_radix(p, k + 1)
    assert sch.max_port_width() <= k
    # broadcast sends the full payload on every edge
    assert all(m.elems == c for r in sch.rounds for m in r.msgs)


@settings(max_examples=60, deadline=None)
@given(p=ps, k=ks, c=cs, root=st.integers(0, 1000))
def test_kported_scatter(p, k, c, root):
    root = root % p
    sch = S.kported_scatter(p, k, c, root=root)
    S.verify_scatter(sch, root=root)
    assert sch.num_rounds == log_radix(p, k + 1)
    # message-size optimality: every block leaves the root exactly once and
    # travels a shrinking-range path — total volume <= c * p * rounds, and
    # volume leaving the root is exactly c*(p-1).
    root_out = sum(m.elems for r in sch.rounds for m in r.msgs if m.src == root)
    assert root_out == c * (p - 1)


@settings(max_examples=30, deadline=None)
@given(p=st.integers(2, 24), k=ks, c=cs)
def test_kported_alltoall(p, k, c):
    sch = S.kported_alltoall(p, k, c)
    S.verify_alltoall(sch)
    assert sch.num_rounds == math.ceil((p - 1) / k)
    # volume optimal: each of the p*(p-1) blocks moves exactly once
    assert sch.total_elems() == c * p * (p - 1)


@settings(max_examples=30, deadline=None)
@given(p=st.integers(2, 24), k=ks, c=cs)
def test_bruck_alltoall(p, k, c):
    sch = S.bruck_alltoall(p, k, c)
    S.verify_alltoall(sch)
    assert sch.num_rounds == log_radix(p, k + 1)
    # message combining trades volume for rounds: volume >= direct's
    assert sch.total_elems() >= c * p * (p - 1) or p == 2


@settings(max_examples=30, deadline=None)
@given(N=Ns, n=ns, k=ks, c=cs)
def test_klane_broadcast_scatter(N, n, k, c):
    topo = Topology(N, n, min(2, n))
    k = min(k, n)
    sb = S.klane_broadcast(topo, k, c)
    S.verify_broadcast(sb)
    ss = S.klane_scatter(topo, k, c)
    S.verify_scatter(ss)


@settings(max_examples=20, deadline=None)
@given(N=Ns, n=ns, c=cs)
def test_fulllane_family_lane_legal(N, n, c):
    """Full-lane and k-lane alltoall schedules must be 1-ported per
    processor (the lane model's constraint)."""
    topo = Topology(N, n, min(2, n))
    for sch in [
        S.fulllane_broadcast(topo, c),
        S.fulllane_scatter(topo, c),
        S.fulllane_alltoall(topo, c),
        S.klane_alltoall(topo, c),
    ]:
        assert sch.max_port_width() == 1, (sch.op, sch.algorithm)
    S.verify_broadcast(S.fulllane_broadcast(topo, c))
    S.verify_scatter(S.fulllane_scatter(topo, c))
    S.verify_alltoall(S.fulllane_alltoall(topo, c))
    S.verify_alltoall(S.klane_alltoall(topo, c))


def test_fulllane_scatter_round_optimal():
    """Paper §2.2: ceil(log n) + ceil(log N) rounds, at most one off optimal."""
    topo = Topology(8, 16, 2)
    sch = S.fulllane_scatter(topo, 4)
    assert sch.num_rounds <= math.ceil(math.log2(16)) + math.ceil(math.log2(8))


def test_fulllane_alltoall_double_volume():
    """Paper §2.2: the full-lane alltoall communicates (nearly) all data
    twice.  Exactly: per source proc, same-node blocks (n-1) and same-lane
    cross-node blocks (N-1) move once; the remaining (n-1)(N-1) move twice."""
    topo = Topology(4, 4, 2)
    N, n = topo.num_nodes, topo.procs_per_node
    c = 5
    sch = S.fulllane_alltoall(topo, c)
    per_proc = (n - 1) + (N - 1) + 2 * (n - 1) * (N - 1)
    assert sch.total_elems() == c * topo.p * per_proc


def test_paper_scale_verifies():
    """The Hydra configuration: N=36, n=32, p=1152."""
    topo = Topology(36, 32, 2)
    S.verify_broadcast(S.kported_broadcast(1152, 6, 10))
    S.verify_broadcast(S.klane_broadcast(topo, 6, 10))
    S.verify_broadcast(S.fulllane_broadcast(topo, 1000))
    S.verify_scatter(S.fulllane_scatter(topo, 9))
